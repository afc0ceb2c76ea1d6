"""Mixed-precision mesh solve tests (ISSUE 8).

The acceptance surface of the f32-factor + f64-refine rebuild
(parallel/dist_refine.py):

- Option.MixedPrecision=off is jaxpr-IDENTICAL to the direct f64
  gesv_mesh/posv_mesh path; auto (the default) factors in f32 and meets
  the refine.py residual gate ||r|| <= ||x|| * ||A|| * eps * sqrt(n).
- The fused refinement loop performs ZERO host syncs per iteration
  (transfer-guard dispatch of the warm program).
- Ill-conditioned escalation: IR fails -> GMRES-IR -> full-f64 fallback,
  with the ir.* counters recording the ladder.
- opts threading: the mixed solve is bitwise-invariant under
  Lookahead x BcastImpl (every component kernel is), and the Pallas
  panel lowering still meets the residual gate.
- The Ozaki residual SUMMA is bitwise-stable across mesh shapes and its
  comm-audit wire bytes are exactly slice_count/8 x the plain f64 SUMMA
  volume (per BcastImpl factor), proven analytically.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from slate_tpu.parallel import make_mesh
from slate_tpu.parallel.comm import comm_audit
from slate_tpu.parallel.dist import from_dense, to_dense
from slate_tpu.parallel.dist_refine import (
    residual_comm_bytes,
    resolve_mixed,
    use_mixed,
)
from slate_tpu.parallel.drivers import (
    _gesv_mesh_plain,
    _posv_mesh_plain,
    gesv_mesh,
    gesv_mixed_gmres_mesh,
    gesv_mixed_mesh,
    posv_mesh,
    posv_mixed_mesh,
)
from slate_tpu.types import Option

from conftest import cpu_devices

N, NB, NRHS = 96, 16, 2


def mesh24():
    return make_mesh(2, 4, devices=cpu_devices(8))


def _well(rng):
    a = rng.standard_normal((N, N)) + N * np.eye(N)
    return jnp.asarray(a)


def _spd(rng):
    g = rng.standard_normal((N, N))
    return jnp.asarray(g @ g.T / N + 2 * np.eye(N))


def _cond(rng, c):
    q1, _ = np.linalg.qr(rng.standard_normal((N, N)))
    q2, _ = np.linalg.qr(rng.standard_normal((N, N)))
    s = np.logspace(0, -np.log10(c), N)
    return jnp.asarray(q1 @ np.diag(s) @ q2)


def _rhs(rng, k=NRHS):
    return jnp.asarray(rng.standard_normal((N, k)))


def _gate(a, x, b):
    """The refine.py residual gate: ||r||inf <= ||x||inf ||A||inf eps sqrt(n)."""
    a, x, b = map(np.asarray, (a, x, b))
    r = b - a @ x
    rnorm = np.abs(r).sum(axis=1).max()
    xnorm = np.abs(x).sum(axis=1).max()
    anorm = np.abs(a).sum(axis=1).max()
    return rnorm <= xnorm * anorm * np.finfo(np.float64).eps * np.sqrt(N)


# ---------------------------------------------------------------------------
# off-switch: trace identity with the direct path; auto: default-on
# ---------------------------------------------------------------------------


def test_resolve_chain_defaults_to_auto():
    assert resolve_mixed(None) == "auto"
    assert resolve_mixed({Option.MixedPrecision: "off"}) == "off"
    with use_mixed("ir"):
        assert resolve_mixed(None) == "ir"
        assert resolve_mixed({Option.MixedPrecision: "gmres"}) == "gmres"
    with pytest.raises(ValueError):
        resolve_mixed({Option.MixedPrecision: "sometimes"})


@pytest.mark.parametrize("kind", ["gesv", "posv"])
def test_off_is_jaxpr_identical_to_direct_path(kind, rng):
    mesh = mesh24()
    a = _well(rng) if kind == "gesv" else _spd(rng)
    b = _rhs(rng)
    off = {Option.MixedPrecision: "off"}
    drv = gesv_mesh if kind == "gesv" else posv_mesh
    plain = _gesv_mesh_plain if kind == "gesv" else _posv_mesh_plain
    j_off = jax.make_jaxpr(lambda x, y: drv(x, y, mesh, NB, opts=off))(a, b)
    j_plain = jax.make_jaxpr(lambda x, y: plain(x, y, mesh, NB, opts=off))(a, b)
    assert str(j_off) == str(j_plain)


def test_traced_f64_driver_keeps_direct_path(rng):
    # the ladder is host-driven (per-tier convergence readbacks between
    # programs): under an outer trace there is no host, so a traced f64
    # call must keep the direct path — same jaxpr as before the routing
    # existed, and jit over the public driver must still work
    mesh = mesh24()
    a = _spd(rng)
    b = _rhs(rng)
    j_auto = jax.make_jaxpr(lambda x, y: posv_mesh(x, y, mesh, NB))(a, b)
    j_plain = jax.make_jaxpr(lambda x, y: _posv_mesh_plain(x, y, mesh, NB))(a, b)
    assert str(j_auto) == str(j_plain)
    x, info = jax.jit(lambda x, y: posv_mesh(x, y, mesh, NB))(a, b)
    assert int(info) == 0
    assert _gate(a, x, b)


def test_non_f64_never_routes(rng):
    # f32 input: no mixed tier exists below it — direct path, identical
    mesh = mesh24()
    a = _spd(rng).astype(jnp.float32)
    b = _rhs(rng).astype(jnp.float32)
    j_auto = jax.make_jaxpr(lambda x, y: posv_mesh(x, y, mesh, NB))(a, b)
    j_plain = jax.make_jaxpr(lambda x, y: _posv_mesh_plain(x, y, mesh, NB))(a, b)
    assert str(j_auto) == str(j_plain)


@pytest.mark.parametrize("kind", ["gesv", "posv"])
def test_auto_routes_through_f32_factor_and_meets_gate(kind, rng):
    from slate_tpu.obs import REGISTRY

    mesh = mesh24()
    a = _well(rng) if kind == "gesv" else _spd(rng)
    b = _rhs(rng)
    drv = gesv_mesh if kind == "gesv" else posv_mesh
    before = REGISTRY.counter_value("ir.solves", op=kind)
    x, info = drv(a, b, mesh, NB)  # default = auto: the mixed ladder
    assert int(info) == 0
    assert _gate(a, x, b)
    # the ladder ran (the ir.* surface is how a service observes it)
    assert REGISTRY.counter_value("ir.solves", op=kind) == before + 1
    # and the refinement really did the work from an f32 factor: the
    # mixed driver agrees with the routed result bitwise (same programs)
    mixed_drv = gesv_mixed_mesh if kind == "gesv" else posv_mixed_mesh
    x2, iters, info2 = mixed_drv(a, b, mesh, NB)
    assert int(info2) == 0 and int(iters) >= 0
    np.testing.assert_array_equal(np.asarray(x), np.asarray(x2))


# ---------------------------------------------------------------------------
# accuracy: well/ill-conditioned, multi-RHS, at the residual gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cond,max_iters", [
    pytest.param(1e2, 4, id="100.0-4"),
    pytest.param(1e8, 30, id="100000000.0-30"),
    pytest.param(None, 4, id="spd"),  # the SPD solve: posv_mixed_mesh
])
def test_mixed_accuracy_at_gate(cond, max_iters, rng):
    mesh = mesh24()
    a = _cond(rng, cond) if cond is not None else _spd(rng)
    b = _rhs(rng, 3)  # multi-RHS
    mixed, plain = ((gesv_mixed_mesh, _gesv_mesh_plain) if cond is not None
                    else (posv_mixed_mesh, _posv_mesh_plain))
    x, iters, info = mixed(a, b, mesh, NB)
    assert int(info) == 0
    assert 0 <= int(iters) <= max_iters
    assert _gate(a, x, b)
    # mixed-vs-f64: the direct f64 solve also satisfies the same gate —
    # the mixed path's accuracy contract is the f64 path's
    xf, info_f = plain(a, b, mesh, NB)
    assert _gate(a, xf, b)


def test_posv_lower_only_storage_routes_correctly(rng):
    # the potrf contract reads only the lower triangle, so lower-only
    # storage is a valid posv input; the routed refinement must mirror
    # it before computing residuals (or it would "converge" on the wrong
    # nonsymmetric operator with info == 0)
    mesh = mesh24()
    full = _spd(rng)
    low = jnp.tril(full)
    b = _rhs(rng)
    x, info = posv_mesh(low, b, mesh, NB)  # default = auto
    assert int(info) == 0
    assert _gate(full, x, b)  # the gate is vs the SYMMETRIC operator
    # and lower-only input is bitwise the full-storage routing
    xf, _ = posv_mesh(full, b, mesh, NB)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xf))


def test_posv_mixed_failed_factor_is_nan(rng):
    mesh = mesh24()
    b = _rhs(rng)
    x, iters, info = posv_mixed_mesh(jnp.asarray(-np.eye(N)), b, mesh, NB)
    assert int(info) != 0
    assert int(iters) == -1
    assert np.all(np.isnan(np.asarray(x)))


# ---------------------------------------------------------------------------
# escalation: IR -> GMRES -> full-f64 fallback
# ---------------------------------------------------------------------------


def test_escalation_ladder_ill_conditioned(rng):
    from slate_tpu.obs import REGISTRY

    mesh = mesh24()
    a = _cond(rng, 1e12)  # far beyond the f32 factor's reach
    b = _rhs(rng)
    # tier 1 alone: IR reports non-convergence honestly
    _x, iters, info = gesv_mixed_mesh(a, b, mesh, NB)
    assert int(info) == 0 and int(iters) == -1
    # the routed default walks the whole ladder and still returns an
    # f64-grade answer (the fallback tier IS the direct f64 solve)
    esc0 = REGISTRY.counter_value("ir.escalated_gmres", op="gesv")
    fb0 = REGISTRY.counter_value("ir.fallback", op="gesv")
    x, info = gesv_mesh(a, b, mesh, NB)
    assert int(info) == 0
    assert _gate(a, x, b)
    assert REGISTRY.counter_value("ir.escalated_gmres", op="gesv") == esc0 + 1
    assert REGISTRY.counter_value("ir.fallback", op="gesv") == fb0 + 1
    # the fallback answer is bitwise the direct path's
    xf, _ = _gesv_mesh_plain(a, b, mesh, NB)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(xf))


def test_gmres_tier_converges_where_ir_does(rng):
    mesh = mesh24()
    a = _well(rng)
    b = _rhs(rng)
    x, rnorm, info = gesv_mixed_gmres_mesh(a, b, mesh, NB)
    assert int(info) == 0
    # the GMRES tier's own contract is the LEFT-PRECONDITIONED tolerance
    # ||M^-1(b - A x)|| <= eps sqrt(n) ||b|| (gesv_mixed_gmres.cc / the
    # refine.py convention) — the measured rnorm must meet it...
    eps = np.finfo(np.float64).eps
    tol = eps * np.sqrt(N) * np.linalg.norm(np.asarray(b), axis=0).max()
    assert float(rnorm) <= tol
    # ...and the unpreconditioned backward error stays f64-grade
    r = np.asarray(b) - np.asarray(a) @ np.asarray(x)
    denom = np.abs(np.asarray(a)).sum(axis=1).max() * max(
        np.abs(np.asarray(x)).max(), 1e-300)
    assert np.abs(r).max() / denom < 1e-11
    # pinning mode=gmres runs GMRES as tier 1 — that is a REQUESTED
    # tier, not an escalation, so the escalation counter must not move
    from slate_tpu.obs import REGISTRY

    esc0 = REGISTRY.counter_value("ir.escalated_gmres", op="gesv")
    xg, info = gesv_mesh(a, b[:, :1], mesh, NB,
                         opts={Option.MixedPrecision: "gmres"})
    assert int(info) == 0
    assert REGISTRY.counter_value("ir.escalated_gmres", op="gesv") == esc0


# ---------------------------------------------------------------------------
# opts threading: lookahead x bcast-impl bitwise invariance; pallas panels
# ---------------------------------------------------------------------------


def test_mixed_opts_threading_bitwise_invariant(rng):
    mesh = mesh24()
    a = _spd(rng)
    b = _rhs(rng)
    outs = []
    for la in (0, 2):
        for bi in ("psum", "ring"):
            x, iters, info = posv_mixed_mesh(
                a, b, mesh, NB,
                opts={Option.Lookahead: la, Option.BcastImpl: bi},
            )
            assert int(info) == 0 and int(iters) >= 0
            outs.append(np.asarray(x))
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


# ---------------------------------------------------------------------------
# Ozaki residual: bitwise across mesh shapes; comm bytes proven
# ---------------------------------------------------------------------------


def test_ozaki_residual_bitwise_across_mesh_shapes(rng):
    from slate_tpu.parallel.summa import gemm_summa_ozaki

    a = np.asarray(_well(rng))
    x = rng.standard_normal((N, NRHS))
    b = rng.standard_normal((N, NRHS))
    outs = {}
    for p, q in [(2, 4), (1, 8), (2, 2)]:
        mesh = make_mesh(p, q, devices=cpu_devices(p * q))
        ad = from_dense(jnp.asarray(a), mesh, NB, diag_pad_one=True)
        xd = from_dense(jnp.asarray(x), mesh, NB)
        bd = from_dense(jnp.asarray(b), mesh, NB)
        outs[(p, q)] = np.asarray(
            to_dense(gemm_summa_ozaki(-1.0, ad, xd, 1.0, bd))
        )
    ref = b - a @ x
    for grid, out in outs.items():
        # f64-grade accurate...
        assert np.abs(out - ref).max() < 1e-11, grid
        # ...and BITWISE identical to every other mesh shape
        np.testing.assert_array_equal(outs[(2, 4)], out, err_msg=str(grid))


def test_ozaki_mixed_solve_meets_gate(rng):
    mesh = mesh24()
    a = _well(rng)
    b = _rhs(rng)
    x, iters, info = gesv_mixed_mesh(
        a, b, mesh, NB, opts={Option.ResidualImpl: "ozaki"}
    )
    assert int(info) == 0 and int(iters) >= 0
    assert _gate(a, x, b)


@pytest.mark.parametrize("impl", ["psum", "ring"])
def test_ozaki_residual_comm_volume_analytic(impl, rng):
    """The acceptance criterion: the Ozaki residual SUMMA moves exactly
    slice_count(=9)/8 x the plain f64 SUMMA wire bytes — the digit planes
    are int8 on the identical broadcast schedule."""
    from slate_tpu.parallel.summa import gemm_summa, gemm_summa_ozaki
    from slate_tpu.types import MethodGemm

    p, q = 2, 4
    mesh = make_mesh(p, q, devices=cpu_devices(8))
    ad = from_dense(_well(rng), mesh, NB, diag_pad_one=True)
    xd = from_dense(_rhs(rng), mesh, NB)
    bd = from_dense(_rhs(rng), mesh, NB)
    mt, ntb, kt = ad.tiles.shape[0], bd.tiles.shape[1], ad.nt

    def total(records):
        return sum(nbytes * m for _, nbytes, m in records)

    jax.clear_caches()  # audit records at trace time only
    with comm_audit() as recs_oz:
        gemm_summa_ozaki(-1.0, ad, xd, 1.0, bd,
                         bcast_impl=impl).tiles.block_until_ready()
    jax.clear_caches()
    with comm_audit() as recs_f64:
        gemm_summa(-1.0, ad, xd, 1.0, bd, method=MethodGemm.GemmC,
                   bcast_impl=impl).tiles.block_until_ready()

    expect_oz = residual_comm_bytes(mt, ntb, kt, NB, p, q, impl, "ozaki")
    expect_f64 = residual_comm_bytes(mt, ntb, kt, NB, p, q, impl, "f64")
    assert total(recs_oz) == expect_oz
    assert total(recs_f64) == expect_f64
    assert total(recs_oz) * 8 == total(recs_f64) * 9  # 9 int8 planes vs f64


def test_refine_loop_audited_volume(rng):
    """The fused refinement program's trace-time audit carries the
    residual SUMMA at the loop multiplicity: under the masked-psum
    lowering the int8 digit-plane records are exactly the analytic
    per-iteration volume x (max_iter + 1) — the worst-case audit the
    lint loop-audit contract requires for a dynamic-trip while_loop
    (plus the norm-pair reductions riding the same scope)."""
    mesh = mesh24()
    a = _well(rng)
    b = _rhs(rng)
    max_iter = 5
    jax.clear_caches()
    with comm_audit() as recs:
        gesv_mixed_mesh(
            a, b, mesh, NB, max_iter=max_iter,
            opts={Option.ResidualImpl: "ozaki", Option.BcastImpl: "psum"},
        )
    p, q = 2, 4
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    bd = from_dense(b, mesh, NB)
    mt, ntb, kt = ad.tiles.shape[0], bd.tiles.shape[1], ad.nt
    mtl, ntl = mt // p, ntb // q
    # the int8 plane payloads are unique byte sizes in the whole program
    a_pan, x_pan = 9 * mtl * NB * NB, 9 * ntl * NB * NB
    got = sum(nbytes * m for op, nbytes, m in recs
              if op.startswith("psum") and nbytes in (a_pan, x_pan))
    expect = (max_iter + 1) * residual_comm_bytes(
        mt, ntb, kt, NB, p, q, "psum", "ozaki")
    assert got == expect
    # the mesh-reduced norm pair rides the same loop scope: one psum of
    # the stacked (2, mtl, nb) row sums per iteration
    norm_bytes = 2 * mtl * NB * 8
    norm_recs = [(nb_, m) for op, nb_, m in recs
                 if op.startswith("psum") and nb_ == norm_bytes]
    assert (norm_bytes, (max_iter + 1)) in norm_recs


# ---------------------------------------------------------------------------
# zero host syncs: the warm refinement program dispatches under a
# disallow-transfers guard (the while_loop never reads back)
# ---------------------------------------------------------------------------


def test_refinement_loop_zero_host_syncs(rng):
    from slate_tpu.parallel.dist import DistMatrix
    from slate_tpu.parallel.dist_chol import potrf_dist
    from slate_tpu.parallel.dist_refine import _astype_dist, _ir_posv_jit

    mesh = mesh24()
    a = _spd(rng)
    b = _rhs(rng)
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    a32 = _astype_dist(ad, jnp.float32)
    l, info = potrf_dist(a32)
    statics = (mesh, 2, 4, N, NRHS, NB, 30, None, "auto", "f64")
    bt = from_dense(b, mesh, NB).tiles
    out = _ir_posv_jit(ad.tiles, bt, l.tiles, info, *statics)  # warm-up
    jax.block_until_ready(out)
    bt2 = from_dense(b, mesh, NB).tiles  # fresh RHS: bt was donated
    jax.block_until_ready((ad.tiles, bt2, l.tiles, info))
    with jax.transfer_guard("disallow"):
        out2 = _ir_posv_jit(ad.tiles, bt2, l.tiles, info, *statics)
    x_t, _r, iters, conv, _rn, _xn = jax.block_until_ready(out2)
    assert bool(conv) and int(iters) >= 0


# ---------------------------------------------------------------------------
# obs: the ir section reaches RunReports and the --check gate
# ---------------------------------------------------------------------------


def test_ir_counters_reach_runreport():
    from slate_tpu import obs
    from slate_tpu.linalg.refine import ir_count
    from slate_tpu.obs import report

    obs.reset()
    ir_count("ir.solves", "gesv")
    ir_count("ir.converged", "gesv")
    ir_count("ir.iters_total", "gesv", 3)
    rep = report.make_report("mixed_test")
    assert report.validate_report(rep) == []
    assert rep["ir"]["solves"] == 1.0
    assert rep["ir"]["iters_total"] == 3.0
    vals = report.load_values(rep)
    assert vals["ir_converged"] == 1.0
    # convergence collapsing to zero under a fixed workload is a FAIL
    old = dict(vals)
    new = dict(vals, ir_converged=0.0)
    failures, _ = report.check_regression(new, old)
    assert any("ir_converged" in f for f in failures)
    # iters rising beyond threshold is a FAIL (lower-is-better)
    new2 = dict(vals, ir_iters_total=30.0)
    failures2, _ = report.check_regression(new2, old, threshold=1.5)
    assert any("ir_iters_total" in f for f in failures2)
    obs.reset()
