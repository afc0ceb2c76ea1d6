"""Fused Pallas trailing-update kernels (PR 20): Option.UpdateImpl
end-to-end.

Contracts under test, on CPU with every kernel running under the Pallas
interpreter (the tier-1 parity story — the same kernels compile for the
MXU on a real TPU backend):

1. Every fused trailing-update kernel replicates its XLA einsum bulk
   form's op sequence (contraction at HIGHEST → astype → select →
   add/subtract).  In float64 the interpreter reproduces the einsum
   forms BITWISE — at kernel level AND through the mesh drivers
   (gemm_summa consume, potrf trailing herk, LU-nopiv trailing gemm),
   aligned and ragged, at every lookahead depth.  In float32 the
   contraction's accumulation order is the backend's own (the
   interpreter's dot and XLA:CPU's einsum differ in the last bits, as
   the chip's Mosaic and XLA kernels do), so each lowering is held to
   the rounding bound of a length-k dot at kernel level and to the
   factorization's backward-error class through the drivers.
2. ``Option.UpdateImpl = xla`` IS today's trace (identical jaxpr), and
   ``auto`` resolves to xla off-TPU — the default tier-1 schedules are
   untouched.
3. The option plumbs through driver ``update_impl=``, the
   ``use_update_impl`` context, and the ``SLATE_TPU_UPDATE_IMPL``
   environment default, with explicit > context > environment
   precedence; complex dtypes fall back to xla even when pallas is
   requested.
4. The comm-audit byte totals are UpdateImpl-invariant: the fused
   dispatch sits strictly inside the compute half of each k-step.
5. The tournament-pivoted mesh LU reconstructs P A.
6. The serving tier's ``gels`` route polices the recorded QR
   orthogonality-loss gauge: a factor past ``ORTH_THRESHOLD`` costs one
   counted re-orthogonalization retry, not a bad solution.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import cpu_devices

from slate_tpu.ops import pallas_ops as po
from slate_tpu.parallel import from_dense, make_mesh, to_dense
from slate_tpu.parallel.dist_chol import potrf_dist
from slate_tpu.parallel.dist_lu import getrf_nopiv_dist, getrf_tntpiv_dist
from slate_tpu.parallel.summa import MethodGemm, gemm_summa
from slate_tpu.types import Option

N, NB = 64, 8


def mesh24():
    return make_mesh(2, 4, devices=cpu_devices(8))


def _spd(rng, n, dtype):
    g = rng.standard_normal((n, n))
    return jnp.asarray(g @ g.T + n * np.eye(n), dtype)


def _diag_dom(rng, n, dtype):
    return jnp.asarray(rng.standard_normal((n, n)) + n * np.eye(n), dtype)


def _tol(dtype, scale=1.0):
    """The backward-error class of an nb-blocked factorization or
    product: 100 nb eps times the operand scale."""
    return 100 * NB * float(jnp.finfo(dtype).eps) * scale


# ---------------------------------------------------------------------------
# kernel-level parity vs the XLA bulk forms: BITWISE in float64, the
# length-k dot rounding bound in float32
# ---------------------------------------------------------------------------

_HI = jax.lax.Precision.HIGHEST
F32 = [jnp.float32]


def _assert_dot_bound(outs, acc, pan, other, mask=None):
    """Each float32 result in ``outs`` is ``acc +/- sum_k pan . other``
    (masked per tile) summed in some order: it lies within
    gamma_{k+1} * (|acc| + |pan| |other|) of the exact float64 value,
    with k the contraction length and gamma_m = m eps / (1 - m eps)
    (the accumulate is one more term of the sum).  ``other`` is already
    laid out for ``iab,jbc->ijac``."""
    a64, p64, o64 = (np.asarray(x, np.float64) for x in (acc, pan, other))
    k = p64.shape[-1]
    eps = float(jnp.finfo(jnp.float32).eps)
    gamma = (k + 1) * eps / (1 - (k + 1) * eps)
    keep = np.ones(a64.shape[:2], bool) if mask is None else np.asarray(mask)
    keep = keep[:, :, None, None]
    upd = np.einsum("iab,jbc->ijac", p64, o64)
    mag = np.einsum("iab,jbc->ijac", np.abs(p64), np.abs(o64))
    bound = gamma * (np.abs(a64) + np.where(keep, mag, 0))
    for sign, out in outs:
        exact = a64 + sign * np.where(keep, upd, 0)
        err = np.abs(np.asarray(out, np.float64) - exact)
        assert (err <= bound).all(), float((err - bound).max())


def _update_operands(rng, dtype, mtl=3, ntl=4, nb=NB):
    acc = jnp.asarray(rng.standard_normal((mtl, ntl, nb, nb)), dtype)
    pan = jnp.asarray(rng.standard_normal((mtl, nb, nb)), dtype)
    pan_t = jnp.asarray(rng.standard_normal((ntl, nb, nb)), dtype)
    urow = jnp.asarray(rng.standard_normal((ntl, nb, nb)), dtype)
    lower = jnp.asarray(
        np.arange(mtl)[:, None] >= np.arange(ntl)[None, :]
    )
    return acc, pan, pan_t, urow, lower


@pytest.mark.parametrize("dtype", [jnp.float64])
def test_summa_update_kernel_bitwise(rng, dtype):
    acc, pan, _, urow, _ = _update_operands(rng, dtype)
    out = po.summa_update_pallas(acc, pan, urow)
    upd = jnp.einsum("iab,jbc->ijac", pan, urow, precision=_HI)
    ref = acc + upd.astype(acc.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float64])
def test_chol_trailing_kernel_bitwise(rng, dtype):
    acc, pan, pan_t, _, lower = _update_operands(rng, dtype)
    out = po.chol_trailing_update_pallas(acc, pan, pan_t, lower)
    upd = jnp.einsum(
        "iab,jcb->ijac", pan, pan_t, precision=_HI
    ).astype(acc.dtype)
    ref = acc - jnp.where(lower[:, :, None, None], upd, 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float64])
def test_lu_trailing_kernel_bitwise(rng, dtype):
    acc, pan, _, urow, lower = _update_operands(rng, dtype)
    out = po.lu_trailing_update_pallas(acc, pan, urow, lower)
    upd = jnp.einsum("iab,jbc->ijac", pan, urow, precision=_HI)
    ref = acc - jnp.where(lower[:, :, None, None], upd.astype(acc.dtype), 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", F32)
def test_summa_update_kernel_dot_bound(rng, dtype):
    acc, pan, _, urow, _ = _update_operands(rng, dtype)
    out = po.summa_update_pallas(acc, pan, urow)
    upd = jnp.einsum("iab,jbc->ijac", pan, urow, precision=_HI)
    ref = acc + upd.astype(acc.dtype)
    _assert_dot_bound([(1, out), (1, ref)], acc, pan, urow)


@pytest.mark.parametrize("dtype", F32)
def test_chol_trailing_kernel_dot_bound(rng, dtype):
    acc, pan, pan_t, _, lower = _update_operands(rng, dtype)
    out = po.chol_trailing_update_pallas(acc, pan, pan_t, lower)
    upd = jnp.einsum(
        "iab,jcb->ijac", pan, pan_t, precision=_HI
    ).astype(acc.dtype)
    ref = acc - jnp.where(lower[:, :, None, None], upd, 0)
    _assert_dot_bound([(-1, out), (-1, ref)], acc, pan,
                      jnp.swapaxes(pan_t, 1, 2), lower)


@pytest.mark.parametrize("dtype", F32)
def test_lu_trailing_kernel_dot_bound(rng, dtype):
    acc, pan, _, urow, lower = _update_operands(rng, dtype)
    out = po.lu_trailing_update_pallas(acc, pan, urow, lower)
    upd = jnp.einsum("iab,jbc->ijac", pan, urow, precision=_HI)
    ref = acc - jnp.where(lower[:, :, None, None], upd.astype(acc.dtype), 0)
    _assert_dot_bound([(-1, out), (-1, ref)], acc, pan, urow, lower)


# ---------------------------------------------------------------------------
# driver-level parity: mesh kernels bitwise across lowerings in float64,
# both in the backward-error class (and within it of each other) in float32
# ---------------------------------------------------------------------------

RAGGED = pytest.mark.parametrize("n", [N, N - 4], ids=["aligned", "ragged-tail"])


def _assert_close_class(outs, err_of, dtype, scale):
    """Both lowerings meet the dtype's backward-error class against the
    float64 reference (``err_of``), and agree with each other within it."""
    tol = _tol(dtype, scale)
    for impl, out in outs.items():
        assert err_of(out) < tol, (impl, err_of(out), tol)
    assert np.abs(outs["pallas"] - outs["xla"]).max() < tol


@RAGGED
@pytest.mark.parametrize("dtype", [jnp.float64])
def test_gemm_summa_update_pallas_bitwise(rng, n, dtype):
    mesh = mesh24()
    a = jnp.asarray(rng.standard_normal((n, n)), dtype)
    b = jnp.asarray(rng.standard_normal((n, n)), dtype)
    outs = {}
    for impl in ("xla", "pallas"):
        c = gemm_summa(
            1.0, from_dense(a, mesh, NB), from_dense(b, mesh, NB),
            method=MethodGemm.GemmC, update_impl=impl,
        )
        outs[impl] = np.asarray(to_dense(c))
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])


@RAGGED
def test_gemm_summa_update_pallas_f32(rng, n):
    mesh = mesh24()
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    outs = {}
    for impl in ("xla", "pallas"):
        c = gemm_summa(
            1.0, from_dense(a, mesh, NB), from_dense(b, mesh, NB),
            method=MethodGemm.GemmC, update_impl=impl,
        )
        outs[impl] = np.asarray(to_dense(c), np.float64)[:n, :n]
    an, bn = np.asarray(a, np.float64), np.asarray(b, np.float64)
    ref = an @ bn
    _assert_close_class(outs, lambda c: np.abs(c - ref).max(), jnp.float32,
                        (np.abs(an) @ np.abs(bn)).max())


@RAGGED
@pytest.mark.parametrize("dtype", [jnp.float64])
def test_potrf_dist_update_pallas_bitwise(rng, n, dtype):
    mesh = mesh24()
    ad = from_dense(_spd(rng, n, dtype), mesh, NB, diag_pad_one=True)
    l_x, info_x = potrf_dist(ad, update_impl="xla")
    l_p, info_p = potrf_dist(ad, update_impl="pallas")
    assert int(info_x) == 0 and int(info_p) == int(info_x)
    np.testing.assert_array_equal(
        np.asarray(to_dense(l_p)), np.asarray(to_dense(l_x))
    )


@RAGGED
def test_potrf_dist_update_pallas_f32(rng, n):
    mesh = mesh24()
    a = _spd(rng, n, jnp.float32)
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    outs = {}
    for impl in ("xla", "pallas"):
        l, info = potrf_dist(ad, update_impl=impl)
        assert int(info) == 0, impl
        outs[impl] = np.tril(np.asarray(to_dense(l), np.float64))[:n, :n]
    an = np.asarray(a, np.float64)
    _assert_close_class(outs, lambda l: np.abs(l @ l.T - an).max(),
                        jnp.float32, np.abs(an).max() * n)


@RAGGED
@pytest.mark.parametrize("dtype", [jnp.float64])
def test_getrf_nopiv_dist_update_pallas_bitwise(rng, n, dtype):
    mesh = mesh24()
    ad = from_dense(_diag_dom(rng, n, dtype), mesh, NB, diag_pad_one=True)
    lu_x, info_x = getrf_nopiv_dist(ad, update_impl="xla")
    lu_p, info_p = getrf_nopiv_dist(ad, update_impl="pallas")
    assert int(info_x) == 0 and int(info_p) == int(info_x)
    np.testing.assert_array_equal(
        np.asarray(to_dense(lu_p)), np.asarray(to_dense(lu_x))
    )


@RAGGED
def test_getrf_nopiv_dist_update_pallas_f32(rng, n):
    mesh = mesh24()
    a = _diag_dom(rng, n, jnp.float32)
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    outs = {}
    for impl in ("xla", "pallas"):
        lu, info = getrf_nopiv_dist(ad, update_impl=impl)
        assert int(info) == 0, impl
        outs[impl] = np.asarray(to_dense(lu), np.float64)[:n, :n]
    an = np.asarray(a, np.float64)

    def err(lun):
        return np.abs((np.tril(lun, -1) + np.eye(n)) @ np.triu(lun) - an).max()

    _assert_close_class(outs, err, jnp.float32, np.abs(an).max() * n)


@pytest.mark.parametrize("depth", [0, 1, 2])
def test_lookahead_depth_invariant_under_pallas(rng, depth):
    """Lookahead moves WHEN the fused update runs, never what it
    computes: every depth must land the depth-0 bits under pallas."""
    mesh = mesh24()
    ad = from_dense(_spd(rng, N, jnp.float64), mesh, NB, diag_pad_one=True)
    l0, _ = potrf_dist(ad, lookahead=0, update_impl="pallas")
    ld, info = potrf_dist(ad, lookahead=depth, update_impl="pallas")
    assert int(info) == 0
    np.testing.assert_array_equal(
        np.asarray(to_dense(ld)), np.asarray(to_dense(l0))
    )


# ---------------------------------------------------------------------------
# UpdateImpl=xla is today's trace; plumbing and precedence
# ---------------------------------------------------------------------------


def test_update_impl_xla_is_todays_trace(rng):
    """``xla`` and off-TPU ``auto`` must produce the IDENTICAL jaxpr for
    every routed driver — the acceptance bar that UpdateImpl=xla
    reproduces today's results bitwise."""
    mesh = mesh24()
    spd = from_dense(_spd(rng, N, jnp.float64), mesh, NB, diag_pad_one=True)
    dd = from_dense(_diag_dom(rng, N, jnp.float64), mesh, NB,
                    diag_pad_one=True)
    g = from_dense(jnp.asarray(rng.standard_normal((N, N))), mesh, NB)
    runs = {
        "summa": lambda impl: (lambda x: gemm_summa(
            1.0, x, g, method=MethodGemm.GemmC, update_impl=impl)),
        "potrf": lambda impl: (lambda x: potrf_dist(x, update_impl=impl)),
        "getrf": lambda impl: (
            lambda x: getrf_nopiv_dist(x, update_impl=impl)),
    }
    operands = {"summa": g, "potrf": spd, "getrf": dd}
    for name, mk in runs.items():
        jx = {impl: str(jax.make_jaxpr(mk(impl))(operands[name]))
              for impl in ("xla", "auto")}
        assert jx["auto"] == jx["xla"], name
        assert "pallas_call" not in jx["xla"], name


def _uses_pallas(run):
    jax.clear_caches()  # trace-time dispatch
    return "pallas_call" in str(jax.make_jaxpr(run)())


def test_update_impl_context_and_env_defaults(rng, monkeypatch):
    mesh = mesh24()
    ad = from_dense(_spd(rng, N, jnp.float64), mesh, NB, diag_pad_one=True)

    def run(**kw):
        return lambda: potrf_dist(ad, **kw)

    # environment default
    monkeypatch.setenv(po.UPDATE_IMPL_ENV, "pallas")
    assert _uses_pallas(run())
    # context beats environment
    with po.use_update_impl("xla"):
        assert not _uses_pallas(run())
        # explicit argument beats context
        assert _uses_pallas(run(update_impl="pallas"))
    # unknown values fail loudly, at resolve time
    with pytest.raises(ValueError, match="unknown update impl"):
        potrf_dist(ad, update_impl="fpga")
    monkeypatch.setenv(po.UPDATE_IMPL_ENV, "abacus")
    with pytest.raises(ValueError, match="unknown update impl"):
        potrf_dist(ad)


def test_update_impl_plumbs_through_driver_opts(rng):
    from slate_tpu.parallel import potrf_mesh

    mesh = mesh24()
    a = _spd(rng, N, jnp.float64)
    run = lambda impl: (lambda: potrf_mesh(a, mesh, nb=NB,
                                           opts={Option.UpdateImpl: impl}))
    assert not _uses_pallas(run("xla"))
    assert _uses_pallas(run("pallas"))
    assert not _uses_pallas(run("auto"))  # off-TPU auto -> xla


def test_resolve_update_default_is_auto(monkeypatch):
    monkeypatch.delenv(po.UPDATE_IMPL_ENV, raising=False)
    assert po.resolve_update_impl() == "auto"
    assert po.resolve_update_impl("pallas") == "pallas"


def test_complex_update_falls_back_to_xla(rng):
    """Complex trailing updates have no fused kernel: requesting pallas
    must trace the XLA einsum forms rather than fail."""
    mesh = mesh24()
    g = rng.standard_normal((N, N)) + 1j * rng.standard_normal((N, N))
    a = jnp.asarray(g @ g.conj().T + N * np.eye(N), jnp.complex128)
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    jx = str(jax.make_jaxpr(
        lambda x: potrf_dist(x, update_impl="pallas")
    )(ad))
    assert "pallas_call" not in jx
    l, info = potrf_dist(ad, update_impl="pallas")
    assert int(info) == 0


def test_update_bytes_invariant_across_impls(rng):
    """The fused dispatch sits strictly inside the compute half of each
    k-step: the audited collective schedule (ops, payloads, multiplier
    totals) must be IDENTICAL across UpdateImpl."""
    from slate_tpu.parallel.comm import comm_audit

    mesh = mesh24()
    spd = from_dense(_spd(rng, N, jnp.float64), mesh, NB, diag_pad_one=True)
    g = from_dense(jnp.asarray(rng.standard_normal((N, N))), mesh, NB)
    runs = {
        "potrf": (lambda x, impl: potrf_dist(x, update_impl=impl), spd),
        "summa": (lambda x, impl: gemm_summa(
            1.0, x, g, method=MethodGemm.GemmC, update_impl=impl), g),
    }
    for name, (fn, operand) in runs.items():
        recs = {}
        for impl in ("xla", "pallas"):
            jax.clear_caches()
            with comm_audit() as r:
                jax.make_jaxpr(lambda x: fn(x, impl))(operand)
            recs[impl] = sorted((op, nb, m) for op, nb, m in r)
        assert recs["pallas"] == recs["xla"], name


def test_flight_on_bitwise_and_bytes_unchanged(rng):
    """Under the flight recorder's per-step fenced dispatch the fused
    update keeps the SAME phase events and byte attribution as the xla
    loop, and the results stay bitwise — the ScheduleModel sees one
    schedule regardless of UpdateImpl."""
    from slate_tpu.obs import flight, schedule

    mesh = mesh24()
    ad = from_dense(_spd(rng, N, jnp.float64), mesh, NB, diag_pad_one=True)
    outs, rows = {}, {}
    for impl in ("xla", "pallas"):
        with flight.flight_scope() as rec:
            l, info = potrf_dist(ad, lookahead=1, update_impl=impl)
        assert int(info) == 0
        outs[impl] = np.asarray(to_dense(l))
        rows[impl] = [
            (r["phase"], r["k"], r["bytes"])
            for r in schedule.rows_from_events(rec.events)
        ]
    np.testing.assert_array_equal(outs["pallas"], outs["xla"])
    assert rows["pallas"] == rows["xla"]
    # the fenced pallas dispatch matches the plain (unfenced) kernel too
    l_plain, _ = potrf_dist(ad, lookahead=1, update_impl="pallas")
    np.testing.assert_array_equal(
        outs["pallas"], np.asarray(to_dense(l_plain))
    )


# ---------------------------------------------------------------------------
# tournament-pivoted mesh LU
# ---------------------------------------------------------------------------


def test_getrf_tntpiv_dist_reconstruction(rng):
    """Tournament-pivot LU reconstructs P A to the float64 class."""
    mesh = mesh24()
    a = jnp.asarray(rng.standard_normal((N, N)))
    ad = from_dense(a, mesh, NB, diag_pad_one=True)
    lu, perm, info = getrf_tntpiv_dist(ad)
    assert int(info) == 0
    lun = np.asarray(to_dense(lu), np.float64)[:N, :N]
    an = np.asarray(a, np.float64)
    rec = (np.tril(lun, -1) + np.eye(N)) @ np.triu(lun)
    err = np.abs(rec - an[np.asarray(perm)]).max()
    assert err < 1e-10 * N * np.abs(an).max(), err


# ---------------------------------------------------------------------------
# serving tier: the gels route polices the QR orthogonality gauge
# ---------------------------------------------------------------------------


def _ls_router(opts=None):
    from slate_tpu.serve.router import Router

    return Router(mesh=mesh24(), nb=NB, bins=(64,), opts=opts or {})


def test_router_gels_serves_least_squares(rng):
    router = _ls_router()
    a = jnp.asarray(rng.standard_normal((N, N // 2)))
    b = jnp.asarray(rng.standard_normal(N))
    x = router.gels(a, b)
    assert x.shape == (N // 2,)
    an, bn = np.asarray(a), np.asarray(b)
    # least-squares optimality: the residual is normal to range(A)
    grad = an.T @ (an @ np.asarray(x) - bn)
    assert np.abs(grad).max() < 1e-8


def test_router_gels_orth_retry(rng, monkeypatch):
    """A monitored factor past ORTH_THRESHOLD costs exactly one counted
    re-orthogonalization retry — and the served solution is still the
    least-squares optimum (the two-factor solve folds R2 R1)."""
    from slate_tpu.obs import numerics as _num
    from slate_tpu.serve import metrics as serve_metrics

    router = _ls_router({Option.NumMonitor: "on"})
    a = jnp.asarray(rng.standard_normal((N, N // 2)))
    b = jnp.asarray(rng.standard_normal((N, 2)))
    # a healthy panel records ~eps loss: force the police to trip
    monkeypatch.setattr(_num, "ORTH_THRESHOLD", 0.0)
    before = serve_metrics.serve_counter_values()["retries"]
    x = router.gels(a, b)
    after = serve_metrics.serve_counter_values()["retries"]
    assert after == before + 1
    an, bn = np.asarray(a), np.asarray(b)
    grad = an.T @ (an @ np.asarray(x) - bn)
    assert np.abs(grad).max() < 1e-8


def test_router_gels_unmonitored_keeps_single_pass(rng, monkeypatch):
    """No gauge, no degradation action: an unmonitored request never
    pays the retry even when the threshold would trip."""
    from slate_tpu.obs import numerics as _num
    from slate_tpu.serve import metrics as serve_metrics

    router = _ls_router()
    monkeypatch.setattr(_num, "ORTH_THRESHOLD", 0.0)
    a = jnp.asarray(rng.standard_normal((N, N // 2)))
    b = jnp.asarray(rng.standard_normal(N))
    before = serve_metrics.serve_counter_values()["retries"]
    router.gels(a, b)
    after = serve_metrics.serve_counter_values()["retries"]
    assert after == before
