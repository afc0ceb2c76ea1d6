"""Distributed-layer tests on the forced 8-device CPU mesh.

Mirrors the reference's oversubscribed single-node MPI CI (Jenkinsfile-mpi):
shard_map kernels run over a real (p, q) Mesh of XLA:CPU devices, so every
psum/all_gather in the SUMMA/potrf/LU/trsm kernels executes as an actual
collective; numerical gates are the 3-eps style residuals of test/ (§4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.parallel import (
    DistMatrix,
    from_dense,
    gemm_mesh,
    gemm_summa,
    gesv_nopiv_mesh,
    make_mesh,
    posv_mesh,
    potrf_dist,
    potrf_mesh,
    to_dense,
    trsm_dist,
)
from slate_tpu.types import Diag, Op, Uplo

from conftest import cpu_devices


def mesh24():
    return make_mesh(2, 4, devices=cpu_devices(8))


def mesh22():
    return make_mesh(2, 2, devices=cpu_devices(4))


def _rand(rng, m, n, dtype=np.float64):
    a = rng.standard_normal((m, n))
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal((m, n))
    return jnp.asarray(a.astype(dtype))


def _spd(rng, n, dtype=np.float64):
    a = _rand(rng, n, n, dtype)
    return a @ jnp.conj(a).T + n * jnp.eye(n, dtype=dtype)


def _tol(dtype, nb, scale):
    """Backward-error class of an nb-blocked factorization: 100 nb eps
    times the operand scale."""
    return 100 * nb * float(np.finfo(dtype).eps) * scale


def test_roundtrip(rng):
    mesh = mesh24()
    a = _rand(rng, 100, 68)
    d = from_dense(a, mesh, nb=16)
    assert d.mt % 4 == 0 and d.nt % 4 == 0  # lcm(2,4) padding
    np.testing.assert_array_equal(np.asarray(to_dense(d)), np.asarray(a))


def test_roundtrip_diag_pad(rng):
    mesh = mesh24()
    a = _spd(rng, 50)
    d = from_dense(a, mesh, nb=16, diag_pad_one=True)
    back = to_dense(d)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(a))


@pytest.mark.parametrize("dims", [(96, 96, 96), (100, 52, 68), (32, 96, 16)])
def test_gemm_summa(rng, dims):
    m, n, k = dims
    mesh = mesh24()
    a, b = _rand(rng, m, k), _rand(rng, k, n)
    c = gemm_mesh(1.0, a, b, mesh, nb=16)
    ref = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(c), ref, rtol=1e-12, atol=1e-10)


def test_gemm_summa_beta(rng):
    mesh = mesh22()
    a, b, c0 = _rand(rng, 64, 32), _rand(rng, 32, 48), _rand(rng, 64, 48)
    c = gemm_mesh(2.0, a, b, mesh, nb=16, beta=-1.0, c=c0)
    ref = 2.0 * np.asarray(a) @ np.asarray(b) - np.asarray(c0)
    np.testing.assert_allclose(np.asarray(c), ref, rtol=1e-12, atol=1e-10)


def test_gemm_summa_stationary_a(rng):
    # GemmA (src/gemmA.cc): stationary-A schedule must agree with GemmC
    # and numpy on thin-C shapes, where select_gemm_method auto-picks it
    from slate_tpu.types import MethodGemm, select_gemm_method

    mesh = mesh24()
    m, k, n = 96, 128, 16
    a, b, c0 = _rand(rng, m, k), _rand(rng, k, n), _rand(rng, m, n)
    ad, bd = from_dense(a, mesh, 8), from_dense(b, mesh, 8)
    cd = from_dense(c0, mesh, 8)
    ref = 2.0 * np.asarray(a) @ np.asarray(b) - np.asarray(c0)
    outs = {
        meth: np.asarray(to_dense(gemm_summa(2.0, ad, bd, -1.0, cd, method=meth)))
        for meth in (MethodGemm.GemmA, MethodGemm.GemmC)
    }
    for meth, out in outs.items():
        np.testing.assert_allclose(out, ref, rtol=1e-12, atol=1e-10, err_msg=str(meth))
    # thin output panel auto-selects the stationary-A path (method.hh:35-45)
    assert select_gemm_method(m // 8, n // 8, k // 8) == MethodGemm.GemmA


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("op", [Op.NoTrans, Op.Trans, Op.ConjTrans])
def test_trsm_dist_stationary_a(rng, uplo, op):
    # TrsmA (src/trsmA.cc): stationary-A schedule, thin RHS, ALL ops —
    # the transposed ops route partials across mesh rows (r5 item 7)
    from slate_tpu.types import MethodTrsm, Side, select_trsm_method

    mesh = mesh24()
    n, nrhs = 96, 8
    # complex operands so ConjTrans is distinguishable from Trans
    t = np.tril(np.asarray(_rand(rng, n, n, np.complex128))) + n * np.eye(n)
    if uplo == Uplo.Upper:
        t = t.T
    b = _rand(rng, n, nrhs, np.complex128)
    ad = from_dense(jnp.asarray(t), mesh, nb=8, diag_pad_one=True)
    bd = from_dense(b, mesh, nb=8)
    x = to_dense(trsm_dist(ad, bd, uplo, op, method=MethodTrsm.TrsmA))
    opt = {Op.NoTrans: t, Op.Trans: t.T, Op.ConjTrans: t.conj().T}[op]
    err = np.linalg.norm(opt @ np.asarray(x) - np.asarray(b)) / np.linalg.norm(np.asarray(b))
    assert err < 1e-12
    assert select_trsm_method(Side.Left, n // 8, nrhs // 8) == MethodTrsm.TrsmA


@pytest.mark.parametrize("dtype,n,nb", [
    pytest.param(np.float64, 64, 16, id="64"),
    pytest.param(np.float64, 100, 16, id="100"),
    pytest.param(np.float64, 64, 8, id="float64-aligned"),
    pytest.param(np.float64, 60, 8, id="float64-ragged-tail"),
    pytest.param(np.float32, 64, 8, id="float32-aligned"),
    pytest.param(np.float32, 60, 8, id="float32-ragged-tail"),
    pytest.param(np.complex128, 64, 8, id="complex128-aligned"),
])
def test_potrf_dist(rng, dtype, n, nb):
    """L L^H = A to the dtype's backward-error class against the float64
    (complex128) reference, aligned and with a ragged last tile."""
    mesh = mesh24()
    a = _spd(rng, n, dtype)
    l, info = potrf_mesh(a, mesh, nb=nb)
    assert int(info) == 0
    wide = np.complex128 if np.issubdtype(dtype, np.complexfloating) else np.float64
    ld = np.tril(np.asarray(to_dense(l), wide))
    an = np.asarray(a, wide)
    err = ld @ ld.conj().T - an
    if dtype != np.float32:
        assert np.linalg.norm(err) / np.linalg.norm(an) < 1e-13
    assert np.abs(err).max() < _tol(dtype, nb, np.abs(an).max() * n)


def test_potrf_dist_complex(rng):
    mesh = mesh22()
    a = _spd(rng, 48, np.complex128)
    l, info = potrf_mesh(a, mesh, nb=16)
    assert int(info) == 0
    ld = np.tril(np.asarray(to_dense(l)))
    resid = np.linalg.norm(ld @ ld.conj().T - np.asarray(a)) / np.linalg.norm(np.asarray(a))
    assert resid < 1e-13


def test_potrf_dist_not_spd(rng):
    mesh = mesh22()
    a = jnp.eye(32, dtype=jnp.float64)
    a = a.at[10, 10].set(-1.0)
    _, info = potrf_mesh(a, mesh, nb=8)
    # failure is in tile 1 (global rows 8..15, bad pivot at 10): info lands
    # in (8, 11] — tile-start granularity, see dist_chol.py info note
    assert 8 < int(info) <= 11


def test_posv_mesh(rng):
    mesh = mesh24()
    n, nrhs = 80, 24
    a = _spd(rng, n)
    x_true = _rand(rng, n, nrhs)
    b = jnp.asarray(np.asarray(a) @ np.asarray(x_true))
    x, info = posv_mesh(a, b, mesh, nb=16)
    assert int(info) == 0
    err = np.linalg.norm(np.asarray(x) - np.asarray(x_true)) / np.linalg.norm(np.asarray(x_true))
    assert err < 1e-10


@pytest.mark.parametrize("n", [64, 60], ids=["aligned", "ragged-tail"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_getrf_nopiv_dist_factor(rng, dtype, n):
    """L U = A for a diagonally dominant A (no pivot needed), to the
    dtype's backward-error class against the float64 reference."""
    from slate_tpu.parallel import getrf_nopiv_mesh

    mesh, nb = mesh24(), 8
    a = _rand(rng, n, n, dtype) + n * jnp.eye(n, dtype=dtype)
    lu, info = getrf_nopiv_mesh(a, mesh, nb=nb)
    assert int(info) == 0
    lun = np.asarray(to_dense(lu), np.float64)[:n, :n]
    an = np.asarray(a, np.float64)
    rec = (np.tril(lun, -1) + np.eye(n)) @ np.triu(lun)
    assert np.abs(rec - an).max() < _tol(dtype, nb, np.abs(an).max() * n)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("form", ["factor_solve", "rowsolve"])
def test_lu_panel_forms(rng, form, dtype):
    """The mesh LU's panel solves on one nb = 8 diagonal tile and 8
    tiles beside it, against the float64 reference: the column half
    packs L\\U of the tile (L U = A_kk) and solves A_i U^-1; the row half
    solves L^-1 A_j with the unit lower factor."""
    from slate_tpu.parallel.dist_lu import _lu_panel_factor_solve, _lu_panel_rowsolve

    nb, tiles = 8, 8
    d = (rng.standard_normal((nb, nb)) + nb * np.eye(nb)).astype(dtype)
    pan = rng.standard_normal((tiles, nb, nb)).astype(dtype)
    luk, solved = _lu_panel_factor_solve(jnp.asarray(d), jnp.asarray(pan))
    lu = np.asarray(luk, np.float64)
    lo, up = np.tril(lu, -1) + np.eye(nb), np.triu(lu)
    dn, pn = np.asarray(d, np.float64), np.asarray(pan, np.float64)
    tol = 100 * nb * float(np.finfo(dtype).eps)
    assert np.abs(lo @ up - dn).max() < tol * nb * np.abs(dn).max()
    if form == "factor_solve":
        got, rebuilt = np.asarray(solved, np.float64), lambda x: x @ up
    else:
        eye = jnp.eye(nb, dtype=dtype)
        got = np.asarray(_lu_panel_rowsolve(luk, jnp.asarray(pan), eye), np.float64)
        rebuilt = lambda x: lo @ x
    scale = nb * np.abs(pn).max() * max(np.abs(lo).max(), np.abs(up).max())
    assert np.abs(rebuilt(got) - pn).max() < tol * scale


def test_gesv_nopiv_mesh(rng):
    mesh = mesh24()
    n, nrhs = 96, 8
    # diagonally dominant => no-pivot LU is stable (gesv_nopiv contract)
    a = _rand(rng, n, n) + n * jnp.eye(n, dtype=jnp.float64)
    x_true = _rand(rng, n, nrhs)
    b = jnp.asarray(np.asarray(a) @ np.asarray(x_true))
    x, info = gesv_nopiv_mesh(a, b, mesh, nb=16)
    assert int(info) == 0
    err = np.linalg.norm(np.asarray(x) - np.asarray(x_true)) / np.linalg.norm(np.asarray(x_true))
    assert err < 1e-10


@pytest.mark.parametrize("uplo,op", [
    (Uplo.Lower, Op.NoTrans),
    (Uplo.Lower, Op.ConjTrans),
    (Uplo.Upper, Op.NoTrans),
    (Uplo.Upper, Op.Trans),
])
def test_trsm_dist(rng, uplo, op):
    mesh = mesh22()
    n, nrhs = 64, 16
    t = np.tril(np.asarray(_rand(rng, n, n))) + n * np.eye(n)
    if uplo == Uplo.Upper:
        t = t.T
    b = _rand(rng, n, nrhs)
    ad = from_dense(jnp.asarray(t), mesh, nb=16, diag_pad_one=True)
    bd = from_dense(b, mesh, nb=16)
    x = to_dense(trsm_dist(ad, bd, uplo, op))
    opt = t.T if op != Op.NoTrans else t
    err = np.linalg.norm(opt @ np.asarray(x) - np.asarray(b)) / np.linalg.norm(np.asarray(b))
    assert err < 1e-12


def test_gesv_tntpiv_mesh(rng):
    # general NON-diagonally-dominant matrix: real pivoting must happen
    from slate_tpu.parallel import gesv_tntpiv_mesh

    mesh = mesh24()
    for n, nb in [(96, 16), (130, 16)]:
        a = np.asarray(_rand(rng, n, n))
        b = np.asarray(_rand(rng, n, 3))
        x, info = gesv_tntpiv_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
        x = np.asarray(x)
        resid = np.abs(a @ x - b).max() / (np.abs(a).max() * np.abs(x).max() * n)
        assert int(info) == 0
        assert resid < 1e-13, (n, nb, resid)


def test_getrf_tntpiv_dist_factor(rng):
    # PA = LU at the factor level, incl. cross-shard row motion
    from slate_tpu.parallel import getrf_tntpiv_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = np.asarray(_rand(rng, n, n))
    lu, perm, info = getrf_tntpiv_mesh(jnp.asarray(a), mesh, nb=nb)
    lud, perm = np.asarray(to_dense(lu)), np.asarray(perm)
    l = np.tril(lud, -1) + np.eye(n)
    u = np.triu(lud)
    ap = np.pad(a, ((0, perm.shape[0] - n), (0, 0)))[perm][:n]
    assert int(info) == 0
    assert np.abs(ap - l @ u).max() < 1e-12
    assert sorted(perm.tolist()) == list(range(perm.shape[0]))


def test_permute_rows_dist(rng):
    from slate_tpu.parallel import permute_rows_dist

    mesh = mesh22()
    n = 64
    b = np.asarray(_rand(rng, n, 5))
    bd = from_dense(jnp.asarray(b), mesh, nb=16)
    mglob = bd.mt * bd.nb
    perm = np.random.default_rng(3).permutation(mglob)
    out = np.asarray(to_dense(permute_rows_dist(bd, jnp.asarray(perm))))
    bp = np.pad(b, ((0, mglob - n), (0, 0)))[perm][:n]
    np.testing.assert_allclose(out, bp, atol=0)


def test_gesv_tntpiv_mesh_zero_leading_pivot(rng):
    # review-found bug class: winners already inside block k must be
    # position-tracked through earlier swaps; a[0,0]=0 makes the tournament
    # reorder within the leading block (win=[1,0]-style), which the naive
    # original-position swap sim cancelled out, leaving the zero pivot
    from slate_tpu.parallel import gesv_tntpiv_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = np.asarray(_rand(rng, n, n)).copy()
    a[0, 0] = 0.0
    a[1, 0] = 5.0
    b = np.asarray(_rand(rng, n, 2))
    x, info = gesv_tntpiv_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
    x = np.asarray(x)
    assert int(info) == 0
    assert np.isfinite(x).all()
    resid = np.abs(a @ x - b).max() / (np.abs(a).max() * np.abs(x).max() * n)
    assert resid < 1e-13, resid


def test_gesv_tntpiv_mesh_near_singular_column(rng):
    # column 0 mostly zeros: pivot quality must not silently degrade
    from slate_tpu.parallel import gesv_tntpiv_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = np.asarray(_rand(rng, n, n)).copy()
    a[:, 0] = 0.0
    a[40, 0] = 3.0  # the single viable pivot lives deep in another shard
    b = np.asarray(_rand(rng, n, 2))
    x, info = gesv_tntpiv_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
    x = np.asarray(x)
    assert int(info) == 0
    resid = np.abs(a @ x - b).max() / (np.abs(a).max() * np.abs(x).max() * n)
    assert resid < 1e-13, resid


def test_caqr_orthogonality_and_reconstruction(rng):
    # Q Q^H b = b (implicit-Q orthogonality) and A = Q R via unmqr replay
    from slate_tpu.parallel import geqrf_dist, unmqr_dist

    mesh = mesh24()
    m, n, nb = 96, 64, 16
    a = np.asarray(_rand(rng, m, n))
    f = geqrf_dist(from_dense(jnp.asarray(a), mesh, nb))
    b = np.asarray(_rand(rng, m, 3))
    bd = from_dense(jnp.asarray(b), mesh, nb)
    qhb = unmqr_dist(f, bd, Op.ConjTrans)
    back = np.asarray(to_dense(unmqr_dist(f, qhb, Op.NoTrans)))
    assert np.abs(back - b).max() < 1e-12
    r_up = np.triu(np.asarray(to_dense(f.fact))[:n, :n])
    r_ext = np.zeros((m, n))
    r_ext[:n] = r_up
    rd = from_dense(jnp.asarray(r_ext), mesh, nb)
    qr = np.asarray(to_dense(unmqr_dist(f, rd, Op.NoTrans)))
    assert np.abs(qr - a).max() / np.abs(a).max() < 1e-13


def test_gels_mesh(rng):
    from slate_tpu.parallel import gels_mesh

    mesh = mesh24()
    # least-squares optimality on an overdetermined system
    m, n, nb = 96, 64, 16
    a = np.asarray(_rand(rng, m, n))
    b = np.asarray(_rand(rng, m, 3))
    x, info = gels_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
    x = np.asarray(x)
    opt = np.abs(a.T @ (a @ x - b)).max() / (np.abs(a).max() ** 2 * np.abs(b).max())
    assert int(info) == 0 and opt < 1e-12
    # consistent system at a non-multiple size solves exactly
    m, n = 130, 70
    a = np.asarray(_rand(rng, m, n))
    xt = np.asarray(_rand(rng, n, 2))
    x, info = gels_mesh(jnp.asarray(a), jnp.asarray(a @ xt), mesh, nb=nb)
    assert int(info) == 0
    assert np.abs(np.asarray(x) - xt).max() < 1e-10


def test_caqr_single_tile_rows(rng):
    # mtl == 1 (one tile per mesh row): rowless devices must not clobber
    # their clamped tile slot with the zeroed gather copy (review/debug
    # found the replay wiping rows at panels they do not participate in)
    from slate_tpu.parallel import geqrf_dist, unmqr_dist
    from slate_tpu.parallel.mesh import make_mesh
    from conftest import cpu_devices

    mesh = make_mesh(2, 1, devices=cpu_devices(2))
    m = n = 32
    a = np.asarray(_rand(rng, m, n))
    f = geqrf_dist(from_dense(jnp.asarray(a), mesh, 16))
    b = np.asarray(_rand(rng, m, 2))
    bd = from_dense(jnp.asarray(b), mesh, 16)
    rt = np.asarray(to_dense(unmqr_dist(f, unmqr_dist(f, bd, Op.ConjTrans), Op.NoTrans)))
    assert np.abs(rt - b).max() < 1e-12
    qa = np.asarray(to_dense(unmqr_dist(f, from_dense(jnp.asarray(a), mesh, 16), Op.ConjTrans)))
    r_up = np.triu(np.asarray(to_dense(f.fact))[:n, :n])
    assert np.abs(qa[:n] - r_up).max() < 1e-12


def test_norm_dist(rng):
    from slate_tpu.parallel import norm_dist
    from slate_tpu.types import Norm

    mesh = mesh24()
    m, n, nb = 90, 70, 16  # non-multiples: pad masking matters
    a = np.asarray(_rand(rng, m, n))
    # diag_pad_one writes 1s into the pad region; norms must mask them out
    ad = from_dense(jnp.asarray(a), mesh, nb, diag_pad_one=True)
    for nt, ref in [
        (Norm.Max, np.abs(a).max()),
        (Norm.Fro, np.linalg.norm(a)),
        (Norm.One, np.abs(a).sum(0).max()),
        (Norm.Inf, np.abs(a).sum(1).max()),
    ]:
        assert abs(float(norm_dist(nt, ad)) - ref) < 1e-10 * max(1, ref)


def test_herk_dist(rng):
    from slate_tpu.parallel import herk_dist

    mesh = mesh24()
    a = np.asarray(_rand(rng, 90, 70))
    ad = from_dense(jnp.asarray(a), mesh, 16, diag_pad_one=True)
    ref = a @ a.T
    cd = np.asarray(to_dense(herk_dist(1.0, ad, full=True)))
    assert np.abs(cd - ref).max() < 1e-11
    cl = np.asarray(to_dense(herk_dist(1.0, ad, uplo=Uplo.Lower)))
    assert np.abs(np.tril(cl) - np.tril(ref)).max() < 1e-11
    assert np.abs(np.triu(cl, 1)).max() == 0


@pytest.mark.parametrize("uplo,op", [
    (Uplo.Lower, Op.NoTrans), (Uplo.Lower, Op.Trans),
    (Uplo.Upper, Op.NoTrans), (Uplo.Upper, Op.ConjTrans),
])
def test_trsm_dist_right(rng, uplo, op):
    from slate_tpu.parallel import trsm_dist_right

    mesh = mesh24()
    m, n, nb = 90, 70, 16
    t = np.tril(np.asarray(_rand(rng, n, n))) + n * np.eye(n)
    if uplo == Uplo.Upper:
        t = t.T
    b = np.asarray(_rand(rng, m, n))
    td = from_dense(jnp.asarray(t), mesh, nb, diag_pad_one=True)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    x = np.asarray(to_dense(trsm_dist_right(td, bd, uplo, op)))
    opa = t.T if op != Op.NoTrans else t
    assert np.abs(x @ opa - b).max() / np.abs(b).max() < 1e-11


def test_redistribute_device_side(rng):
    from slate_tpu.parallel import redistribute

    mesh = mesh24()
    a = np.asarray(_rand(rng, 90, 70))
    ad = from_dense(jnp.asarray(a), mesh, 16)
    d2 = redistribute(ad, make_mesh(4, 2, devices=cpu_devices(8)))
    assert np.abs(np.asarray(to_dense(d2)) - a).max() == 0
    d3 = redistribute(ad, mesh22(), nb=32)  # mesh AND nb change
    assert np.abs(np.asarray(to_dense(d3)) - a).max() == 0


@pytest.mark.parametrize("grid2", [(4, 2), (1, 8)])
def test_redistribute_shardmap_matches_eager(rng, grid2):
    """ISSUE 12: the shard_map ppermute redistribution is BITWISE the
    eager path on a ragged-tail operand, non-square grids included."""
    from slate_tpu.parallel import redistribute

    mesh = mesh24()
    a = np.asarray(_rand(rng, 90, 70))
    ad = from_dense(jnp.asarray(a), mesh, 16)
    m2 = make_mesh(*grid2, devices=cpu_devices(8))
    ea = redistribute(ad, m2, impl="eager")
    sm = redistribute(ad, m2, impl="shardmap")
    assert (ea.m, ea.n, ea.nb, ea.diag_pad) == (sm.m, sm.n, sm.nb, sm.diag_pad)
    np.testing.assert_array_equal(np.asarray(ea.tiles), np.asarray(sm.tiles))
    assert np.abs(np.asarray(to_dense(sm)) - a).max() == 0


def test_redistribute_shardmap_psum_era_grid(rng):
    """The 4-device 2x2 grid (the psum-era harness shape) through the
    shardmap exchange, including a reshape to a degenerate 4x1 ring."""
    from slate_tpu.parallel import redistribute

    mesh = mesh22()
    a = np.asarray(_rand(rng, 52, 52))
    ad = from_dense(jnp.asarray(a), mesh, 16)
    m2 = make_mesh(4, 1, devices=cpu_devices(4))
    ea = redistribute(ad, m2, impl="eager")
    sm = redistribute(ad, m2, impl="shardmap")
    np.testing.assert_array_equal(np.asarray(ea.tiles), np.asarray(sm.tiles))
    assert np.abs(np.asarray(to_dense(sm)) - a).max() == 0


def test_redistribute_roundtrip_bitwise(rng):
    """ISSUE 12 satellite (the pad-tile diagonal bug class): a
    redistribute → redistribute round trip with mesh reshape AND nb
    change is bitwise, and a diag-padded factorization operand KEEPS its
    identity pad (flag and bytes) through every reshape."""
    from slate_tpu.core.tiling import from_cyclic
    from slate_tpu.parallel import redistribute

    mesh = mesh24()
    a = _spd(rng, 90)
    d = from_dense(a, mesh, 16, diag_pad_one=True)
    m42 = make_mesh(4, 2, devices=cpu_devices(8))
    d2 = redistribute(d, m42, nb=32)  # mesh + nb change (eager retile)
    assert d2.diag_pad  # pre-fix this flag was dropped by the retile
    d2.require_diag_pad("roundtrip")  # i.e. factorizations accept it
    d3 = redistribute(d2, mesh, nb=16)  # round-trip back
    assert d3.diag_pad
    np.testing.assert_array_equal(np.asarray(d3.tiles), np.asarray(d.tiles))
    # a GROWN tile grid gets fresh identity pad tiles (both lowerings):
    # 40/16 -> 3 data tiles, lcm(2,4)=4 grid -> lcm(1,8)=8 grid
    small = from_dense(a[:40, :40], mesh, 16, diag_pad_one=True)
    m18 = make_mesh(1, 8, devices=cpu_devices(8))
    for impl in ("eager", "shardmap"):
        g = redistribute(small, m18, impl=impl)
        assert g.diag_pad, impl
        logi = np.asarray(from_cyclic(g.tiles, 1, 8))
        for t in range(3, 8):
            np.testing.assert_array_equal(
                logi[t, t], np.eye(16), err_msg=f"{impl} pad tile {t}")


def test_posv_self_check_fully_distributed(rng):
    # the residual pipeline never gathers to one host: potrf + trsm + SUMMA
    # + distributed Fro norms (VERDICT round-1 item 7)
    from slate_tpu.parallel import norm_dist, potrf_dist
    from slate_tpu.types import Norm

    mesh = mesh24()
    n, nb = 96, 16
    spd = np.asarray(_spd(rng, n))
    b = np.asarray(_rand(rng, n, 8))
    ad = from_dense(jnp.asarray(spd), mesh, nb, diag_pad_one=True)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    l, info = potrf_dist(ad)
    y = trsm_dist(l, bd, Uplo.Lower, Op.NoTrans)
    xd = trsm_dist(l, y, Uplo.Lower, Op.ConjTrans)
    rd = gemm_summa(1.0, from_dense(jnp.asarray(spd), mesh, nb), xd, -1.0, bd)
    resid = float(norm_dist(Norm.Fro, rd)) / float(norm_dist(Norm.Fro, bd))
    assert int(info) == 0
    assert resid < 1e-12


def test_heev_mesh(rng):
    from slate_tpu.parallel import heev_mesh

    n = 96
    a = _rand(rng, n, n)
    a = (a + a.T) / 2
    w, z = heev_mesh(a, mesh24(), nb=16)
    an, zn, wn = np.asarray(a), np.asarray(z), np.asarray(w)
    wref = np.linalg.eigvalsh(an)
    eps = np.finfo(np.float64).eps
    assert np.abs(np.sort(wn) - wref).max() < 50 * n * eps * max(1, np.abs(wref).max())
    assert np.abs(an @ zn - zn * wn).max() < 50 * n * eps * max(1, np.abs(wref).max())
    assert np.abs(zn.T @ zn - np.eye(n)).max() < 50 * n * eps
    # values-only path
    w2 = heev_mesh(a, mesh24(), nb=16, want_vectors=False)
    assert np.abs(np.sort(np.asarray(w2)) - wref).max() < 50 * n * eps * max(
        1, np.abs(wref).max()
    )


def test_heev_mesh_complex(rng):
    from slate_tpu.parallel import heev_mesh

    n = 64
    a = _rand(rng, n, n, np.complex128)
    a = (a + jnp.conj(a).T) / 2
    w, z = heev_mesh(a, mesh22(), nb=16)
    an, zn, wn = np.asarray(a), np.asarray(z), np.asarray(w)
    wref = np.linalg.eigvalsh(an)
    eps = np.finfo(np.float64).eps
    scale = max(1, np.abs(wref).max())
    assert np.abs(np.sort(wn) - wref).max() < 50 * n * eps * scale
    assert np.abs(an @ zn - zn * wn).max() < 50 * n * eps * scale
    assert np.abs(zn.conj().T @ zn - np.eye(n)).max() < 50 * n * eps


@pytest.mark.slow  # tier-1 budget relief (ISSUE 11): 44 s of accuracy
# sweeps; distributed SVD stays tier-1-covered by test_svd_mesh_complex,
# and the full CI pytest pass still runs these
@pytest.mark.parametrize("shape", [(80, 64), (64, 96), (100, 100)])
def test_svd_mesh(rng, shape):
    from slate_tpu.parallel import svd_mesh

    m, n = shape
    a = _rand(rng, m, n)
    u, s, vh = svd_mesh(a, mesh24(), nb=16)
    an, un, sn, vn = np.asarray(a), np.asarray(u), np.asarray(s), np.asarray(vh)
    sref = np.linalg.svd(an, compute_uv=False)
    k = min(m, n)
    eps = np.finfo(np.float64).eps
    scale = max(1, sref.max())
    assert np.abs(sn - sref).max() < 50 * k * eps * scale
    assert np.abs(an - (un * sn) @ vn).max() < 50 * k * eps * scale
    assert np.abs(un.conj().T @ un - np.eye(un.shape[1])).max() < 50 * k * eps
    assert np.abs(vn @ vn.conj().T - np.eye(vn.shape[0])).max() < 50 * k * eps
    svals = svd_mesh(a, mesh24(), nb=16, want_vectors=False)
    assert np.abs(np.asarray(svals) - sref).max() < 50 * k * eps * scale


def test_he2hb_dist_band_structure(rng):
    """Stage-1 output really is banded and orthogonally similar to A."""
    from slate_tpu.parallel import from_dense, he2hb_dist, to_dense

    n, nb = 64, 16
    a = _rand(rng, n, n)
    a = (a + a.T) / 2
    f = he2hb_dist(from_dense(a, mesh24(), nb))
    band = np.asarray(to_dense(f.band))
    # outside the band: zero
    ii, jj = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    out = np.abs(ii - jj) > nb
    assert np.abs(band[out]).max() < 1e-12
    # same spectrum
    wref = np.linalg.eigvalsh(np.asarray(a))
    wband = np.linalg.eigvalsh(0.5 * (band + band.T))
    assert np.abs(wref - wband).max() < 1e-11


# ---------------------------------------------------------------------------
# partial-pivot mesh LU (src/getrf.cc default; VERDICT r2 missing item 1)
# ---------------------------------------------------------------------------


def _check_pp_factor(a, lu, perm, n):
    lud, perm = np.asarray(to_dense(lu)), np.asarray(perm)
    l = np.tril(lud, -1) + np.eye(n)
    u = np.triu(lud)
    ap = np.pad(np.asarray(a), ((0, perm.shape[0] - n), (0, 0)))[perm][:n]
    assert np.abs(ap - l @ u).max() < 1e-12
    assert sorted(perm.tolist()) == list(range(perm.shape[0]))
    # partial pivoting invariant: |L| <= 1 everywhere
    assert np.abs(l).max() <= 1.0 + 1e-14


def test_getrf_pp_mesh_factor(rng):
    from slate_tpu.parallel import getrf_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = _rand(rng, n, n)
    lu, perm, info = getrf_mesh(a, mesh, nb=nb)
    assert int(info) == 0
    _check_pp_factor(a, lu, perm, n)


def test_getrf_pp_mesh_matches_lapack_pivots(rng):
    # same pivot choices as scipy's LAPACK getrf on a matrix with distinct
    # column maxima (no ties): the mesh partial pivot IS partial pivoting
    import scipy.linalg as sla
    from slate_tpu.parallel import getrf_mesh

    mesh = mesh22()
    n, nb = 48, 16
    a = np.asarray(_rand(rng, n, n))
    lu, perm, info = getrf_mesh(jnp.asarray(a), mesh, nb=nb)
    assert int(info) == 0
    lud = np.asarray(to_dense(lu))
    lu_ref, piv = sla.lu_factor(a)
    np.testing.assert_allclose(lud[:n, :n], lu_ref, rtol=0, atol=1e-11)


def test_gesv_pp_mesh_zero_leading_pivot(rng):
    from slate_tpu.parallel import gesv_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = np.asarray(_rand(rng, n, n)).copy()
    a[0, 0] = 0.0
    a[1, 0] = 5.0
    b = np.asarray(_rand(rng, n, 2))
    x, info = gesv_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
    x = np.asarray(x)
    assert int(info) == 0
    assert np.isfinite(x).all()
    resid = np.abs(a @ x - b).max() / (np.abs(a).max() * np.abs(x).max() * n)
    assert resid < 1e-13, resid


def test_gesv_pp_mesh_near_singular_column(rng):
    from slate_tpu.parallel import gesv_mesh

    mesh = mesh24()
    n, nb = 64, 16
    a = np.asarray(_rand(rng, n, n)).copy()
    a[:, 0] = 0.0
    a[40, 0] = 3.0  # the single viable pivot lives deep in another shard
    b = np.asarray(_rand(rng, n, 2))
    x, info = gesv_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=nb)
    x = np.asarray(x)
    assert int(info) == 0
    resid = np.abs(a @ x - b).max() / (np.abs(a).max() * np.abs(x).max() * n)
    assert resid < 1e-13, resid


def test_getrf_pp_mesh_singular_info(rng):
    from slate_tpu.parallel import getrf_mesh

    mesh = mesh22()
    n, nb = 32, 16
    a = np.asarray(_rand(rng, n, n)).copy()
    a[:, 5] = 0.0  # exactly singular: U[5,5] = 0 after elimination
    lu, perm, info = getrf_mesh(jnp.asarray(a), mesh, nb=nb)
    assert int(info) == 6  # 1-based first zero pivot


# ---------------------------------------------------------------------------
# mesh BLAS-3 fill: hemm/symm, trmm, her2k/syr2k (VERDICT r2 missing item 3)
# ---------------------------------------------------------------------------


def test_transpose_dist(rng):
    from slate_tpu.parallel.dist_blas3 import transpose_dist

    mesh = mesh24()
    a = _rand(rng, 80, 48, np.complex128)
    d = from_dense(a, mesh, nb=16)
    out = np.asarray(to_dense(transpose_dist(d, conj=True)))
    np.testing.assert_allclose(out, np.asarray(a).conj().T, atol=0)


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("conj", [True, False])
def test_hemm_symm_dist_left(rng, uplo, conj):
    from slate_tpu.parallel.dist_blas3 import hemm_summa
    from slate_tpu.types import Side

    mesh = mesh24()
    n, nrhs, nb = 64, 32, 16
    g = np.asarray(_rand(rng, n, n, np.complex128))
    herm = (g + g.conj().T) / 2 if conj else (g + g.T) / 2
    b = np.asarray(_rand(rng, n, nrhs, np.complex128))
    # poison the dead triangle: the kernel must never read it
    stored = herm.copy()
    dead = np.triu(np.ones((n, n), bool), 1) if uplo == Uplo.Lower else np.tril(np.ones((n, n), bool), -1)
    stored[dead] = 1e6
    ad = from_dense(jnp.asarray(stored), mesh, nb)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    out = np.asarray(to_dense(hemm_summa(Side.Left, 2.0, ad, bd, uplo=uplo, conj=conj)))
    ref = 2.0 * herm @ b
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12


@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
@pytest.mark.parametrize("conj", [True, False])
def test_hemm_stationary_a(rng, uplo, conj):
    # hemmA (src/hemmA.cc): stationary-A schedule, thin B/C (r5 item 7);
    # the auto-selector must pick it for a thin panel
    from slate_tpu.parallel.dist_blas3 import hemm_summa
    from slate_tpu.types import MethodHemm, Side, select_hemm_method

    mesh = mesh24()
    n, nrhs, nb = 96, 8, 8
    g = np.asarray(_rand(rng, n, n, np.complex128))
    herm = (g + g.conj().T) / 2 if conj else (g + g.T) / 2
    b = np.asarray(_rand(rng, n, nrhs, np.complex128))
    stored = herm.copy()
    dead = np.triu(np.ones((n, n), bool), 1) if uplo == Uplo.Lower else np.tril(np.ones((n, n), bool), -1)
    stored[dead] = 1e6  # the kernel must never read the dead triangle
    ad = from_dense(jnp.asarray(stored), mesh, nb)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    out = np.asarray(to_dense(hemm_summa(
        Side.Left, 2.0, ad, bd, uplo=uplo, conj=conj, method=MethodHemm.HemmA
    )))
    ref = 2.0 * herm @ b
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12
    assert select_hemm_method(n // nb, nrhs // nb) == MethodHemm.HemmA


def test_hemm_dist_right(rng):
    from slate_tpu.parallel.dist_blas3 import hemm_summa
    from slate_tpu.types import Side

    mesh = mesh22()
    n, mr, nb = 48, 32, 16
    g = np.asarray(_rand(rng, n, n, np.complex128))
    herm = (g + g.conj().T) / 2
    b = np.asarray(_rand(rng, mr, n, np.complex128))
    ad = from_dense(jnp.asarray(herm), mesh, nb)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    out = np.asarray(to_dense(hemm_summa(Side.Right, 1.5, ad, bd)))
    ref = 1.5 * b @ herm
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12


@pytest.mark.parametrize("op", [Op.NoTrans, Op.Trans, Op.ConjTrans])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
def test_trmm_dist_left(rng, op, uplo):
    from slate_tpu.parallel.dist_blas3 import trmm_dist
    from slate_tpu.types import Side

    mesh = mesh24()
    n, nrhs, nb = 64, 16, 16
    a = np.asarray(_rand(rng, n, n, np.complex128))
    t = np.tril(a) if uplo == Uplo.Lower else np.triu(a)
    b = np.asarray(_rand(rng, n, nrhs, np.complex128))
    ad = from_dense(jnp.asarray(a), mesh, nb)  # full stored; kernel masks
    bd = from_dense(jnp.asarray(b), mesh, nb)
    out = np.asarray(to_dense(trmm_dist(Side.Left, uplo, op, Diag.NonUnit, 1.0, ad, bd)))
    opt = {Op.NoTrans: t, Op.Trans: t.T, Op.ConjTrans: t.conj().T}[op]
    ref = opt @ b
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12


def test_trmm_dist_unit_and_right(rng):
    from slate_tpu.parallel.dist_blas3 import trmm_dist
    from slate_tpu.types import Side

    mesh = mesh22()
    n, mr, nb = 48, 32, 16
    a = np.asarray(_rand(rng, n, n))
    t = np.tril(a, -1) + np.eye(n)
    b = np.asarray(_rand(rng, mr, n))
    ad = from_dense(jnp.asarray(a), mesh, nb)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    out = np.asarray(to_dense(trmm_dist(Side.Right, Uplo.Lower, Op.NoTrans, Diag.Unit, 1.0, ad, bd)))
    ref = b @ t
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12


@pytest.mark.parametrize("conj", [True, False])
def test_her2k_syr2k_dist(rng, conj):
    from slate_tpu.parallel.dist_blas3 import her2k_dist
    from slate_tpu.parallel import norm_dist

    mesh = mesh24()
    n, k, nb = 64, 48, 16
    a = np.asarray(_rand(rng, n, k, np.complex128))
    b = np.asarray(_rand(rng, n, k, np.complex128))
    ad = from_dense(jnp.asarray(a), mesh, nb)
    bd = from_dense(jnp.asarray(b), mesh, nb)
    alpha = 1.0 + (0.5j if conj else 0.0)
    out = np.asarray(to_dense(her2k_dist(alpha, ad, bd, conj=conj, full=True)))
    if conj:
        ref = alpha * a @ b.conj().T + np.conj(alpha) * b @ a.conj().T
    else:
        ref = alpha * a @ b.T + alpha * b @ a.T
    assert np.abs(out - ref).max() / np.abs(ref).max() < 1e-12


def test_svd_mesh_complex(rng):
    # ADVICE r2: the complex path through ge2tb_dist's LQ conjugation and
    # the pu/pv phase handling in the mesh driver was untested
    from slate_tpu.parallel import svd_mesh

    m, n = 72, 56
    a = _rand(rng, m, n, np.complex128)
    u, s, vh = svd_mesh(a, mesh22(), nb=16)
    an, un, sn, vn = np.asarray(a), np.asarray(u), np.asarray(s), np.asarray(vh)
    sref = np.linalg.svd(an, compute_uv=False)
    k = min(m, n)
    eps = np.finfo(np.float64).eps
    scale = max(1, sref.max())
    assert np.abs(sn - sref).max() < 50 * k * eps * scale
    assert np.abs(an - (un * sn) @ vn).max() < 50 * k * eps * scale
    assert np.abs(un.conj().T @ un - np.eye(un.shape[1])).max() < 50 * k * eps
    assert np.abs(vn @ vn.conj().T - np.eye(vn.shape[0])).max() < 50 * k * eps


def test_stedc_dist(rng):
    # VERDICT r2 item 6: the D&C merge tree sharded over the mesh — secular
    # roots over the column axis, eigenvector rows over the row axis
    from slate_tpu.parallel.dist_stedc import stedc_dist

    n = 200  # pads to N=256: exercises pad-block merges too
    d = np.asarray(_rand(rng, n, 1))[:, 0]
    e = np.asarray(_rand(rng, n - 1, 1))[:, 0]
    w, z = stedc_dist(jnp.asarray(d), jnp.asarray(e), mesh24())
    w, z = np.asarray(w), np.asarray(z)
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    wref = np.linalg.eigvalsh(T)
    eps = np.finfo(np.float64).eps
    scale = max(1, np.abs(wref).max())
    assert np.abs(w - wref).max() < 50 * n * eps * scale
    assert np.abs(T @ z - z * w).max() < 50 * n * eps * scale
    assert np.abs(z.T @ z - np.eye(n)).max() < 50 * n * eps


def test_stedc_dist_deflation_heavy(rng):
    # repeated eigenvalues force the Givens-deflation path across shards
    from slate_tpu.parallel.dist_stedc import stedc_dist

    n = 128
    d = np.repeat(np.arange(n // 4), 4).astype(np.float64)
    e = np.full(n - 1, 1e-3)
    w, z = stedc_dist(jnp.asarray(d), jnp.asarray(e), mesh24())
    w, z = np.asarray(w), np.asarray(z)
    T = np.diag(d) + np.diag(e, 1) + np.diag(e, -1)
    wref = np.linalg.eigvalsh(T)
    eps = np.finfo(np.float64).eps
    assert np.abs(w - wref).max() < 100 * n * eps * max(1, np.abs(wref).max())
    assert np.abs(T @ z - z * w).max() < 100 * n * eps * max(1, np.abs(wref).max())
    assert np.abs(z.T @ z - np.eye(n)).max() < 100 * n * eps


def test_heev_mesh_distributed_solver(rng):
    from slate_tpu.parallel import heev_mesh

    n = 96
    a = _rand(rng, n, n)
    a = (a + a.T) / 2
    w, z = heev_mesh(a, mesh24(), nb=16)
    an, zn, wn = np.asarray(a), np.asarray(z), np.asarray(w)
    wref = np.linalg.eigvalsh(an)
    eps = np.finfo(np.float64).eps
    scale = max(1, np.abs(wref).max())
    assert np.abs(np.sort(wn) - wref).max() < 50 * n * eps * scale
    assert np.abs(an @ zn - zn * wn).max() < 50 * n * eps * scale
    assert np.abs(zn.T @ zn - np.eye(n)).max() < 50 * n * eps


# ---------------------------------------------------------------------------
# mixed-precision mesh solvers + distributed inverses (VERDICT r2 items 4/8)
# ---------------------------------------------------------------------------


def test_posv_mixed_mesh(rng):
    from slate_tpu.parallel import posv_mixed_mesh

    mesh = mesh24()
    n = 96
    a = np.asarray(_spd(rng, n))
    b = np.asarray(_rand(rng, n, 3))
    x, iters, info = posv_mixed_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=16)
    assert int(info) == 0
    assert 0 <= int(iters) <= 3  # well-conditioned: converges in <= 3
    resid = np.abs(a @ np.asarray(x) - b).max() / (np.abs(a).max() * np.abs(np.asarray(x)).max() * n)
    assert resid < 1e-14, resid  # f64-grade answer from an f32 factor


def test_gesv_mixed_mesh(rng):
    from slate_tpu.parallel import gesv_mixed_mesh

    mesh = mesh24()
    n = 96
    a = np.asarray(_rand(rng, n, n)) + n * np.eye(n)
    b = np.asarray(_rand(rng, n, 2))
    x, iters, info = gesv_mixed_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=16)
    assert int(info) == 0
    assert 0 <= int(iters) <= 3
    resid = np.abs(a @ np.asarray(x) - b).max() / (np.abs(a).max() * np.abs(np.asarray(x)).max() * n)
    assert resid < 1e-14, resid


def test_posv_mixed_mesh_failed_factor_returns_nan(rng):
    # non-SPD input: info != 0 and x is NaN-filled — a caller that skips
    # the info check cannot mistake the RHS for a solution (ADVICE r3)
    from slate_tpu.parallel import posv_mixed_mesh

    mesh = mesh24()
    n = 96
    a = -np.eye(n)  # negative definite: f32 potrf must fail
    b = np.asarray(_rand(rng, n, 2))
    x, iters, info = posv_mixed_mesh(jnp.asarray(a), jnp.asarray(b), mesh, nb=16)
    assert int(info) != 0
    assert int(iters) == -1
    assert np.all(np.isnan(np.asarray(x)))


def test_getri_potri_mesh(rng):
    from slate_tpu.parallel import getri_mesh, potri_mesh

    mesh = mesh22()
    n = 64
    a = np.asarray(_rand(rng, n, n))
    inv, info = getri_mesh(jnp.asarray(a), mesh, nb=16)
    assert int(info) == 0
    assert np.abs(a @ np.asarray(inv) - np.eye(n)).max() < 1e-10
    s = np.asarray(_spd(rng, n))
    sinv, info2 = potri_mesh(jnp.asarray(s), mesh, nb=16)
    assert int(info2) == 0
    assert np.abs(s @ np.asarray(sinv) - np.eye(n)).max() < 1e-9


# ---------------------------------------------------------------------------
# non-uniform block sizes + GridOrder (func.hh:39-203 parity, ref ex13)
# ---------------------------------------------------------------------------


def test_nonuniform_roundtrip_and_gemm(rng):
    from slate_tpu.parallel import (
        from_dense_nonuniform, gemm_summa, to_dense_nonuniform,
    )

    mesh = mesh24()
    rowsz = [16, 8, 24, 16, 8, 24]
    colsz = [8, 24, 16, 8, 24, 16]
    a = _rand(rng, 96, 96)
    b = _rand(rng, 96, 96)
    ad = from_dense_nonuniform(a, mesh, rowsz, colsz)
    assert ad.nb == 24  # max block size
    back = to_dense_nonuniform(ad, rowsz, colsz)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(a))
    bd = from_dense_nonuniform(b, mesh, colsz, rowsz)
    c = to_dense_nonuniform(gemm_summa(1.0, ad, bd), rowsz, rowsz)
    ref = np.asarray(a) @ np.asarray(b)
    assert np.abs(np.asarray(c) - ref).max() < 1e-12


def test_nonuniform_size_mismatch_raises(rng):
    from slate_tpu.parallel import from_dense_nonuniform

    with pytest.raises(ValueError):
        from_dense_nonuniform(_rand(rng, 64, 64), mesh22(), [32, 16], [32, 32])


def test_nonuniform_factorizations(rng):
    # ex13 parity (VERDICT r5 item 6): real algorithms on non-uniformly
    # tiled input — Cholesky and pivoted LU end-to-end through the
    # device-resident non-uniform -> uniform redistribution
    from slate_tpu.parallel import (
        from_dense_nonuniform, redistribute_nonuniform, to_dense,
        trsm_dist, from_dense,
    )
    from slate_tpu.parallel.dist_chol import potrf_dist
    from slate_tpu.parallel.dist_lu import getrf_pp_dist, permute_rows_dist

    mesh = mesh24()
    n = 96
    rowsz = [16, 8, 24, 16, 8, 24]
    a = _spd(rng, n)
    ad_nu = from_dense_nonuniform(a, mesh, rowsz, rowsz)
    ad = redistribute_nonuniform(ad_nu, rowsz, rowsz, nb=16, diag_pad_one=True)
    l, info = potrf_dist(ad)
    assert int(info) == 0
    ld = np.tril(np.asarray(to_dense(l)))
    assert np.abs(ld @ ld.T - np.asarray(a)).max() / np.abs(np.asarray(a)).max() < 1e-12

    g = _rand(rng, n, n)
    gd_nu = from_dense_nonuniform(g, mesh, rowsz, rowsz)
    gd = redistribute_nonuniform(gd_nu, rowsz, rowsz, nb=16, diag_pad_one=True)
    lu, perm, info2 = getrf_pp_dist(gd)
    assert int(info2) == 0
    b = _rand(rng, n, 4)
    bd = permute_rows_dist(from_dense(b, mesh, 16), perm)
    y = trsm_dist(lu, bd, Uplo.Lower, Op.NoTrans, Diag.Unit)
    x = to_dense(trsm_dist(lu, y, Uplo.Upper, Op.NoTrans))
    resid = np.abs(np.asarray(g) @ np.asarray(x) - np.asarray(b)).max()
    assert resid / np.abs(np.asarray(b)).max() < 1e-10


def test_grid_order_col(rng):
    from slate_tpu.parallel import gemm_mesh
    from slate_tpu.types import GridOrder

    from slate_tpu.parallel import make_mesh as mk
    mesh = mk(2, 4, devices=cpu_devices(8), order=GridOrder.Col)
    a, b = _rand(rng, 64, 48), _rand(rng, 48, 32)
    c = gemm_mesh(1.0, a, b, mesh, nb=16)
    ref = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(c), ref, rtol=1e-12, atol=1e-10)
    # Col vs Row order place device k at transposed grid coordinates
    mrow = mk(2, 4, devices=cpu_devices(8), order=GridOrder.Row)
    dcol = np.asarray(mesh.devices)
    drow = np.asarray(mrow.devices)
    assert dcol[1, 0] == drow[0, 1]  # device k=1: (1,0) in Col vs (0,1) in Row


# ---------------------------------------------------------------------------
# mesh band drivers (src/gbmm.cc, hbmm.cc, tbsm.cc, gbsv, pbsv on the mesh)
# ---------------------------------------------------------------------------


def _band(rng, n, kl, ku):
    a = np.asarray(_rand(rng, n, n)).copy()
    for i in range(n):
        for j in range(n):
            if j < i - kl or j > i + ku:
                a[i, j] = 0.0
    return a


def test_gbmm_hbmm_mesh(rng):
    from slate_tpu.parallel import gbmm_mesh, hbmm_mesh
    from slate_tpu.types import Side

    mesh = mesh22()
    n, kl, ku = 64, 5, 3
    ab = _band(rng, n, kl, ku)
    b = np.asarray(_rand(rng, n, 8))
    c = np.asarray(gbmm_mesh(1.0, jnp.asarray(ab), kl, ku, jnp.asarray(b), mesh, nb=16))
    assert np.abs(c - ab @ b).max() < 1e-12
    hb = _band(rng, n, 4, 4)
    hb = (hb + hb.T) / 2
    c2 = np.asarray(hbmm_mesh(Side.Left, 1.0, jnp.asarray(hb), 4, jnp.asarray(b), mesh, nb=16))
    assert np.abs(c2 - hb @ b).max() < 1e-12


def test_tbsm_pbsv_gbsv_mesh(rng):
    from slate_tpu.parallel import gbsv_mesh, pbsv_mesh, tbsm_mesh

    mesh = mesh22()
    n, kd = 64, 6
    t = np.tril(_band(rng, n, kd, 0)) + n * np.eye(n)
    b = np.asarray(_rand(rng, n, 4))
    x = np.asarray(tbsm_mesh(jnp.asarray(t), kd, jnp.asarray(b), mesh, nb=16))
    assert np.abs(t @ x - b).max() / np.abs(b).max() < 1e-12
    hb = _band(rng, n, kd, kd)
    spd = hb @ hb.T + n * np.eye(n)
    spd_band = np.where(np.abs(np.subtract.outer(np.arange(n), np.arange(n))) <= 2 * kd, spd, 0)
    xs, info = pbsv_mesh(jnp.asarray(spd_band), jnp.asarray(b), 2 * kd, mesh, nb=16)
    assert int(info) == 0
    assert np.abs(spd_band @ np.asarray(xs) - b).max() / np.abs(b).max() < 1e-10
    gb = _band(rng, n, 4, 7) + n * np.eye(n)
    xg, info2 = gbsv_mesh(jnp.asarray(gb), jnp.asarray(b), 4, 7, mesh, nb=16)
    assert int(info2) == 0
    assert np.abs(gb @ np.asarray(xg) - b).max() / np.abs(b).max() < 1e-12


def test_band_mesh_kernels_band_cost(rng):
    # VERDICT r5 item 8 gate: the windowed band kernels do O(n k^2)-class
    # work — their compiled flop count must sit far below the dense mesh
    # factorization's O(n^3)-class count at the same size
    from slate_tpu.parallel.dist_chol import _pbtrf_band_jit, _potrf_jit
    from slate_tpu.parallel.dist_lu import _gb_pp_jit, _pp_jit
    from slate_tpu.parallel import from_dense

    mesh = mesh24()
    n, nb, kd = 512, 16, 32
    tiles = from_dense(jnp.eye(n), mesh, nb, diag_pad_one=True).tiles
    nt = n // nb
    wd = ((nb - 1) + kd) // nb + 1

    def flops(compiled):
        # cost_analysis returns one dict on newer JAX, a per-device list
        # of dicts on 0.4.x
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return ca["flops"]

    # lowering pinned to psum + the xla panel/update forms: the
    # flop-class gate is impl-independent (ppermute adds bytes
    # bookkeeping, not flops; the fused update kernels change
    # dispatch count, not flop class) but the jits now take the
    # bcast-impl / update-impl static args
    dense = _potrf_jit.lower(
        tiles, mesh, 2, 4, nt, 1, "psum", "xla"
    ).compile()
    band = _pbtrf_band_jit.lower(tiles, mesh, 2, 4, nt, wd, 1, "psum").compile()
    assert flops(band) < flops(dense) / 4, (flops(band), flops(dense))

    dense_lu = _pp_jit.lower(
        tiles, mesh, 2, 4, nt, n, 1, "psum"
    ).compile()
    wd_u = ((nb - 1) + 2 * kd) // nb + 1
    wd_usw = ((nb - 1) + 3 * kd) // nb + 1
    band_lu = _gb_pp_jit.lower(
        tiles, mesh, 2, 4, nt, n, wd, wd_u, wd_usw, "psum"
    ).compile()
    assert flops(band_lu) < flops(dense_lu) / 4, (flops(band_lu), flops(dense_lu))


def test_band_mesh_wide_band(rng):
    # windowed kernels with kd wide enough that the window IS the grid:
    # degenerates to the dense schedule, stays correct
    from slate_tpu.parallel import pbsv_mesh

    mesh = mesh22()
    n, kd = 64, 60
    hb = _band(rng, n, kd, kd)
    spd = hb @ hb.T + n * np.eye(n)
    spd = np.where(np.abs(np.subtract.outer(np.arange(n), np.arange(n))) <= kd, spd, 0)
    b = np.asarray(_rand(rng, n, 3))
    x, info = pbsv_mesh(jnp.asarray(spd), jnp.asarray(b), kd, mesh, nb=16)
    assert int(info) == 0
    assert np.abs(spd @ np.asarray(x) - b).max() / np.abs(b).max() < 1e-9


def test_chase_apply_dist_matches_replicated(rng):
    # streamed sharded stage-2 back-transform == the single-program apply
    from slate_tpu.linalg.eig import _chase_sweep_apply, hb2st
    from slate_tpu.parallel.dist_twostage import chase_apply_dist

    n, w = 96, 8
    g = _rand(rng, n, n)
    band = np.tril(np.triu(g + g.T, -w), w)
    d, e, f2, _ = hb2st(jnp.asarray(band), w)
    z = jnp.asarray(_rand(rng, n, n))
    ref = np.asarray(_chase_sweep_apply(f2.vs, f2.taus, z, n, w, False))
    got = np.asarray(chase_apply_dist(f2.vs, f2.taus, z, n, w, mesh24()))
    assert np.abs(got - ref).max() < 1e-12


def test_chase_apply_dist_memory():
    # VERDICT r3 item 4 gate: peak per-device memory of the distributed
    # stage-2 back-transform is O(n^2/p), not the O(n^2) of replication.
    # memory_analysis reports PER-DEVICE sizes for the partitioned program.
    from slate_tpu.parallel.dist_twostage import _chase_apply_dist_jit

    mesh = mesh24()
    n, w = 512, 8
    nparts = 8
    max_hops = -(-(n - 1) // w)
    nsweeps = n - 2
    blk = -(-nsweeps // nparts)
    vs = jnp.zeros((blk * nparts, max_hops, w), jnp.float64)
    taus = jnp.zeros((blk * nparts, max_hops), jnp.float64)
    z = jnp.zeros((n, n), jnp.float64)
    c = _chase_apply_dist_jit.lower(
        vs, taus, z, mesh, 2, 4, n, w, blk, "auto"
    ).compile()
    ma = c.memory_analysis()
    per_dev = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
    repl = (vs.size + taus.size + 2 * z.size) * 8  # replicated footprint
    # sharded run must stay well under half the replicated footprint
    # (measures: z/8 + vs/8 + one streamed block + slack)
    assert per_dev < 0.45 * repl, (per_dev, repl)


@pytest.mark.parametrize("p,q", [(2, 4), (4, 2)])
def test_stedc_finale_memory(p, q):
    # VERDICT r4 item 6 gate: the stedc -> chase handoff is sharded, so
    # the whole heev_mesh stage-2 chain (merge tree out-spec, finale,
    # chase) keeps per-device peak O(n^2/min(p, q)) — no replicated
    # (n, n) Z at the driver boundary.  Both mesh aspect ratios are
    # gated (the gather buffer is O(n^2/q), the input shard O(n^2/p)).
    # memory_analysis reports PER-DEVICE sizes.
    from slate_tpu.parallel.dist_stedc import _stedc_finale_jit

    mesh = make_mesh(p, q, devices=cpu_devices(8))
    n, N = 960, 1024
    z = jnp.zeros((N, N), jnp.float64)
    inv = jnp.arange(N)
    order = jnp.arange(n)
    c = _stedc_finale_jit.lower(z, inv, order, mesh, p, q, n).compile()
    ma = c.memory_analysis()
    per_dev = ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
    repl = 2 * N * N * 8  # replicated in+out footprint
    # input shard N^2/p + one N*(n/q) gather buffer + small temps: the
    # per-device peak must stay well under the replicated footprint and
    # within the O(n^2/p + 2 n^2/q) design bound
    assert per_dev < 0.5 * repl, (p, q, per_dev, repl)
    bound = (N * N / p + 2.5 * N * N / q + 4 * N * n / (p * q)) * 8
    assert per_dev < bound, (p, q, per_dev, bound)
