"""Cholesky family tests — residual gates mirroring test/test_potrf.cc,
test_posv.cc, test_potri.cc, test_pbsv.cc."""

import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.linalg import (
    pbsv_array,
    posv_array,
    posv_mixed_array,
    posv_mixed_gmres_array,
    potrf_array,
    potri_array,
    potrs_array,
    trtri_array,
    trtrm_array,
)
from slate_tpu.types import Diag, Uplo
from slate_tpu.utils.testing import generate


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("uplo", [Uplo.Lower, Uplo.Upper])
def test_potrf(dtype, uplo):
    n = 50
    a = generate("spd", n, dtype=dtype, seed=1)
    astore = np.tril(a) if uplo == Uplo.Lower else np.triu(a)
    f, info = potrf_array(jnp.asarray(astore), uplo)
    assert int(info) == 0
    fn = np.asarray(f)
    if uplo == Uplo.Lower:
        resid = fn @ fn.conj().T - a
    else:
        resid = fn.conj().T @ fn - a
    assert np.abs(resid).max() / np.abs(a).max() < 1e-13


def test_potrf_large_recursive():
    n = 700  # > _NB: exercises recursion
    a = generate("spd", n, dtype=np.float64, seed=2)
    f, info = potrf_array(jnp.asarray(a), Uplo.Lower)
    fn = np.asarray(f)
    assert int(info) == 0
    assert np.abs(fn @ fn.T - a).max() / np.abs(a).max() < 1e-12


def test_potrf_not_spd():
    a = -np.eye(8)
    f, info = potrf_array(jnp.asarray(a), Uplo.Lower)
    assert int(info) == 1  # first pivot fails


def test_posv():
    n, nrhs = 80, 5
    a = generate("spd", n, dtype=np.float64, seed=3)
    b = generate("rands", n, nrhs, np.float64, seed=4)
    x, f, info = posv_array(jnp.asarray(a), jnp.asarray(b), Uplo.Lower)
    assert int(info) == 0
    resid = a @ np.asarray(x) - b
    assert np.abs(resid).max() / (np.abs(a).sum() * np.abs(x).max()) < 1e-14


def test_potrs_upper():
    n = 30
    a = generate("spd", n, dtype=np.complex128, seed=5)
    b = generate("rands", n, 3, np.complex128, seed=6)
    f, info = potrf_array(jnp.asarray(np.triu(a)), Uplo.Upper)
    x = potrs_array(f, jnp.asarray(b), Uplo.Upper)
    np.testing.assert_allclose(a @ np.asarray(x), b, atol=1e-10)


def test_potri():
    n = 40
    a = generate("spd", n, dtype=np.float64, seed=7)
    f, _ = potrf_array(jnp.asarray(a), Uplo.Lower)
    inv = np.asarray(potri_array(f, Uplo.Lower))
    inv_full = np.tril(inv) + np.tril(inv, -1).T
    np.testing.assert_allclose(inv_full @ a, np.eye(n), atol=1e-10)


def test_trtri():
    n = 60
    rng = np.random.default_rng(8)
    l = np.tril(rng.standard_normal((n, n))) + 3 * np.eye(n)
    inv = np.asarray(trtri_array(jnp.asarray(l), Uplo.Lower))
    np.testing.assert_allclose(inv @ l, np.eye(n), atol=1e-12)
    u = np.triu(rng.standard_normal((n, n))) + 3 * np.eye(n)
    invu = np.asarray(trtri_array(jnp.asarray(u), Uplo.Upper))
    np.testing.assert_allclose(invu @ u, np.eye(n), atol=1e-12)


def test_trtrm():
    n = 25
    rng = np.random.default_rng(9)
    l = np.tril(rng.standard_normal((n, n)))
    out = np.asarray(trtrm_array(jnp.asarray(l), Uplo.Lower))
    expect = np.tril(l.T @ l)
    np.testing.assert_allclose(out, expect, atol=1e-12)


def test_pbsv():
    n, kd = 60, 4
    rng = np.random.default_rng(10)
    a = rng.standard_normal((n, n))
    band = np.zeros((n, n))
    for d in range(-kd, kd + 1):
        band += np.diag(np.diag(a, d), d)
    spd = band @ band.T + n * np.eye(n)
    spd_band = np.zeros((n, n))
    for d in range(-kd, kd + 1):  # spd = band@band.T has bandwidth 2kd; rebuild kd-band SPD
        pass
    # construct a kd-banded SPD directly: diagonally dominant band
    ab = np.zeros((n, n))
    for d in range(-kd, kd + 1):
        ab += np.diag(rng.standard_normal(n - abs(d)), d)
    ab = (ab + ab.T) / 2 + (2 * kd + 2) * np.eye(n)
    b = rng.standard_normal((n, 2))
    x, f, info = pbsv_array(jnp.asarray(np.tril(ab)), jnp.asarray(b), kd, Uplo.Lower)
    assert int(info) == 0
    np.testing.assert_allclose(ab @ np.asarray(x), b, atol=1e-10)
    # factor stays banded
    fn = np.asarray(f)
    assert np.abs(np.tril(fn, -kd - 1)).max() == 0


def test_posv_mixed():
    n = 100
    a = generate("spd", n, dtype=np.float64, seed=11)
    b = generate("rands", n, 1, np.float64, seed=12)
    x, iters, done, info = posv_mixed_array(jnp.asarray(a), jnp.asarray(b), Uplo.Lower)
    assert bool(done)
    resid = np.abs(a @ np.asarray(x) - b).max()
    assert resid / np.abs(b).max() < 1e-12  # refined to f64 accuracy


def test_posv_mixed_gmres():
    n = 60
    a = generate("spd", n, dtype=np.float64, seed=13)
    b = generate("rands", n, 1, np.float64, seed=14)[:, 0]
    x, rnorm = posv_mixed_gmres_array(jnp.asarray(a), jnp.asarray(b), Uplo.Lower)
    resid = np.abs(a @ np.asarray(x) - b).max()
    assert resid / np.abs(b).max() < 1e-10


def test_potrf_scan_matches_recursive():
    # single-program scanned Cholesky (north-star sizes code path)
    from slate_tpu.linalg.chol import _potrf_scan

    rng = np.random.default_rng(41)
    for n in (100, 300):
        g = rng.standard_normal((n, n))
        a = g @ g.T + n * np.eye(n)
        l = np.tril(np.asarray(_potrf_scan(jnp.asarray(a), nb=64)))
        ref = np.linalg.cholesky(a)
        assert np.abs(l - ref).max() / np.abs(ref).max() < 1e-13


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_potrf_left_looking(dtype):
    # the f64 left-looking path (potrf_array dispatches here at n >= 4096;
    # exercised directly at small n with a small panel width)
    from slate_tpu.linalg.chol import _potrf_left_looking

    rng = np.random.default_rng(3)
    for n, nb in [(300, 64), (256, 128)]:
        g = rng.standard_normal((n, n))
        if np.issubdtype(dtype, np.complexfloating):
            g = g + 1j * rng.standard_normal((n, n))
        a = (g @ g.conj().T + n * np.eye(n)).astype(dtype)
        l = np.tril(np.asarray(_potrf_left_looking(jnp.asarray(a), nb)))
        resid = np.linalg.norm(l @ l.conj().T - a) / np.linalg.norm(a)
        assert resid < 1e-13, (n, nb, resid)


def test_potrf_left_looking_staged():
    # the staged per-panel-program variant (the n > 20480 f64 chip path:
    # one donated XLA program per panel caps peak HBM at ~one matrix)
    # must match the fused left-looking form exactly in math
    from slate_tpu.linalg.chol import potrf_left_looking_staged

    rng = np.random.default_rng(5)
    n, nb = 300, 64
    g = rng.standard_normal((n, n))
    a = g @ g.T + n * np.eye(n)
    l = np.tril(np.asarray(potrf_left_looking_staged(jnp.asarray(a), nb)))
    resid = np.linalg.norm(l @ l.T - a) / np.linalg.norm(a)
    assert resid < 1e-13, resid


@pytest.mark.parametrize("cond", [1e6, 1e12])
def test_potrf_scan_ill_conditioned(cond):
    # ADVICE r3: the explicit-inverse panel solve trades the trsm's
    # unconditional backward stability for O(eps * cond(L_kk)) — bound the
    # regression on a deliberately ill-conditioned fixture.  Geometric
    # spectrum: cond(A) = cond, cond(L_kk) <= sqrt(cond), so the residual
    # gate is c * n * eps * sqrt(cond) (c small); the well-conditioned
    # tests above keep the 3-eps-class gate.
    from slate_tpu.linalg.chol import _potrf_scan

    rng = np.random.default_rng(7)
    n = 256
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    d = cond ** (-np.arange(n) / (n - 1))  # 1 .. 1/cond
    a = (q * d) @ q.T
    a = (a + a.T) / 2
    l = np.tril(np.asarray(_potrf_scan(jnp.asarray(a), nb=64)))
    resid = np.linalg.norm(l @ l.T - a) / np.linalg.norm(a)
    eps = np.finfo(np.float64).eps
    assert resid < 8 * n * eps * np.sqrt(cond), (resid, cond)
    assert np.isfinite(l).all()


@pytest.mark.parametrize("cond", [None, 1e8])
def test_potrf_ll_ozaki_cached(cond):
    # The digit-cache left-looking f64 path (potrf_array dispatches here on
    # TPU at 4096 <= n <= 20480): panels split once into int8 planes on a
    # fixed sqrt(diag)-bounded row grid, each update one plane-level GEMM.
    # Gate: n*eps-class residual on well- AND ill-conditioned fixtures
    # (the bound slack costs <= log2 sqrt(n) top bits; S=10 absorbs it).
    from slate_tpu.linalg.chol import _potrf_ll_ozaki

    rng = np.random.default_rng(11)
    n, nb = 384, 128
    g = rng.standard_normal((n, n))
    if cond is None:
        a = (g + g.T) / (2 * np.sqrt(n)) + 3 * np.eye(n)
    else:
        q, _ = np.linalg.qr(g)
        a = (q * cond ** (-np.arange(n) / (n - 1))) @ q.T
        a = (a + a.T) / 2
    l = np.tril(np.asarray(_potrf_ll_ozaki(jnp.asarray(a), nb=nb)))
    resid = np.linalg.norm(l @ l.T - a) / np.linalg.norm(a)
    eps = np.finfo(np.float64).eps
    gate = 8 * n * eps * (1 if cond is None else np.sqrt(cond))
    assert resid < gate, (resid, gate)


def test_potrf_scan_carry_updated_in_place():
    # Each k-step of the scanned Cholesky must update its fori_loop's
    # trailing view in place: no copy of a whole view inside a loop body.
    # This guards XLA's copy insertion only (the CPU compile shows the
    # copy a step order that blocks aliasing brings back); the layout
    # copies a TPU adds around a column-major carry are guarded by
    # tests/test_chip_compile.py and show in the chip's device trace.
    import jax
    from conftest import loop_view_copies
    from slate_tpu.linalg.chol import _potrf_scan

    n, nb = 512, 64
    spec = jax.ShapeDtypeStruct((n, n), jnp.float32)
    hlo = jax.jit(lambda a: _potrf_scan(a, nb=nb, nbuckets=4)).lower(spec).compile().as_text()
    copies = loop_view_copies(hlo, min_dim=2 * nb)
    # one loop per bucket, views n, 3n/4, n/2, n/4
    assert sorted(copies) == [128, 256, 384, 512], copies
    assert not any(copies.values()), copies

def _potrf_scan_dus_first(a, nb, nbuckets):
    """The scanned Cholesky's earlier k-step order, kept as the reference:
    write the finished panel column into the carry first, then subtract
    ``l21 l21^T`` over the whole view."""
    import jax

    n = a.shape[0]
    nsteps = -(-n // nb)
    np_ = nsteps * nb
    ap = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
    dpad = jnp.arange(n, np_)
    ap = ap.at[dpad, dpad].set(1)
    bounds = [nsteps * g // nbuckets for g in range(nbuckets)] + [nsteps]
    for g in range(nbuckets):
        k0, k1 = bounds[g], bounds[g + 1]
        if k0 == k1:
            continue
        off = k0 * nb
        view = ap[off:, off:]
        nv = np_ - off
        rows = jnp.arange(nv)

        def step(k, view, off=off, nv=nv, rows=rows):
            kk = k * nb - off
            dblk = jax.lax.dynamic_slice(view, (kk, kk), (nb, nb))
            col = jax.lax.dynamic_slice(view, (0, kk), (nv, nb))
            ld = jax.lax.linalg.cholesky(dblk)
            linv = jax.lax.linalg.triangular_solve(
                ld[None], jnp.eye(nb, dtype=view.dtype)[None], left_side=True,
                lower=True, transpose_a=False,
            )[0]
            sol = jnp.matmul(col, linv.T, precision="highest").astype(view.dtype)
            below = (rows >= kk + nb)[:, None]
            ondiag = ((rows >= kk) & (rows < kk + nb))[:, None]
            dpat = jax.lax.dynamic_update_slice(
                jnp.zeros((nv, nb), view.dtype), jnp.tril(ld), (kk, 0)
            )
            newcol = jnp.where(below, sol, jnp.where(ondiag, dpat, col))
            view = jax.lax.dynamic_update_slice(view, newcol, (0, kk))
            l21 = newcol * below.astype(view.dtype)
            upd = jnp.matmul(l21, l21.T, precision="highest")
            return view - upd.astype(view.dtype)

        view = jax.lax.fori_loop(k0, k1, step, view)
        ap = ap.at[off:, off:].set(view)
    return ap[:n, :n]


def _scan_fixture(kind, n, dtype, seed):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((n, n))
    if kind == "well":
        a = (g + g.T) / (2 * np.sqrt(n)) + 3 * np.eye(n)
    else:  # test_potrf_scan_ill_conditioned's geometric spectrum
        q, _ = np.linalg.qr(g)
        a = (q * 1e6 ** (-np.arange(n) / (n - 1))) @ q.T
        a = (a + a.T) / 2
    return a.astype(dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("kind", ["well", "ill"])
def test_potrf_scan_step_order_bitwise(kind, dtype):
    # the in-place step order (column out, whole-view update, column
    # written last) gives bitwise the L of the column-first order
    import jax
    from slate_tpu.linalg.chol import _potrf_scan

    a = jnp.asarray(_scan_fixture(kind, 300, dtype, seed=43))
    assert a.dtype == dtype
    got = jax.jit(lambda x: _potrf_scan(x, nb=64, nbuckets=4))(a)
    ref = jax.jit(lambda x: _potrf_scan_dus_first(x, nb=64, nbuckets=4))(a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_potrf_scan_non_spd_info():
    # An indefinite input whose first bad pivot (row 512) lies inside the
    # third of four buckets (rows 384..575 at nb 64): the reordered
    # update must neither move nor hide the NaN poisoning, so potrf's
    # info reads the same pivot off the scan's L as off the recursion's.
    from slate_tpu.linalg.chol import _pivot_info, _potrf_lower, _potrf_scan

    n, p = 768, 512  # p also starts a 256-row leaf of the recursion
    a = _scan_fixture("well", n, np.float32, seed=44)
    a[p, p] = -1.0
    a = jnp.asarray(a)
    info_scan = int(_pivot_info(_potrf_scan(a, nb=64, nbuckets=4)))
    info_lower = int(_pivot_info(_potrf_lower(a)))
    assert info_scan == info_lower == p + 1


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_potrf_and_inv_leaf(dtype):
    """The joint (L, L^-1) of one leaf block (the scanned factor's panel
    pair): L L^T = A and L L^-1 = I to the dtype's backward-error class
    against the float64 reference."""
    from slate_tpu.linalg.chol import _potrf_and_inv

    nb = 8
    a = generate("spd", nb, dtype=np.float64, seed=3).astype(dtype)
    l, linv = (np.asarray(x, np.float64) for x in _potrf_and_inv(jnp.asarray(a)))
    an = np.asarray(a, np.float64)
    tol = 100 * nb * float(np.finfo(dtype).eps)
    assert np.abs(np.triu(l, 1)).max() == 0 and np.abs(np.triu(linv, 1)).max() == 0
    assert np.abs(l @ l.T - an).max() < tol * nb * np.abs(an).max()
    scale = nb * np.abs(l).max() * np.abs(linv).max()
    assert np.abs(l @ linv - np.eye(nb)).max() < tol * scale
