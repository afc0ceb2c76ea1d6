"""QR/LQ/least-squares tests — orthogonality + residual gates mirroring
test/test_geqrf.cc, test_gelqf.cc, test_unmqr.cc, test_gels.cc."""

import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.linalg.qr import (
    cholqr_array,
    gelqf_array,
    gelqf_l,
    gels_array,
    gels_cholqr_array,
    gels_qr_array,
    geqrf_array,
    geqrf_q,
    geqrf_r,
    unmlq_array,
    unmqr_array,
)
from slate_tpu.types import Op, Side
from slate_tpu.utils.testing import generate


def _check_qr(a, f, tol=1e-12):
    m, n = a.shape
    q = np.asarray(geqrf_q(f))
    r = np.asarray(geqrf_r(f))
    k = min(m, n)
    assert np.abs(q.conj().T @ q - np.eye(k)).max() < tol * m
    assert np.abs(q @ r - a).max() / max(np.abs(a).max(), 1) < tol * m


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
@pytest.mark.parametrize("shape", [(60, 40), (40, 40), (200, 90)])
def test_geqrf(dtype, shape):
    a = generate("rands", *shape, dtype=dtype, seed=1)
    _check_qr(a, geqrf_array(jnp.asarray(a)))


def test_geqrf_large_recursive():
    a = generate("rands", 300, 150, dtype=np.float64, seed=2)
    _check_qr(a, geqrf_array(jnp.asarray(a)))


def test_unmqr_right_side():
    m, n, k = 50, 30, 20
    a = generate("rands", m, n, np.complex128, seed=3)
    c = generate("rands", k, m, np.complex128, seed=4)
    f = geqrf_array(jnp.asarray(a))
    q = np.asarray(geqrf_q(f, full=True))
    out = np.asarray(unmqr_array(Side.Right, Op.NoTrans, f, jnp.asarray(c)))
    np.testing.assert_allclose(out, c @ q, atol=1e-10)
    outh = np.asarray(unmqr_array(Side.Right, Op.ConjTrans, f, jnp.asarray(c)))
    np.testing.assert_allclose(outh, c @ q.conj().T, atol=1e-10)


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_gelqf(dtype):
    m, n = 40, 70
    a = generate("rands", m, n, dtype, seed=5)
    f = gelqf_array(jnp.asarray(a))
    l = np.asarray(gelqf_l(f))
    # Q rows orthonormal: reconstruct via applying Q^H to [L 0] padded
    eye = jnp.eye(n, dtype=f.lv.dtype)
    q = np.asarray(unmlq_array(Side.Left, Op.NoTrans, f, eye))[:n]
    lq = np.zeros((m, n), dtype=np.asarray(f.lv).dtype)
    lq[:, :m] = l
    np.testing.assert_allclose(lq @ q, a, atol=1e-10)
    np.testing.assert_allclose(q @ q.conj().T, np.eye(n), atol=1e-10)


def test_cholqr():
    a = generate("rands", 120, 30, np.float64, seed=6)
    q, r = cholqr_array(jnp.asarray(a))
    qn, rn = np.asarray(q), np.asarray(r)
    assert np.abs(qn.T @ qn - np.eye(30)).max() < 1e-9
    np.testing.assert_allclose(qn @ rn, a, atol=1e-10)


def test_gels_overdetermined():
    m, n = 100, 40
    a = generate("rands", m, n, np.float64, seed=7)
    b = generate("rands", m, 3, np.float64, seed=8)
    x = np.asarray(gels_qr_array(jnp.asarray(a), jnp.asarray(b)))
    xref = np.linalg.lstsq(a, b, rcond=None)[0]
    np.testing.assert_allclose(x, xref, atol=1e-9)
    x2 = np.asarray(gels_cholqr_array(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_allclose(x2, xref, atol=1e-8)


def test_gels_underdetermined():
    m, n = 30, 80
    a = generate("rands", m, n, np.float64, seed=9)
    b = generate("rands", m, 2, np.float64, seed=10)
    x = np.asarray(gels_array(jnp.asarray(a), jnp.asarray(b)))
    xref = np.linalg.lstsq(a, b, rcond=None)[0]  # minimum-norm solution
    np.testing.assert_allclose(a @ x, b, atol=1e-10)
    np.testing.assert_allclose(x, xref, atol=1e-9)


def test_unmqr_complex_trans_rejected():
    # complex Op.Trans is undefined for compact-WY (LAPACK 'N'/'C' only):
    # must raise, not silently apply Q^H (review-found bug)
    import pytest
    from slate_tpu.types import SlateError
    a = generate("randn", 24, 16, np.complex128, seed=40)
    f = geqrf_array(jnp.asarray(a))
    c = generate("randn", 24, 4, np.complex128, seed=41)
    with pytest.raises(SlateError):
        unmqr_array(Side.Left, Op.Trans, f, jnp.asarray(c))


def test_geqrf_scan():
    # single-program scanned QR (north-star sizes code path)
    from slate_tpu.linalg.qr import geqrf_scan_array, unmqr_scan_array
    from slate_tpu.types import Op

    rng = np.random.default_rng(40)
    for m, n, nb in [(96, 96, 32), (130, 70, 32)]:
        a = rng.standard_normal((m, n))
        f = geqrf_scan_array(jnp.asarray(a), nb=nb)
        r = np.asarray(f.r)
        r_ext = np.zeros((m, n))
        r_ext[: min(m, n)] = r[: min(m, n)]
        qr = np.asarray(unmqr_scan_array(f, jnp.asarray(r_ext), Op.NoTrans))
        assert np.abs(qr - a).max() / np.abs(a).max() < 1e-13
        b = rng.standard_normal((m, 3))
        rt = np.asarray(
            unmqr_scan_array(f, unmqr_scan_array(f, jnp.asarray(b), Op.ConjTrans))
        )
        assert np.abs(rt - b).max() < 1e-12


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_panel_qr_pairs(dtype):
    """The Householder panel + compact-WY T pairs, on one nb = 8 panel
    over a diagonal tile and 8 tiles below it: Q = I - V T V^H is
    orthogonal and rebuilds the panel (``_panel_qr_t``), and likewise for
    the offset-pivot form with one tile of zeroed history above
    (``_panel_qr_offset_t``), against the float64 reference."""
    from slate_tpu.linalg.qr import _panel_qr_offset_t, _panel_qr_t, _v_of

    nb = 8
    m = 9 * nb
    rng = np.random.default_rng(5)
    tol = 100 * nb * float(np.finfo(dtype).eps)

    def check(q, rebuilt, a):
        assert np.abs(q.T @ q - np.eye(q.shape[0])).max() < tol
        assert np.abs(rebuilt - a).max() < tol * q.shape[0] * np.abs(a).max()

    a = rng.standard_normal((m, nb)).astype(dtype)
    vr, _tau, t = _panel_qr_t(jnp.asarray(a))
    v = np.asarray(_v_of(vr), np.float64)
    q = np.eye(m) - v @ np.asarray(t, np.float64) @ v.T
    r = np.vstack([np.triu(np.asarray(vr, np.float64)[:nb]), np.zeros((m - nb, nb))])
    check(q, q @ r, np.asarray(a, np.float64))

    ao = np.vstack([np.zeros((nb, nb)), rng.standard_normal((m, nb))]).astype(dtype)
    r, v, _tau, t = (np.asarray(x, np.float64)
                     for x in _panel_qr_offset_t(jnp.asarray(ao), nb))
    q = np.eye(m + nb) - v @ t @ v.T
    assert np.abs(np.tril(r[nb:], -1)).max() == 0 and np.abs(r[:nb]).max() == 0
    check(q, q @ r, np.asarray(ao, np.float64))
