"""Ozaki split-int8 f64 GEMM (slate_tpu/ops/ozaki.py) — accuracy gates
against numpy f64, including mixed row magnitudes, k-chunking, and the
digit-boundary adversarial case, the operand-ruled slice count and the
fixed-count callers' bits."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.ops.ozaki import matmul_f64


@pytest.mark.parametrize("shape", [(64, 64, 64), (128, 300, 65), (96, 8192, 64)])
@pytest.mark.parametrize("scale", [1.0, 1e8, 1e-12])
def test_matmul_f64_accuracy(shape, scale):
    m, k, n = shape
    rng = np.random.default_rng(0)
    a = rng.standard_normal((m, k)) * scale
    a[::3] *= 1e6  # mixed row magnitudes exercise the per-row exponents
    b = rng.standard_normal((k, n))
    c = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
    ref = a @ b
    rel = np.abs(c - ref).max() / np.abs(ref).max()
    assert rel < 1e-13, rel


def test_matmul_f64_adversarial_boundaries():
    # every element just below a power of two: all digit planes saturate
    a = np.full((64, 8192), 0.9999999999)
    b = np.full((8192, 64), -0.9999999999)
    c = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
    ref = a @ b
    rel = np.abs(c - ref).max() / np.abs(ref).max()
    assert rel < 1e-13, rel


def test_matmul_f64_zero_rows_and_fast_variant():
    rng = np.random.default_rng(1)
    a = rng.standard_normal((32, 50))
    a[5] = 0.0  # zero row: exponent guard
    b = rng.standard_normal((50, 32))
    c = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b)))
    assert np.abs(c - a @ b).max() / np.abs(a @ b).max() < 1e-13
    # reduced-slice variant trades accuracy for speed but stays ~f32-pair
    c6 = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b), n_slices=6))
    assert np.abs(c6 - a @ b).max() / np.abs(a @ b).max() < 1e-8


def test_matmul_f64_rejects_f32():
    with pytest.raises(TypeError):
        matmul_f64(jnp.ones((4, 4), jnp.float32), jnp.ones((4, 4), jnp.float32))


def test_matmul_c128_karatsuba():
    from slate_tpu.ops.ozaki import matmul_c128

    rng = np.random.default_rng(2)
    a = rng.standard_normal((48, 96)) + 1j * rng.standard_normal((48, 96))
    b = rng.standard_normal((96, 32)) + 1j * rng.standard_normal((96, 32))
    c = np.asarray(matmul_c128(jnp.asarray(a), jnp.asarray(b)))
    ref = a @ b
    assert np.abs(c - ref).max() / np.abs(ref).max() < 1e-13


def test_matmul_dispatch_precision_tiers(monkeypatch):
    """matmul() routes f64/c128 through the Ozaki path when the default
    device is a TPU, and through jnp.matmul otherwise; tiers map to XLA
    precisions for f32."""
    import importlib

    mm = importlib.import_module("slate_tpu.ops.matmul")
    from slate_tpu.types import Precision

    rng = np.random.default_rng(3)
    a = rng.standard_normal((32, 40))
    b = rng.standard_normal((40, 24))
    ref = a @ b
    # CPU default (tests pin jax_default_device=cpu): native f64 path
    c = np.asarray(mm.matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.abs(c - ref).max() / np.abs(ref).max() < 1e-14
    # force the "TPU default" branch: the Ozaki kernels are pure XLA and
    # run (slowly) on CPU too, so the dispatch itself is testable hermetically.
    # Lower the measured-win-region gate so test-sized shapes route to Ozaki.
    monkeypatch.setattr(mm, "_tpu_is_default", lambda: True)
    monkeypatch.setattr(mm, "_use_pallas", lambda *_: False)
    monkeypatch.setattr(mm, "_OZAKI_MIN_ELEMS", 256**3)
    monkeypatch.setattr(mm, "_OZAKI_MIN_DIM", 256)
    A = rng.standard_normal((256, 256))
    B = rng.standard_normal((256, 256))
    REF = A @ B
    c = np.asarray(mm.matmul(jnp.asarray(A), jnp.asarray(B)))
    assert np.abs(c - REF).max() / np.abs(REF).max() < 1e-13
    c6 = np.asarray(mm.matmul(jnp.asarray(A), jnp.asarray(B), precision=Precision.Fast))
    assert np.abs(c6 - REF).max() / np.abs(REF).max() < 1e-8
    ce = np.asarray(mm.matmul(jnp.asarray(A), jnp.asarray(B), precision=Precision.Emulated))
    assert np.abs(ce - REF).max() / np.abs(REF).max() < 1e-14
    # below the gate: falls through to jnp.matmul even on "TPU"
    csmall = np.asarray(mm.matmul(jnp.asarray(a), jnp.asarray(b)))
    assert np.abs(csmall - ref).max() / np.abs(ref).max() < 1e-14
    ac = jnp.asarray(A + 1j * A[::-1])
    bc = jnp.asarray(B - 1j * B)
    cc = np.asarray(mm.matmul(ac, bc))
    refc = np.asarray(ac) @ np.asarray(bc)
    assert np.abs(cc - refc).max() / np.abs(refc).max() < 1e-12


# -- the operand-ruled slice count --------------------------------------------

TESTER_EPS = 3 * 2.0**-52  # the gemm tester's limit, in its normalization


def _spread(seed: int, shape, phi: float) -> np.ndarray:
    """(u - 1/2) exp(phi g): phi sets how many binades a row spans."""
    rng = np.random.default_rng(seed)
    return (rng.random(shape) - 0.5) * np.exp(phi * rng.standard_normal(shape))


def _tester_error(a, b, c) -> float:
    """||C - A B||_inf / ((sqrt(k) + 2) ||A||_inf ||B||_inf), as SLATE's gemm tester."""
    ref = a @ b
    den = (np.sqrt(a.shape[1]) + 2) * np.abs(a).sum(1).max() * np.abs(b).sum(1).max()
    return np.abs(c - ref).sum(1).max() / den


@functools.cache
def _ruled():
    """jit of the dispatch's product with its slice count, as an entry reads it."""
    from slate_tpu.ops import ozaki

    def body(a, b):
        with ozaki.slice_record() as counts:
            c = ozaki.matmul_f64(a, b)
        return c, counts[0]

    return jax.jit(body)


@pytest.mark.parametrize("phi", [0.0, 1.0, 2.0, 3.0])
def test_slice_rule_meets_the_tester_with_margin(phi):
    a, b = _spread(10, (512, 512), phi), _spread(11, (512, 512), phi)
    c, s = _ruled()(jnp.asarray(a), jnp.asarray(b))
    s = int(s)
    assert 8 <= s <= 12
    assert _tester_error(a, b, np.asarray(c)) * 10 <= TESTER_EPS, (phi, s)
    if phi == 0.0:
        assert s <= 9, s  # N(0,1)-like data needs no more than the old fixed count
    if phi == 2.0:
        assert s > 9, s  # where nine slices sit ~1.3x under the limit


def test_recorded_count_is_the_count_the_products_ran():
    """Bit for bit the explicit-count product at the recorded S: the int32
    diagonal terms are exact, so only the cascade's order could differ."""
    a, b = _spread(12, (256, 384), 2.0), _spread(13, (384, 128), 2.0)
    c, s = _ruled()(jnp.asarray(a), jnp.asarray(b))
    s = int(s)
    explicit = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b), n_slices=s))
    assert np.array_equal(np.asarray(c), explicit)
    fewer = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b), n_slices=s - 1))
    assert not np.array_equal(np.asarray(c), fewer)


def _row_exp(x32: np.ndarray) -> np.ndarray:
    """ozaki._row_exp in numpy: 2^e > |x| per row, from the f32 exponent."""
    m = np.abs(x32).max(axis=1, keepdims=True)
    e = np.where(m > 0, np.frexp(m)[1], 0)
    return np.clip(e, -125, 126).astype(np.float32)


def _planes(x: np.ndarray, e: np.ndarray, n_slices: int) -> np.ndarray:
    hi = x.astype(np.float32)
    lo = (x - hi.astype(np.float64)).astype(np.float32)
    scale = (2.0 ** -e.astype(np.float64)).astype(np.float32)
    out = np.zeros((n_slices,) + x.shape, np.int64)
    for comp in (hi, lo):
        r = comp * scale
        for t in range(n_slices):
            shift = np.float32(2.0 ** (6 * (t + 1)))
            y = r * shift
            f = np.floor(y)
            q = f + (y - f >= 0.5).astype(np.float32)
            r = r - q / shift
            out[t] += q.astype(np.int64)
    return out


def _ozaki_numpy(a, b, n_slices, ea=None, eb=None):
    """The Ozaki product at a fixed count, written out in numpy: digit
    planes, exact integer diagonals, the f32 pair cascade, the f64
    epilogue."""
    bt = b.T
    ea = _row_exp(a.astype(np.float32)) if ea is None else ea
    eb = _row_exp(bt.astype(np.float32)) if eb is None else eb
    qa, qb = _planes(a, ea, n_slices), _planes(bt, eb, n_slices)
    hi = np.zeros((a.shape[0], b.shape[1]), np.float32)
    lo = np.zeros_like(hi)
    for s in range(n_slices):
        t = sum(qa[u] @ qb[s - u].T for u in range(s + 1))
        th = t.astype(np.float32)
        tl = (t - th.astype(np.int64)).astype(np.float32)
        w = np.float32(2.0 ** (-6 * (s + 2)))
        for x in (th * w, tl * w):
            ssum = hi + x
            bb = ssum - hi
            lo = lo + ((hi - (ssum - bb)) + (x - bb))
            hi = ssum
    out = hi.astype(np.float64) + lo.astype(np.float64)
    return out * 2.0 ** ea.astype(np.float64) * (2.0 ** eb.astype(np.float64)).T


@pytest.mark.parametrize("n_slices", [6, 9])
def test_explicit_counts_keep_their_bits(n_slices):
    """Callers that name a count (the Fast tier's 6, the Cholesky cache's
    9 or 10, the mesh residual's 9) get the fixed-count algorithm, bit for
    bit, through matmul_f64 and through matmul_planes with an a-priori
    row bound."""
    from slate_tpu.ops.ozaki import matmul_planes, split_rows

    a, b = _spread(14, (96, 160), 1.0), _spread(15, (160, 64), 1.0)
    got = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b), n_slices=n_slices))
    assert np.array_equal(got, _ozaki_numpy(a, b, n_slices))
    # a looser row bound than the row max, as chol._potrf_ll_ozaki fixes it
    ea = _row_exp(a.astype(np.float32)) + 2
    qa, _ = split_rows(jnp.asarray(a), n_slices, jnp.asarray(ea))
    qb, eb = split_rows(jnp.asarray(b.T), n_slices)
    got = np.asarray(matmul_planes(qa, jnp.asarray(ea), qb, eb))
    assert np.array_equal(got, _ozaki_numpy(a, b, n_slices, ea=ea))


def test_c128_records_three_counts_and_stays_in_class():
    from slate_tpu.ops import ozaki

    a = _spread(16, (128, 96), 2.0) + 1j * _spread(17, (128, 96), 2.0)
    b = _spread(18, (96, 64), 2.0) - 1j * _spread(19, (96, 64), 2.0)

    def body(x, y):
        with ozaki.slice_record() as counts:
            c = ozaki.matmul_c128(x, y)
        return c, jnp.stack(counts)

    c, counts = jax.jit(body)(jnp.asarray(a), jnp.asarray(b))
    assert counts.shape == (3,) and all(8 <= int(s) <= 12 for s in counts)
    ref = a @ b
    assert np.abs(np.asarray(c) - ref).max() / np.abs(ref).max() < 1e-13


def test_ozaki_scopes_in_op_metadata_and_f32_dispatch_unchanged(monkeypatch):
    """The stage and its three phases name the dispatch product's ops in the
    lowered program; a float32 product through the dispatch traces the same
    jaxpr as XLA's own dot at HIGHEST."""
    import importlib

    from slate_tpu.ops import ozaki

    text = jax.jit(ozaki.matmul_f64).lower(
        jnp.zeros((64, 64), jnp.float64), jnp.zeros((64, 64), jnp.float64)).as_text(debug_info=True)
    for phase in ("split", "products", "accumulate"):
        assert f"ozaki/{phase}/" in text, phase
    mm = importlib.import_module("slate_tpu.ops.matmul")
    monkeypatch.setattr(mm, "_tpu_is_default", lambda: True)
    x = jnp.zeros((256, 256), jnp.float32)
    got = jax.make_jaxpr(mm.matmul)(x, x)
    want = jax.make_jaxpr(lambda p, q: jnp.matmul(p, q, precision=jax.lax.Precision.HIGHEST))(x, x)
    assert str(got) == str(want)


@pytest.mark.parametrize("n_slices", [9, 11])
def test_digit_at_a_rounding_tie_keeps_the_entry_exact(n_slices):
    """An entry whose f32 high part sits just below a power of two of the
    row grid, 4 - 2^-22 in a row bounded by 2^9: its first digit is
    y + 1/2 = 1 - 2^-25 + ..., which f32 rounds to 1, so a digit taken as
    floor(y + 1/2) left a residual with one bit more than f32 holds and
    the product was off by 2^-22 of the entry (seen on a v5e at phi 2)."""
    a = np.zeros((8, 16))
    a[0, 0], a[0, 1] = 3.9999998718434497, 300.0
    a[1:] = _spread(20, (7, 16), 2.0)
    b = np.eye(16)
    c = np.asarray(matmul_f64(jnp.asarray(a), jnp.asarray(b), n_slices=n_slices))
    assert abs(c[0, 0] - a[0, 0]) <= 2.0**-52 * 300.0  # the dropped digits' bound, not 2^-22
