"""f64 matmul on the int8 MXU: Ozaki-scheme split-integer GEMM.

TPU has no native f64 MXU path — XLA emulates f64 as float32 pairs at ~1.3
TF/s on v5e, while the same chip does ~280 TOPS of s8 x s8 -> s32 matmul.
The Ozaki scheme (an error-free transformation of a high-precision GEMM
into a sum of low-precision GEMMs) recovers f64-accurate products from the
integer unit:

  1. Split each f64 element exactly into two f32 components x = hi + lo
     (hi = f32(x); lo = f32(x - hi); both conversions are exact, even under
     TPU's f32-pair f64 emulation, because hi IS the pair's high word).
  2. Row-scale A (col-scale B) by a power of two 2^e so |x'| < 1 per row.
  3. Slice hi' and lo' into signed 6-bit digits on the shared row grid
     (weights 2^(-6(t+1))) using native f32 arithmetic — every step is
     exact because each f32 component has 24 mantissa bits and digit
     removal only shortens them.  Summing the hi and lo digit planes gives
     digits of x' in [-64, 64]: int8 with headroom.
  4. Every digit-plane product qa_t @ qb_u is EXACT in int32 (|q| <= 64,
     so a k-term dot is < k * 2^12 — k is chunked to stay below 2^31).
  5. C = 2^(ea+eb) * sum_{t+u<S} (qa_t @ qb_u) 2^(-6(t+u+2)); terms with
     t+u >= S fall below f64 round-off for S = 9 (54 bits).

The t+u=s diagonals are evaluated as ONE integer matmul each over a
concatenated contraction axis ([qa_0..qa_s] against [qb_s..qb_0]), so the
whole product costs S(S+1)/2 unit-GEMM flops — 45 for S=9, i.e. ~6 TF/s of
f64-equivalent throughput at the v5e int8 peak vs 1.3 TF/s emulated.

Accuracy: the dropped t+u >= S tail is bounded relative to the row and
column scales, not to the entries, so operands whose rows span many
binades need more slices than N(0,1) data.  A caller that names no count
gets the smallest S in [8, 12] that the operands' own exponents and norms
show to be enough (``slice_count``, derived there); callers that pass
``n_slices`` keep their count.  Elements with |x| outside the f32 exponent
range (|x| > ~1e38 or rows whose max is < ~1e-38) are not supported (the
hi/lo split degenerates); scale your data, as you would for any
f32-adjacent pipeline.

Scopes: the dispatch product runs under the stage ``ozaki`` with the
phases ``split`` (hi/lo, digit planes, the slice rule), ``products`` (the
int8 contractions) and ``accumulate`` (the pair cascade and the f64
epilogue), op metadata only.

References (design provenance, no code taken): the reference SLATE has no
f64-emulation tier — its f64 path is cuBLAS DGEMM dispatched from
src/internal/internal_gemm.cc.  This module is the TPU-native answer to
the same capability, following the published Ozaki-scheme-on-integer-units
construction (Ootomo et al. 2024 style), implemented from the definitions
above.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_W = 5          # magnitude bits per digit: |digit component| <= 2^_W = 32
_D = _W + 1     # grid step in bits; hi+lo digit sums are <= 2^_D = 64
# Largest contraction chunk whose int32 accumulator cannot overflow:
# (s+1) * k * 2^(2*_D) < 2^31 with s+1 <= 16  =>  k < 2^(31-12-4) = 2^15.
_K_CHUNK = 8192
_DEFAULT_SLICES = 9  # 6*9 = 54 bits > f64's 53-bit significand
# The count ``slice_count`` chooses lies in [_MIN_SLICES, _MAX_SLICES]; the
# split makes the top count's planes once and the products run as many as
# the operands need.
_MIN_SLICES, _MAX_SLICES = 8, 12
# The gemm tester's limit is 3 eps (sqrt(k) + 2) ||A|| ||B||; the rule's
# bound on the dropped digits may take this share of it.
_TESTER_EPS = 3 * 2.0**-52
_RULE_SHARE = 0.5


def _exp2i(e: Array) -> Array:
    """Exact f32 2^e for integer-valued f32 ``e`` in [-126, 127].

    Assembles the IEEE-754 bit pattern directly — runtime exp2 is a libm
    approximation and must not be trusted to hit powers of two exactly.
    """
    bits = (e.astype(jnp.int32) + 127) << 23
    return lax.bitcast_convert_type(bits, jnp.float32)


def _row_exp(absmax32: Array) -> Array:
    """Exponent e (f32) with absmax < 2^e, from native-f32 bit twiddling.

    frexp does not lower on TPU (s64 bitcast in the x64 rewriter), and
    ceil(log2(x)) can under-round near powers of two; reading the IEEE
    exponent field of the f32 row max is exact and native everywhere.
    """
    bits = lax.bitcast_convert_type(absmax32, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 126  # unbiased exponent + 1: 2^e > absmax
    e = jnp.where(absmax32 > 0, e, 0)
    # keep both 2^e and 2^-e in the normal f32 range
    return jnp.clip(e, -125, 126).astype(jnp.float32)


def _slice_digits(hi: Array, lo: Array, e: Array, n_slices: int) -> Array:
    """Digit planes (n_slices, *x.shape) int8 of (hi+lo) * 2^-e.

    Slices the two f32 components on the shared per-row grid with exact
    f32 arithmetic, then sums the planes (|q_hi|,|q_lo| <= 32 so the sum
    fits int8 with 2x headroom).
    """
    scale = _exp2i(-e)  # exact f32 power of two

    def planes(comp):
        r = comp * scale
        digs = []
        for t in range(n_slices):
            # shift as an exact Python-float literal: runtime exp2 is a
            # libm approximation and its off-by-one-ulp results cascade
            # through the residual recurrence
            shift = jnp.float32(2.0 ** (_D * (t + 1)))
            # the nearest integer, halves up, from the exact fraction
            # y - floor(y): floor(y + 0.5) rounds y + 0.5 in f32 first,
            # which takes y = 1/2 - 2^-25 to 1, and the residual below then
            # needs a bit more than f32 holds
            y = r * shift
            f = jnp.floor(y)
            q = f + (y - f >= 0.5).astype(jnp.float32)
            # |y - q| <= 1/2, so the residual is exact; the first digit
            # reaches +-64 (|r| < 1), later ones +-32
            r = r - q / shift
            digs.append(q.astype(jnp.int8))
        return jnp.stack(digs)

    return planes(hi) + planes(lo)


def _split_f32(x: Array) -> tuple[Array, Array]:
    """Exact two-f32 decomposition of f64 ``x`` (hi = f32(x), lo = rest)."""
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(x.dtype)).astype(jnp.float32)
    return hi, lo


def split_rows(x: Array, n_slices: int = _DEFAULT_SLICES, e: Array | None = None):
    """Digit planes + row exponents of an (m, k) f64 operand.

    Returns ``(q, e)`` with q (n_slices, m, k) int8 and e (m, 1) f32.  When
    ``e`` is given it must satisfy |x[i, :]| < 2^e[i] (a per-row BOUND, not
    necessarily the row max) — callers with an a-priori row bound (e.g.
    Cholesky's |L[i, j]| <= sqrt(A_ii)) can fix the digit grid once and
    cache/concatenate planes of different column blocks exactly, because
    every block shares the same per-row scaling (see
    linalg/chol._potrf_ll_ozaki).  A bound looser than the row max costs
    top digit planes (log2(bound/rowmax) bits); add a slice to compensate.
    """
    q, e, _ = _split_rows_hi(x, n_slices, e)
    return q, e


def _split_rows_hi(x: Array, n_slices: int, e: Array | None = None):
    """``split_rows`` that also returns the f32 high part of ``x``."""
    hi, lo = _split_f32(x)
    if e is None:
        e = _row_exp(jnp.max(jnp.abs(hi), axis=1, keepdims=True))
    return _slice_digits(hi, lo, e, n_slices), e, hi


# Open ``slice_record`` lists, innermost last.
_SLICE_RECORDS: list[list] = []


@contextlib.contextmanager
def slice_record():
    """Yield a list that receives the slice count of every product whose
    count ``slice_count`` chose while the block is open (int32 scalars of
    the caller's trace: tracers under ``jit``).  A jitted caller returns
    them beside its result, so a count is read from the program that ran
    it and never computed twice."""
    rec: list = []
    _SLICE_RECORDS.append(rec)
    try:
        yield rec
    finally:
        _SLICE_RECORDS.pop()


def matmul_f64(a: Array, b: Array, n_slices: int | None = None) -> Array:
    """f64-accurate ``a @ b`` computed as Ozaki-split int8 GEMMs.

    a: (m, k) f64, b: (k, n) f64.  With ``n_slices`` None the operands
    choose the count (``slice_count``) and an open ``slice_record`` gets
    it; ``n_slices=9`` is the fixed full-accuracy count for N(0,1)-like
    data, ``n_slices=6`` a ~1.7x faster variant at ~f32-pair (2^-36)
    accuracy.
    """
    if a.dtype != jnp.float64 or b.dtype != jnp.float64:
        raise TypeError(f"matmul_f64 requires f64 operands, got {a.dtype}, {b.dtype}")
    c, s = _matmul_f64(a, b, n_slices=n_slices)
    if n_slices is None and _SLICE_RECORDS:
        _SLICE_RECORDS[-1].append(s)
    return c


@functools.partial(jax.jit, static_argnames=("n_slices",))
def _matmul_f64(a: Array, b: Array, n_slices: int | None) -> tuple[Array, Array]:
    """(a @ b, the slice count the products ran)."""
    from ..parallel.comm import phase_scope

    with jax.named_scope("ozaki"):
        with phase_scope("split"):
            top = _MAX_SLICES if n_slices is None else n_slices
            qa, ea, hi_a = _split_rows_hi(a, top)
            qb, eb, hi_bt = _split_rows_hi(b.T, top)
            s = (jnp.int32(n_slices) if n_slices is not None
                 else slice_count(hi_a, ea, hi_bt, eb))
        c = matmul_planes(qa, ea, qb, eb, None if n_slices is not None else s)
    return c, s


def _slice_bound_eps(n_slices: int) -> float:
    """eps_S of ``slice_count``: the dropped digits of one term of the
    k-sum, relative to its row and column scales."""
    return (n_slices + 2) * 2.0 ** (-_D * n_slices)


def slice_count(hi_a: Array, ea: Array, hi_bt: Array, eb: Array) -> Array:
    """The smallest slice count in [_MIN_SLICES, _MAX_SLICES] whose bound
    on the dropped digits meets ``_RULE_SHARE`` of the gemm tester's limit
    3 u (sqrt(k) + 2) ||A||_inf ||B||_inf (u = 2^-52), or the top count
    where none does; an int32 scalar computed on the device from the
    split's row exponents ``ea`` (m, 1) of A and ``eb`` (n, 1) of B^T and
    the f32 high parts ``hi_a`` (m, k) and ``hi_bt`` (n, k).

    The bound.  Row i of A is scaled by 2^-ea_i and column j of B by
    2^-eb_j, so every scaled entry lies below 1.  A scaled entry is the
    digit sum  sum_t d_t 2^(-6(t+1)) + r  with |d_t| <= 64, so each digit
    term is at most 2^(-6t), and the split's remainder after S digits
    (half a last-digit unit for each of hi and lo) is at most 2^(-6S).
    The product keeps the digit pairs t + u < S.  Of one term a_il b_lj of
    the k-sum it drops, relative to 2^(ea_i + eb_j):

      - the pairs t + u = s, S <= s <= 2S - 2: at most 2S - 1 - s of them,
        each at most 2^(-6s); together at most (S - 1) 2^(-6S) / (1 - 2^-6);
      - the two remainders times the other operand: at most
        2^(-6S) + 2^(-6S) (1 + 2^(-6S));

    together at most  eps_S = (S + 2) 2^(-6S).  Added without cancellation
    over the k terms that is  k eps_S 2^(ea_i + eb_j).  The digits are
    rounded to nearest, so what is dropped has mean zero, and the
    probabilistic model of rounding error (Higham and Mary, SIAM J. Sci.
    Comput. 41, 2019) takes  sqrt(k) eps_S 2^(ea_i + eb_j)  instead: the
    same sqrt(k) growth the tester's normalisation assumes.  Summed over j
    and maximised over i,

      ||C - C_S||_inf <= sqrt(k) eps_S max_i 2^ea_i sum_j 2^eb_j.

    Each dropped term is at most its bound and in practice far below it
    (a digit spreads over its range rather than sitting at 64, and most
    entries lie binades below their row's maximum): on seeded operands
    (u - 1/2) exp(phi g), n 512, phi 0 to 3, the bound sits 20-100x above
    the truncation error measured against numpy, so the share 1/2 keeps
    the product's error at least ten times under the tester's limit
    (tests/test_ozaki.py checks it).  The rule gives S = 8 for phi 0 and
    11 for phi 2, where the row maxima stand about 2^10 above the typical
    entry.

    The norms are summed in f32 after scaling by the largest exponent, so
    nothing overflows (every scaled entry is below 1); an all-zero operand
    gives the bottom count."""
    k = hi_a.shape[1]
    ea_max, eb_max = jnp.max(ea), jnp.max(eb)
    sa, sb = _exp2i(-ea_max), _exp2i(-eb_max)
    norm_a = jnp.max(jnp.sum(jnp.abs(hi_a) * sa, axis=1))   # ||A||_inf 2^-ea_max
    norm_b = jnp.max(jnp.sum(jnp.abs(hi_bt) * sb, axis=0))  # ||B||_inf 2^-eb_max
    col_scales = jnp.sum(_exp2i(jnp.maximum(eb - eb_max, -126)))  # sum_j 2^eb_j 2^-eb_max
    root = float(k) ** 0.5
    limit = _RULE_SHARE * _TESTER_EPS * (root + 2.0) / root
    denom = norm_a * norm_b
    ratio = jnp.where(denom > 0, col_scales / jnp.where(denom > 0, denom, 1.0), 0.0) / limit
    misses = [ratio * jnp.float32(_slice_bound_eps(c)) > 1.0
              for c in range(_MIN_SLICES, _MAX_SLICES)]
    return jnp.int32(_MIN_SLICES) + sum(m.astype(jnp.int32) for m in misses)


def matmul_planes(qa: Array, ea: Array, qb: Array, eb: Array,
                  n_slices: Array | None = None) -> Array:
    """f64 product A @ B^T from pre-split digit planes (split_rows of A
    (m, k) and of B^T (n, k)).  This is the reuse entry point: operands
    whose planes are cached (factorization panels, stationary matrices)
    skip the O(S m k) digit split and the f64 hi/lo subtract on every
    reuse — the panel-update schedule in linalg/chol rides this.

    ``n_slices`` None runs every diagonal the planes have.  A traced count
    (``slice_count``'s, at least ``_MIN_SLICES``) runs the diagonals below
    it: those from ``_MIN_SLICES`` up each sit under a ``lax.cond``, so
    one program serves every count and runs S(S+1)/2 unit products."""
    from ..parallel.comm import phase_scope

    top, m, k = qa.shape
    assert qb.shape[0] == top and qb.shape[2] == k, (qa.shape, qb.shape)
    n = qb.shape[1]

    nchunks = -(-k // _K_CHUNK)

    def diag_term(s):
        # one integer GEMM for the t+u == s anti-diagonal:
        # [qa_0 .. qa_s] against [qb_s .. qb_0] over a joint (slice, k)
        # contraction axis, chunked in k to bound the int32 accumulator
        at, bt = qa[: s + 1], qb[s::-1]
        acc = jnp.zeros((m, n), jnp.int32)
        for c in range(nchunks):
            sl = slice(c * _K_CHUNK, min((c + 1) * _K_CHUNK, k))
            ci = lax.dot_general(
                at[..., sl],
                bt[..., sl],
                (((0, 2), (0, 2)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            acc = acc + ci if nchunks > 1 else ci
        return acc

    # Weighted-term accumulation in native f32 PAIRS (double-single with a
    # TwoSum cascade), not in emulated f64: each int32 term splits exactly
    # into two f32 components (|term| < 2^28.2, so hi carries the top 24
    # bits and the residual fits f32 exactly), the power-of-two weights are
    # exact f32 scalings, and only the final pair->f64 conversion and the
    # row/column scales touch emulated-f64 arithmetic (3 ops/element vs
    # ~2 n_slices before; measured +6-8% end-to-end at the 8192-class
    # shapes, residual 9.2e-15 at n=1024 vs the 1.1e-11 gate).
    def add_diag(pair, s):
        with phase_scope("products"):
            t = diag_term(s)
        with phase_scope("accumulate"):
            hi, lo = pair
            # digit t carries weight 2^(-D(t+1)): the s = t+u diagonal
            # carries 2^(-D(s+2))
            w = jnp.float32(2.0 ** (-_D * (s + 2)))
            th = t.astype(jnp.float32)
            tl = (t - th.astype(jnp.int32)).astype(jnp.float32)
            for x in (th * w, tl * w):
                # TwoSum(hi, x) with the error folded into lo
                ssum = hi + x
                bb = ssum - hi
                err = (hi - (ssum - bb)) + (x - bb)
                hi = ssum
                lo = lo + err
        return hi, lo

    pair = (jnp.zeros((m, n), jnp.float32), jnp.zeros((m, n), jnp.float32))
    for s in range(top):
        if n_slices is None or s < _MIN_SLICES:
            pair = add_diag(pair, s)
        else:
            pair = lax.cond(s < n_slices, functools.partial(add_diag, s=s),
                            lambda p: p, pair)
    with phase_scope("accumulate"):
        hi, lo = pair
        # 2^ea, 2^eb each fit f32; apply as two exact f64 multiplies
        sa = _exp2i(ea).astype(jnp.float64)          # (m, 1)
        sb = _exp2i(eb).astype(jnp.float64).T        # (1, n)
        out = hi.astype(jnp.float64) + lo.astype(jnp.float64)
        return out * sa * sb


# ---------------------------------------------------------------------------
# Block-cyclic tile-stack forms (ISSUE 8): the split/accumulate pieces the
# distributed residual SUMMA (parallel/summa.gemm_summa_ozaki) composes
# inside its shard_map kernel.  Everything here is pure elementwise/local
# math — the mesh reductions (global row maxima) and the panel broadcasts
# stay in parallel/, riding the exact gemm_summa schedule.  The splits and
# the per-diagonal integer contractions reuse the single-chip construction
# above, and the summation order is fixed by the logical k order regardless
# of mesh shape: results are bitwise-reproducible across (p, q) grids
# (padded tiles/steps contribute exact zeros, and x + 0.0 is the identity).
# ---------------------------------------------------------------------------


def row_exp_from_absmax(absmax32: Array) -> Array:
    """Per-row digit-grid exponents from an f32 row-max array of any shape
    (the ``_row_exp`` bit-twiddle, shape-polymorphic).  Distributed callers
    pmax their local tile-row maxima over the mesh axis that shards the
    contraction first, so every device slices on the same global grid."""
    return _row_exp(absmax32)


def split_tiles(x: Array, e: Array, n_slices: int = _DEFAULT_SLICES) -> Array:
    """Digit planes (n_slices, *x.shape) int8 of an f64 tile stack.

    ``e`` must broadcast against ``x`` and satisfy |x| < 2^e along each
    scaled row (the ``split_rows`` bound contract — here the caller aligns
    e to the tile-stack row axis, e.g. (mtl, 1, nb, 1) for a local
    (mtl, ktl, nb, nb) stack of A or (1, ntl, 1, nb) for B's per-column
    grid).  Exact for the same reasons as ``split_rows``: the hi/lo f32
    decomposition is exact, and digit removal on a power-of-two grid only
    shortens f32 significands."""
    hi, lo = _split_f32(x)
    return _slice_digits(hi, lo, e, n_slices)


def plane_diag_term(qa: Array, qb: Array, s: int) -> Array:
    """One t+u == s anti-diagonal of a batched tile product, as a single
    int32 contraction: qa (S, I, nb, nb) digit planes of an A tile column,
    qb (S, J, nb, nb) planes of a B tile row; returns (I, J, nb, nb) int32
    = sum_{t+u=s} qa_t[i] @ qb_u[j].  EXACT: |q| <= 64 so an (s+1)*nb-term
    dot stays far below 2^31 for nb <= 8192 (the _K_CHUNK bound)."""
    return jnp.einsum(
        "tiab,tjbc->ijac",
        qa[: s + 1],
        qb[s::-1],
        preferred_element_type=jnp.int32,
    )


def accumulate_diag_planes(acc: Array, qa: Array, qb: Array,
                           n_slices: int) -> Array:
    """Fold every t+u == s diagonal of one (A tile column) x (B tile row)
    panel product into the running f64 accumulator — the per-k-step
    consume of the distributed Ozaki SUMMA.  Same weights and diagonal
    order as ``matmul_planes``, but the cross-k-step accumulation is f64,
    NOT the f32 pair: ``matmul_planes`` contracts the FULL k in int32
    before it ever touches the pair (2 n_slices pair-adds of
    geometrically decaying terms), while a SUMMA consume adds same-scale
    partials every k-step — a pair cascade there compounds at the
    double-single unit 2^-48 per step and the refinement loop's residual
    stalls ~5 bits above the f64 gate.  Here the int32 -> f64 conversion
    and the power-of-two weight multiply are both exact, so the ONLY
    rounding is one f64 add per slice per step (2^-53, the same class as
    the plain f64 SUMMA residual) — and adding an exact zero stays the
    bitwise identity, which is what keeps padded tiles/steps free."""
    for s in range(n_slices):
        w = 2.0 ** (-_D * (s + 2))
        t = plane_diag_term(qa, qb, s)
        acc = acc + t.astype(jnp.float64) * w
    return acc


def scale_rows_cols_f64(acc: Array, sa: Array, sb: Array) -> Array:
    """Final epilogue: the exact power-of-two row/column scales
    (sa = 2^ea along rows, sb = 2^eb along columns, broadcastable)."""
    return acc * sa * sb


def exp2_scale_f64(e: Array) -> Array:
    """2^e as exact f64 (f32 power of two widened), for the epilogue."""
    return _exp2i(e).astype(jnp.float64)


def matmul_c128(a: Array, b: Array, n_slices: int | None = None) -> Array:
    """complex128 ``a @ b`` as three real Ozaki products (Karatsuba).

    (ar + i*ai)(br + i*bi) = (m1 - m2) + i*(m3 - m1 - m2) with
    m1 = ar@br, m2 = ai@bi, m3 = (ar+ai)@(br+bi) — 3 real GEMMs instead
    of 4.  The m3 - m1 - m2 cancellation costs at most a couple of ulps
    relative to |a||b|, the same backward-error class as a plain complex
    GEMM (reference complex path: vendor ZGEMM, internal_gemm.cc:634).
    ``n_slices`` as in ``matmul_f64``; with None each real product
    chooses its own count and an open ``slice_record`` gets all three.
    """
    if a.dtype != jnp.complex128 or b.dtype != jnp.complex128:
        raise TypeError(f"matmul_c128 requires c128 operands, got {a.dtype}, {b.dtype}")
    c, counts = _matmul_c128(a, b, n_slices=n_slices)
    if n_slices is None and _SLICE_RECORDS:
        _SLICE_RECORDS[-1].extend(counts)
    return c


@functools.partial(jax.jit, static_argnames=("n_slices",))
def _matmul_c128(a: Array, b: Array, n_slices: int | None):
    ar, ai = jnp.real(a), jnp.imag(a)
    br, bi = jnp.real(b), jnp.imag(b)
    m1, s1 = _matmul_f64(ar, br, n_slices=n_slices)
    m2, s2 = _matmul_f64(ai, bi, n_slices=n_slices)
    m3, s3 = _matmul_f64(ar + ai, br + bi, n_slices=n_slices)
    return jax.lax.complex(m1 - m2, m3 - m1 - m2), (s1, s2, s3)
