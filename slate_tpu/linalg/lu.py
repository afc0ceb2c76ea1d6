"""LU family: getrf (partial pivot / no-pivot / tournament), getrs, gesv,
getri, band gbtrf/gbtrs/gbsv.

Analogues of reference drivers ``src/{getrf,getrf_nopiv,getrf_tntpiv,getrs,
gesv,getri,gbtrf,gbtrs,gbsv}.cc`` and the panel kernels
``src/internal/internal_getrf.cc`` + ``Tile_getrf.hh:169-417``.

Design inversion (the hardest piece per SURVEY.md §7): the reference panel is
a multithreaded pipeline — per column: thread-local max, cross-thread
reduction, cross-rank MPI exchange, row swap, scale (Tile_getrf.hh) — and row
swaps move single rows between ranks over MPI (internal_swap.cc).  On TPU:

- the *panel* is an unblocked ``lax.fori_loop`` over columns with masked
  argmax pivot search and full-row dynamic swaps — one traced program, no
  latency-bound per-element dispatches;
- the *outer* factorization is recursive (Toledo-style): factor the left
  half, permute, triangular-solve for U12, one big gemm on the trailing
  block, recurse — exact 2n^3/3 flops with O(log n) distinct shapes;
- row swaps become gather/scatter permutations of whole row blocks (XLA
  lowers these to efficient collective permutes when sharded), replacing
  per-row MPI sends;
- tournament pivoting (getrf_tntpiv, CALU) reduces pivot candidates through
  a binary tree of small LUs — the communication-avoiding default for wide
  meshes, mirroring internal_getrf_tntpiv.cc.

Pivots are carried as a row-permutation vector ``perm`` (logical row i of
PA = LU is original row perm[i]) — the functional equivalent of the
reference's Pivots = vector<vector<Pivot>> (types.hh:64).
"""

from __future__ import annotations

from ..obs import instrument

from dataclasses import replace
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..blas3.blas3 import _NB, _split, split_pow2, trsm_array
from ..core.matrix import BaseMatrix, Matrix, band_project, tri_project
from ..ops.matmul import matmul
from ..types import Diag, MethodLU, Op, Option, Options, Precision, Side, Uplo, get_option

ArrayLike = Union[jax.Array, BaseMatrix]

_PANEL_W = 64  # unblocked panel width (reference ib, enums InnerBlocking)


class LUFactors(NamedTuple):
    """Packed LU: unit-lower L below diagonal, U on/above; perm applied to
    rows (PA = LU); info = 1 + first zero pivot index, or 0."""

    lu: jax.Array
    perm: jax.Array
    info: jax.Array


# ---------------------------------------------------------------------------
# Unblocked panel (Tile_getrf.hh analogue)
# ---------------------------------------------------------------------------


def _panel_lu(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Partial-pivot LU of an (m, w) panel, w small. Returns (lu, perm)."""
    m, w = a.shape
    rows = jnp.arange(m)

    def step(j, carry):
        a, perm = carry
        col = jnp.abs(a[:, j])
        col = jnp.where(rows >= j, col, -jnp.inf)
        p = jnp.argmax(col)
        rj, rp = a[j], a[p]
        a = a.at[j].set(rp).at[p].set(rj)
        pj, pp = perm[j], perm[p]
        perm = perm.at[j].set(pp).at[p].set(pj)
        piv = a[j, j]
        denom = jnp.where(piv == 0, jnp.ones_like(piv), piv)
        below = (rows > j).astype(a.dtype)
        lcol = a[:, j] / denom * below
        a = a.at[:, j].set(a[:, j] * (1 - below) + lcol)
        cmask = (jnp.arange(w) > j).astype(a.dtype)
        a = a - jnp.outer(lcol, a[j] * cmask)
        return a, perm

    # wide panels (m < w): only min(m, w) elimination steps exist; looping
    # past m would argmax an all -inf column and corrupt row m-1
    a, perm = jax.lax.fori_loop(0, min(m, w), step, (a, jnp.arange(m)))
    return a, perm


# ---------------------------------------------------------------------------
# Recursive blocked LU (partial pivoting)
# ---------------------------------------------------------------------------


def _getrf_rec(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Recursive LU of (m, n), m >= n. Returns (lu, perm).  Leaf panels
    and the U12 solve sit under the ``panel`` phase scope, the row
    gathers under ``swap``, the Schur update under ``bulk``."""
    from ..parallel.comm import phase_scope

    m, n = a.shape
    if n <= _PANEL_W:
        with phase_scope("panel"):
            return _panel_lu(a)
    h = _split_panel(n)
    lu1, p1 = _getrf_rec(a[:, :h])
    with phase_scope("swap"):
        a2 = a[:, h:][p1]
    l11 = lu1[:h, :h]
    with phase_scope("panel"):
        u12 = trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.Unit, 1.0, l11, a2[:h])
    with phase_scope("bulk"):
        s = a2[h:] - matmul(lu1[h:, :h], u12).astype(a.dtype)
    lu2, p2 = _getrf_rec(s)
    with phase_scope("swap"):
        l21 = lu1[h:, :h][p2]
    top = jnp.concatenate([lu1[:h], u12.reshape(h, n - h)], axis=1)
    bot = jnp.concatenate([l21, lu2], axis=1)
    perm = jnp.concatenate([p1[:h], p1[h:][p2]])
    return jnp.concatenate([top, bot], axis=0), perm


def _split_panel(n: int) -> int:
    return split_pow2(n, _PANEL_W)


def _getrf_rec_inv(a: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Recursive LU of (m, w), m >= w, that ALSO returns inv(unit L11).

    The f64 analogue of _getrf_rec: the U12 triangular solve becomes a
    gemm against the child's unit-L inverse, and the combined inverse is
    assembled block-wise (inv([[L11,0],[L21,L22]]) has i21 = -i22 L21 i11)
    — so every O(m w^2) flop is a matmul riding the f64 dispatch (Ozaki /
    tuned emulation) instead of XLA's crawling emulated trsm (cf.
    chol._potrf_and_inv, same redesign).  Error class: explicit-inverse
    O(eps cond(L11)); partial pivoting keeps |L| <= 1 so unit-L blocks are
    well conditioned in practice (cond growth is the usual pivot-growth
    factor)."""
    m, w = a.shape
    if w <= _PANEL_W:
        lu, perm = _panel_lu(a)
        l11 = jnp.tril(lu[:w], -1) + jnp.eye(w, dtype=a.dtype)
        if a.dtype == jnp.dtype(jnp.float64):
            linv = _unit_linv_f64(l11)
        else:
            linv = jax.lax.linalg.triangular_solve(
                l11[None], jnp.eye(w, dtype=a.dtype)[None],
                left_side=True, lower=True, unit_diagonal=True,
            )[0]
        return lu, perm, linv
    h = _split_panel(w)
    lu1, p1, i1 = _getrf_rec_inv(a[:, :h])
    a2 = a[:, h:][p1]
    u12 = matmul(i1, a2[:h]).astype(a.dtype)
    s = a2[h:] - matmul(lu1[h:, :h], u12).astype(a.dtype)
    lu2, p2, i2 = _getrf_rec_inv(s)
    l21 = lu1[h:, :h][p2]
    i21 = -matmul(i2, matmul(l21[: w - h], i1).astype(a.dtype)).astype(a.dtype)
    top = jnp.concatenate([lu1[:h], u12.reshape(h, w - h)], axis=1)
    bot = jnp.concatenate([l21, lu2], axis=1)
    perm = jnp.concatenate([p1[:h], p1[h:][p2]])
    z = jnp.zeros((h, w - h), a.dtype)
    linv = jnp.block([[i1, z], [i21, i2]])
    return jnp.concatenate([top, bot], axis=0), perm, linv


def _unit_linv_f64(l11: jax.Array) -> jax.Array:
    """inv(unit-lower L) for a small f64 block, f32-seeded + Newton-refined
    (VERDICT r5 item 2, cf. chol._potrf_inv_base_f64): TPU has no native
    f64 triangular_solve — the x64 rewriter unrolls it into serialized
    micro-ops — so the leaf runs the NATIVE f32 solve and two coupled
    Newton sweeps X <- X (2I - L X) in f64 (each a pair of small gemms).
    Seed error ~eps32 * cond(L) squares per sweep; partial pivoting keeps
    |L| <= 1 so cond is modest.  A residual-gated fallback runs the exact
    path when the seed failed or the block is pathological."""
    w = l11.shape[0]
    dt = l11.dtype
    eye = jnp.eye(w, dtype=dt)
    x32 = jax.lax.linalg.triangular_solve(
        l11.astype(jnp.float32)[None], jnp.eye(w, dtype=jnp.float32)[None],
        left_side=True, lower=True, unit_diagonal=True,
    )[0]
    x = jnp.where(jnp.isfinite(x32), x32, 0).astype(dt)
    for _ in range(2):
        x = x @ (2.0 * eye - l11 @ x)
    resid = jnp.linalg.norm(eye - l11 @ x)
    tol = 1e3 * w * jnp.finfo(dt).eps * jnp.linalg.norm(x) * jnp.linalg.norm(l11)
    good = jnp.isfinite(resid) & (resid <= tol)

    def exact():
        return jax.lax.linalg.triangular_solve(
            l11[None], eye[None], left_side=True, lower=True,
            unit_diagonal=True,
        )[0]

    return jax.lax.cond(good, lambda: jnp.tril(x), exact)


def _getrf_left_looking(a: jax.Array, nb: Optional[int] = None) -> Tuple[jax.Array, jax.Array]:
    """Left-looking blocked partial-pivot LU for f64 on TPU (VERDICT r4
    item 1, cf. chol._potrf_left_looking).  Per panel: (1) U rows above
    the panel by blocked forward substitution — gemms against the CACHED
    unit-L diagonal-block inverses from _getrf_rec_inv; (2) one big Schur
    gemm  A[r0:, pj] -= L[r0:, :r0] U[:r0, pj]  whose k = r0 contraction
    is exactly the Ozaki-dispatch win shape; (3) recursive all-gemm panel
    LU with partial pivoting; (4) the panel's row permutation applied to
    the factored history (the permuteRows data motion, src/getrf.cc:161-178,
    as one row gather).  Same 2n^3/3 flops as the right-looking form, but
    the big-k products land where f64 is fast.  Returns (lu, perm)."""
    m, n = a.shape
    if nb is None:
        nb = 4096 if n >= 16384 else 2048
    if n <= nb or m != n:
        return _getrf_rec(a)
    nsteps = -(-n // nb)
    np_ = nsteps * nb
    if np_ != n:
        ap = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
        dpad = jnp.arange(n, np_)
        ap = ap.at[dpad, dpad].set(1)
    else:
        ap = a
    perm = jnp.arange(np_)
    linvs = []  # unit-L diagonal-block inverses, one per factored panel
    for j in range(nsteps):
        r0 = j * nb
        panel = ap[:, r0 : r0 + nb]
        if j:
            # U[:r0, pj]: blocked forward substitution through the factored
            # diagonal blocks (each step one small + one growing gemm)
            urows = []
            for k in range(j):
                k0 = k * nb
                bk = panel[k0 : k0 + nb]
                if k:
                    bk = bk - matmul(ap[k0 : k0 + nb, :k0], jnp.concatenate(urows, axis=0)).astype(ap.dtype)
                urows.append(matmul(linvs[k], bk).astype(ap.dtype))
            u_top = jnp.concatenate(urows, axis=0)  # (r0, nb)
            # Schur complement of the panel below r0: one big-k gemm
            sc = panel[r0:] - matmul(ap[r0:, :r0], u_top).astype(ap.dtype)
            panel = jnp.concatenate([u_top, sc], axis=0)
        lu_p, pv, linv = _getrf_rec_inv(panel[r0:])
        linvs.append(linv)
        # permute the history + trailing columns FIRST (lu_p is already in
        # pivoted row order), then write the factored panel.  Only the
        # trailing rows [r0:] move — gathering just them (instead of a
        # whole-matrix ap[gpv]) keeps the transient at (n - r0) rows,
        # which is what lets the 16384 f64 factorization fit v5e HBM.
        trail = ap[r0:][pv]
        ap = jax.lax.dynamic_update_slice(ap, trail, (r0, 0))
        perm = perm.at[r0:].set(perm[r0:][pv])
        ap = jax.lax.dynamic_update_slice(
            ap, jnp.concatenate([panel[:r0], lu_p], axis=0), (0, r0)
        )
    return ap[:n, :n], perm[:n]


def _lu_info(lu: jax.Array) -> jax.Array:
    d = jnp.diagonal(lu)
    bad = (d == 0) | ~jnp.isfinite(d)
    return jnp.where(jnp.any(bad), jnp.argmax(bad) + 1, 0).astype(jnp.int32)


_GETRF_LL_MIN_N = 4096  # f64 on TPU: left-looking from here
# Chip-validated ceiling (round 5): the full left-looking program is
# residual-correct on the real chip at 4096 (1.0e-11) and 8192 (3.7e-11),
# but the n = 16384 / nb = 4096 run factors WRONG (independent numpy
# residual 13.7) even though every component — the (12288, 4096) all-gemm
# panel, the f32-seeded unit-L leaf inverses, Ozaki products at the exact
# operand shapes/distributions, and the 4-panel driver at 8192 — passes
# in isolation at matching shapes.  The suspect is an XLA/x64-rewriter
# lowering issue at the full-program scale (e.g. the ~1.6 GB f64
# trailing-row gather); until it is root-caused the dispatch is gated to
# the validated sizes and larger f64 problems take the scanned form.
_GETRF_LL_MAX_N = 8192


@instrument("getrf_array")
def getrf_array(a: jax.Array) -> LUFactors:
    """Partial-pivot LU, PA = LU (src/getrf.cc)."""
    if (
        a.dtype in (jnp.dtype(jnp.float64), jnp.dtype(jnp.complex128))
        and a.ndim == 2
        and a.shape[0] == a.shape[1] >= _GETRF_LL_MIN_N
    ):
        from ..ops.matmul import _tpu_is_default

        if _tpu_is_default():
            if a.shape[0] <= _GETRF_LL_MAX_N:
                lu, perm = _getrf_left_looking(a)
                return LUFactors(lu, perm, _lu_info(lu))
            # past the validated ceiling: the scanned single-program form
            # (correct on chip; the recursive trace is too large here)
            return getrf_scan_array(a)
    lu, perm = _getrf_rec(a)
    return LUFactors(lu, perm, _lu_info(lu))


# ---------------------------------------------------------------------------
# Single-program scanned LU (north-star sizes)
#
# The recursive form above traces a full binary tree of panels — ~2n/w HLO
# node groups, which explodes compile time and program size at n = 65536
# (the reference hits the same wall differently: its task DAG is runtime-
# scheduled, getrf.cc:86-200).  The scanned form is ONE lax.fori_loop whose
# body works on full-size arrays with static shapes and row/col masks, so
# the program is O(1) in n.  Cost: the trailing update runs on the full
# matrix every step (~2.25x the optimal flop count for m = n) — the same
# trade the masked mesh kernels make (parallel/dist_chol.py) — but every
# flop is a big MXU gemm, and compile time stays flat.
# ---------------------------------------------------------------------------


def _swaps_to_perm(piv: jax.Array, kk, m: int, nb: int) -> jax.Array:
    """Permutation vector from a panel's pivot-swap sequence.

    piv[j] is the global row swapped with row kk+j at elimination step j
    (LAPACK ipiv semantics, 0-based).
    """

    def step(j, pv):
        gi = kk + j
        a_, b_ = pv[gi], pv[piv[j]]
        return pv.at[gi].set(b_).at[piv[j]].set(a_)

    return jax.lax.fori_loop(0, nb, step, jnp.arange(m))


def _panel_lu_masked(panel: jax.Array, kk, nmin: int, m_true: int, pivot: bool = True):
    """LU of full-height panel columns [kk, kk+nb) with rows < kk frozen.
    Returns (factored panel, pivot row per column).

    The panel is (mp, nb) with rows >= m_true zero padding; elimination
    step j operates on global row/col index kk+j and is masked off once
    kk+j >= nmin = min(m, n).  Padded and dead (all-zero) columns keep
    p = gi, matching LAPACK's keep-in-place zero-pivot behavior.  With
    ``pivot=False`` no row interchanges happen (pre-pivoted panels,
    tournament path).
    """
    mp, nb = panel.shape
    rows = jnp.arange(mp)
    cols = jnp.arange(nb)

    def step(j, carry):
        pan, piv = carry
        gi = kk + j
        active = gi < nmin
        if pivot:
            col = jax.lax.dynamic_slice(pan, (0, j), (mp, 1))[:, 0]
            mag = jnp.where(
                (rows >= gi) & (rows < m_true) & active, jnp.abs(col), -jnp.inf
            )
            p = jnp.argmax(mag)
            p = jnp.where(active & (mag[p] > 0), p, gi)
            # swap rows gi <-> p
            r_gi = jax.lax.dynamic_slice(pan, (gi, 0), (1, nb))
            r_p = jax.lax.dynamic_slice(pan, (p, 0), (1, nb))
            pan = jax.lax.dynamic_update_slice(pan, r_p, (gi, 0))
            pan = jax.lax.dynamic_update_slice(pan, r_gi, (p, 0))
            piv = piv.at[j].set(p)
        col = jax.lax.dynamic_slice(pan, (0, j), (mp, 1))[:, 0]
        pivval = col[gi]
        denom = jnp.where(pivval == 0, jnp.ones_like(pivval), pivval)
        below = ((rows > gi) & active).astype(pan.dtype)
        lcol = col / denom * below
        newcol = col * (1 - below) + lcol
        pan = jax.lax.dynamic_update_slice(pan, newcol[:, None], (0, j))
        urow = pan[gi] * (cols > j).astype(pan.dtype)
        pan = pan - jnp.outer(lcol, urow)
        return pan, piv

    piv0 = kk + jnp.arange(nb)  # identity swaps for masked-off columns
    return jax.lax.fori_loop(0, nb, step, (panel, piv0))


def _apply_bounded_perm(x: jax.Array, pv: jax.Array, targets: jax.Array):
    """x[pv] when pv differs from the identity only at ``targets``
    (static count): gather + scatter 2nb rows instead of all of x."""
    vals = x[pv[targets]]
    return x.at[targets].set(vals, mode="drop", unique_indices=False)


def _scan_step_update(out, pan, perm, piv, kk, nb: int, pv=None):
    """Shared tail of one scanned panel step: apply the panel's row swaps
    (bounded scatter — a panel moves at most 2nb rows; phase ``swap``),
    write the factored panel back and solve the U row block (``panel``),
    masked trailing gemm (``bulk``)."""
    from ..parallel.comm import phase_scope

    mp, n = out.shape
    rows = jnp.arange(mp)
    cols = jnp.arange(n)

    with phase_scope("swap"):
        if pv is None:
            pv = _swaps_to_perm(piv, kk, mp, nb)
        targets = jnp.concatenate([kk + jnp.arange(nb), piv])
        out = _apply_bounded_perm(out, pv, targets)
        perm = _apply_bounded_perm(perm, pv, targets)
    with phase_scope("panel"):
        out = jax.lax.dynamic_update_slice(out, pan, (0, kk))
        l11 = tri_project(
            jax.lax.dynamic_slice(pan, (kk, 0), (nb, nb)), Uplo.Lower, Diag.Unit
        )
        rowblk = jax.lax.dynamic_slice(out, (kk, 0), (nb, n))
        # row solve as explicit-inverse gemm (cf. chol._potrf_scan): the
        # wide-rhs triangular_solve runs ~10x below the MXU matmul rate
        linv = jax.lax.linalg.triangular_solve(
            l11[None], jnp.eye(nb, dtype=out.dtype)[None], left_side=True,
            lower=True, transpose_a=False, unit_diagonal=True,
        )[0]
        u12 = matmul(linv, rowblk).astype(out.dtype)
        right = (cols >= kk + nb)[None, :]
        rowblk = jnp.where(right, u12, rowblk)
        out = jax.lax.dynamic_update_slice(out, rowblk, (kk, 0))
    with phase_scope("bulk"):
        l21 = pan * ((rows >= kk + nb)[:, None]).astype(pan.dtype)
        u12m = rowblk * right.astype(pan.dtype)
        out = out - matmul(l21, u12m).astype(out.dtype)
    return out, perm


def getrf_scan_array(
    a: jax.Array, nb: int = _PANEL_W, nbuckets: int = 4
) -> LUFactors:
    """Partial-pivot LU as one fixed-shape scanned program (PA = LU).

    Same math and pivot choices as ``getrf_array`` (src/getrf.cc
    semantics); built for north-star sizes where the recursive trace is
    too large to compile.  On exactly singular inputs the zero-pivot rows
    stay in place (info > 0 flags them) rather than swapping zero rows.

    The k-range is segmented into ``nbuckets`` statically-shrinking
    trailing views (cf. parallel.dist_chol bucketing): pivot search and
    swaps only ever touch rows >= k, so each bucket runs entirely on
    ``out[off:, off:]``, cutting the HBM-bound masked trailing traffic to
    ~0.47x of the full-width form at 4 buckets; finished L columns receive
    the bucket's composed row permutation in one gather at bucket end
    (LAPACK's deferred laswp on columns < k).  Phase scopes as in
    ``chol._potrf_scan``: ``panel``, ``swap``, ``bulk`` per step,
    ``regroup`` at the bucket boundaries.
    """
    from ..parallel.comm import phase_scope

    m, n = a.shape
    nmin = min(m, n)
    nsteps = -(-nmin // nb)
    # pad rows AND cols so the dynamic panel slices never clamp (a clamped
    # start silently reads the wrong window)
    mp = max(m, nsteps * nb)
    np_ = max(n, nsteps * nb)
    out = jnp.pad(a, ((0, mp - m), (0, np_ - n)))
    perm = jnp.arange(mp)

    bounds = [nsteps * g // nbuckets for g in range(nbuckets)] + [nsteps]
    for g in range(nbuckets):
        k0, k1 = bounds[g], bounds[g + 1]
        if k0 == k1:
            continue
        off = k0 * nb
        with phase_scope("regroup"):
            view = out[off:, off:]
        mv = mp - off

        def body(k, carry, off=off, mv=mv):
            view, pl = carry
            kk = k * nb - off  # view-local column/row of the panel head
            with phase_scope("panel", k):
                panel = jax.lax.dynamic_slice(view, (0, kk), (mv, nb))
                # global masks shift uniformly: local row r is global off + r
                pan, piv = _panel_lu_masked(panel, kk, nmin - off, m - off)
            # the factored panel is already in post-swap row order; swapping
            # `view` rows then overwriting columns [kk, kk+nb) reconciles both
            return _scan_step_update(view, pan, pl, piv, kk, nb)

        view, pl = jax.lax.fori_loop(
            k0, k1, body, (view, jnp.arange(mv))
        )
        with phase_scope("regroup"):
            out = out.at[off:, off:].set(view)
        with phase_scope("swap"):
            if off:
                out = out.at[off:, :off].set(out[off:, :off][pl])
            perm = perm.at[off:].set(perm[off:][pl])
    return LUFactors(out[:m, :n], perm[:m], _lu_info(out[:m, :n]))


# ---------------------------------------------------------------------------
# No-pivot LU (src/getrf_nopiv.cc) — structurally potrf-like
# ---------------------------------------------------------------------------


_GETRF_NOPIV_SCAN_MIN_N = 16384  # above this the recursive trace is too large


def _schur_product(l: jax.Array, u: jax.Array, precision: Precision) -> jax.Array:
    """``l @ u`` of a trailing update at the caller's tier.  Under
    ``Precision.Fast`` a float32 update takes bfloat16 operands and
    accumulates in float32 (HPL-MxP's tensor-core form, the MXU's single
    pass), written out so every backend rounds alike; other tiers and
    dtypes go through ``ops.matmul``."""
    if precision == Precision.Fast and l.dtype == jnp.float32:
        return jnp.matmul(l.astype(jnp.bfloat16), u.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return matmul(l, u, precision=precision)


def _getrf_nopiv_rec(a: jax.Array, precision: Precision = Precision.Highest) -> jax.Array:
    n = min(a.shape)
    if n <= _NB:
        return _nopiv_base(a)
    h = _split(n)
    a11, a12, a21, a22 = a[:h, :h], a[:h, h:], a[h:, :h], a[h:, h:]
    lu11 = _nopiv_base(a11) if h <= _NB else _getrf_nopiv_rec(a11, precision)
    u12 = trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.Unit, 1.0, lu11, a12)
    l21 = trsm_array(Side.Right, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, lu11, a21)
    s = a22 - _schur_product(l21, u12, precision).astype(a.dtype)
    lu22 = _getrf_nopiv_rec(s, precision)
    return jnp.block([[lu11, u12], [l21, lu22]])


def _getrf_nopiv_scan(
    a: jax.Array, nb: int = 256, nbuckets: int = 4, precision: Precision = Precision.Highest
) -> jax.Array:
    """Single-program scanned LU without pivoting of a square array, on
    ``chol._scan_factor``'s shrinking bucketed views, with
    ``chol._potrf_scan``'s step order so that each k-step updates the
    view in place: (1) the panel column and the panel row leave the carry
    as materialized values (``optimization_barrier``); (2) ``view - L21
    U12`` is the step's only op that reads or writes the whole view; (3)
    the finished column and row go back last.  The panel is the nb x nb
    diagonal block's unblocked LU, then ``L21 = A21 U11^-1`` and ``U12 =
    L11^-1 A12`` as explicit-inverse gemms at full precision; the update
    runs at ``precision`` (``_schur_product``).  ``L21`` is zero in the
    panel rows and above and ``U12`` in the panel columns and left of
    them, so the update leaves the panel as it was.  Phase scopes as in
    ``_potrf_scan``: ``panel`` and ``bulk`` per step, ``regroup`` at the
    bucket boundaries."""
    from ..parallel.comm import phase_scope
    from .chol import _scan_factor

    def step(k, view, off, rows):
        nv = rows.shape[0]
        dt = view.dtype
        with phase_scope("panel", k):
            kk = k * nb - off  # view-local panel head
            col, row = jax.lax.optimization_barrier((
                jax.lax.dynamic_slice(view, (0, kk), (nv, nb)),
                jax.lax.dynamic_slice(view, (kk, 0), (nb, nv)),
            ))
            lu11 = _nopiv_base(jax.lax.dynamic_slice(col, (kk, 0), (nb, nb)))
            eye = jnp.eye(nb, dtype=dt)[None]
            linv = jax.lax.linalg.triangular_solve(
                lu11[None], eye, left_side=True, lower=True, unit_diagonal=True)[0]
            uinv = jax.lax.linalg.triangular_solve(
                lu11[None], eye, left_side=True, lower=False)[0]
            below = rows >= kk + nb
            ondiag = (rows >= kk) & (rows < kk + nb)
            l21 = jnp.where(below[:, None], matmul(col, uinv).astype(dt), 0)
            u12 = jnp.where(below[None, :], matmul(linv, row).astype(dt), 0)
            newcol = jnp.where(below[:, None], l21, jnp.where(
                ondiag[:, None],
                jax.lax.dynamic_update_slice(jnp.zeros((nv, nb), dt), lu11, (kk, 0)), col))
            newrow = jnp.where(below[None, :], u12, jnp.where(
                ondiag[None, :],
                jax.lax.dynamic_update_slice(jnp.zeros((nb, nv), dt), lu11, (0, kk)), row))
        with phase_scope("bulk", k):
            view = view - _schur_product(l21, u12, precision).astype(dt)
        with phase_scope("panel", k):
            view = jax.lax.dynamic_update_slice(view, newcol, (0, kk))
            view = jax.lax.dynamic_update_slice(view, newrow, (kk, 0))
        return view

    return _scan_factor(a, nb, nbuckets, step)


def _nopiv_base(a: jax.Array) -> jax.Array:
    m, n = a.shape
    rows = jnp.arange(m)

    def step(j, a):
        piv = a[j, j]
        denom = jnp.where(piv == 0, jnp.ones_like(piv), piv)
        below = (rows > j).astype(a.dtype)
        lcol = a[:, j] / denom * below
        a = a.at[:, j].set(a[:, j] * (1 - below) + lcol)
        cmask = (jnp.arange(n) > j).astype(a.dtype)
        return a - jnp.outer(lcol, a[j] * cmask)

    return jax.lax.fori_loop(0, min(m, n), step, a)


def getrf_nopiv_array(a: jax.Array, precision: Precision = Precision.Highest) -> LUFactors:
    """LU without pivoting (src/getrf_nopiv.cc); the trailing updates run
    at ``precision`` (``_schur_product``).  Square arrays from
    ``_GETRF_NOPIV_SCAN_MIN_N`` take the scanned single-program form."""
    if a.ndim == 2 and a.shape[0] == a.shape[1] >= _GETRF_NOPIV_SCAN_MIN_N:
        lu = _getrf_nopiv_scan(a, precision=precision)
    else:
        lu = _getrf_nopiv_rec(a, precision)
    return LUFactors(lu, jnp.arange(a.shape[0]), _lu_info(lu))


# ---------------------------------------------------------------------------
# Tournament pivoting (CALU, src/getrf_tntpiv.cc + internal_getrf_tntpiv.cc)
# ---------------------------------------------------------------------------


def _tournament_reduce(ap: jax.Array, idx: jax.Array, w: int, sentinel: int):
    """Binary-tree reduction of pivot candidates: small partial-pivot LUs
    pick the w best rows per block, pairs of blocks merge until one block
    remains.  ``ap`` (rows, w) must have invalid rows zeroed and ``idx``
    (rows,) their ids set to ``sentinel``.  Returns (values, ids) of the w
    winners.  Shared by the single-chip scanned tntpiv and the mesh
    tournament (parallel/dist_lu.py)."""
    mp = ap.shape[0]
    block = max(2 * w, _PANEL_W)
    nblk = -(-mp // block)
    pad = nblk * block - mp
    ap = jnp.pad(ap, ((0, pad), (0, 0)))
    idx = jnp.pad(idx, (0, pad), constant_values=sentinel)
    cand_a = ap.reshape(nblk, block, w)
    cand_i = idx.reshape(nblk, block)

    def local_top(a_blk, i_blk):
        _, p = _panel_lu(a_blk)
        return a_blk[p][:w], i_blk[p][:w]

    tops_a, tops_i = jax.vmap(local_top)(cand_a, cand_i)
    while tops_a.shape[0] > 1:
        k = tops_a.shape[0]
        if k % 2 == 1:  # odd: pad a dead block
            tops_a = jnp.concatenate([tops_a, tops_a[-1:] * 0], axis=0)
            tops_i = jnp.concatenate(
                [tops_i, jnp.full_like(tops_i[-1:], sentinel)], axis=0
            )
            k += 1
        pa = tops_a.reshape(k // 2, 2 * w, w)
        pi = tops_i.reshape(k // 2, 2 * w)
        tops_a, tops_i = jax.vmap(local_top)(pa, pi)
    return tops_a[0], tops_i[0]


def _tournament_pivots_masked(panel: jax.Array, w: int, kk, m_true: int) -> jax.Array:
    """Tournament pivot selection over full-height panel rows with rows
    < kk (already factored) and >= m_true (padding) masked out.  Static
    shapes throughout: the block grid and tree depth depend only on the
    padded height.  Returns w global row indices (invalid slots carry the
    sentinel mp when fewer than w candidate rows remain)."""
    mp = panel.shape[0]
    rows = jnp.arange(mp)
    valid = (rows >= kk) & (rows < m_true)
    ap = jnp.where(valid[:, None], panel, 0)
    idx = jnp.where(valid, rows, mp)  # sentinel rows sort last in each LU
    _, tops_i = _tournament_reduce(ap, idx, w, mp)
    return tops_i


def _tournament_swap_seq(piv: jax.Array, kk, mp: int) -> jax.Array:
    """Convert tournament-selected global rows into a LAPACK-style
    sequential swap sequence (swap i brings selected row i to kk+i),
    tracking row positions as earlier swaps displace them."""
    w = piv.shape[0]

    def step(i, carry):
        seq, pos2row, row2pos = carry
        tgt = kk + i
        valid = piv[i] < mp
        cur = jnp.where(valid, row2pos[jnp.minimum(piv[i], mp - 1)], tgt)
        r1 = pos2row[tgt]
        r2 = pos2row[cur]
        pos2row = pos2row.at[tgt].set(r2).at[cur].set(r1)
        row2pos = row2pos.at[r2].set(tgt).at[r1].set(cur)
        return seq.at[i].set(cur), pos2row, row2pos

    seq0 = kk + jnp.arange(w)
    ident = jnp.arange(mp)
    seq, _, _ = jax.lax.fori_loop(0, w, step, (seq0, ident, ident))
    return seq


def getrf_tntpiv_array(a: jax.Array, nb: int = _PANEL_W) -> LUFactors:
    """Blocked LU with tournament pivoting (CALU) as one fixed-shape
    scanned program.  Per panel, the tournament tree picks nb pivot rows
    which are swapped to the top LAPACK-style, then the panel factors
    without further interchanges (getrf_tntpiv.cc:18-169,
    internal_getrf_tntpiv.cc)."""
    m, n = a.shape
    nmin = min(m, n)
    nb = min(nb, nmin)
    nsteps = -(-nmin // nb)
    mp = max(m, nsteps * nb)
    np_ = max(n, nsteps * nb)
    out = jnp.pad(a, ((0, mp - m), (0, np_ - n)))

    def body(k, carry):
        out, perm = carry
        kk = k * nb
        panel = jax.lax.dynamic_slice(out, (0, kk), (mp, nb))
        piv_rows = _tournament_pivots_masked(panel, nb, kk, m)
        piv = _tournament_swap_seq(piv_rows, kk, mp)
        pv = _swaps_to_perm(piv, kk, mp, nb)
        targets = jnp.concatenate([kk + jnp.arange(nb), piv])
        panel = _apply_bounded_perm(panel, pv, targets)
        pan, _ = _panel_lu_masked(panel, kk, nmin, m, pivot=False)
        out, perm = _scan_step_update(out, pan, perm, piv, kk, nb, pv=pv)
        return out, perm

    out, perm = jax.lax.fori_loop(0, nsteps, body, (out, jnp.arange(mp)))
    return LUFactors(out[:m, :n], perm[:m], _lu_info(out[:m, :n]))


# ---------------------------------------------------------------------------
# Solves / drivers
# ---------------------------------------------------------------------------


def getrs_array(f: LUFactors, b: jax.Array, op: Op = Op.NoTrans) -> jax.Array:
    """Solve op(A) X = B from factors (src/getrs.cc)."""
    lu, perm = f.lu, f.perm
    n = lu.shape[0]
    if op == Op.NoTrans:
        pb = b[perm]
        y = trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.Unit, 1.0, lu, pb)
        return trsm_array(Side.Left, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, lu, y)
    # op(A) = A^T or A^H: solve U^op y = b; L^op z = y; x = P^T z
    y = trsm_array(Side.Left, Uplo.Upper, op, Diag.NonUnit, 1.0, lu, b)
    z = trsm_array(Side.Left, Uplo.Lower, op, Diag.Unit, 1.0, lu, y)
    inv = jnp.argsort(perm)
    return z[inv]


@instrument("gesv_array")
def gesv_array(a: jax.Array, b: jax.Array, method: MethodLU = MethodLU.PartialPiv):
    """Factor + solve (src/gesv.cc). Returns (x, factors).  The factor
    sits under the ``getrf`` stage scope, the solves under ``trsm``."""
    if method == MethodLU.RBT:
        from .rbt import gesv_rbt_array

        return gesv_rbt_array(a, b)
    with jax.named_scope("getrf"):
        if method == MethodLU.PartialPiv:
            f = getrf_array(a)
        elif method == MethodLU.CALU:
            f = getrf_tntpiv_array(a)
        elif method == MethodLU.NoPiv:
            f = getrf_nopiv_array(a)
        else:
            raise ValueError(method)
    with jax.named_scope("trsm"):
        return getrs_array(f, b), f


def getri_array(f: LUFactors) -> jax.Array:
    """Matrix inverse from factors (src/getri.cc): A^-1 = U^-1 L^-1 P."""
    from .tri import trtri_array

    uinv = trtri_array(tri_project(f.lu, Uplo.Upper), Uplo.Upper, Diag.NonUnit)
    linv = trtri_array(tri_project(f.lu, Uplo.Lower, Diag.Unit), Uplo.Lower, Diag.Unit)
    x = matmul(uinv, linv).astype(f.lu.dtype)
    # A^-1 = (U^-1 L^-1) P; right-multiplying by P permutes columns by
    # perm^-1 since (X P)[i, j] = X[i, perm^-1(j)]
    return x[:, jnp.argsort(f.perm)]


def getri_oop_array(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Out-of-place inverse (src/getriOOP.cc): factor A and solve
    A X = I without forming triangular inverses — the reference's
    workspace-matrix variant.  Returns (A^-1, info)."""
    f = getrf_array(a)
    eye = jnp.eye(a.shape[0], dtype=a.dtype)
    return getrs_array(f, eye), f.info


# object-level drivers -------------------------------------------------------


def getrf(a: ArrayLike, opts: Optional[Options] = None) -> Tuple[Matrix, LUFactors]:
    ad = a.array if isinstance(a, BaseMatrix) else jnp.asarray(a)
    method = get_option(opts, Option.MethodLU, MethodLU.PartialPiv)
    if method == MethodLU.CALU:
        # MaxPanelThreads (reference: threads cooperating on one panel,
        # internal_getrf.cc) maps to the tournament panel-width
        # multiplier: wider panels amortize per-step latency against
        # bigger trailing updates, the same trade the reference makes by
        # adding panel threads (PartialPiv/NoPiv panels are recursive and
        # take no width knob).  NUMERICAL SIDE EFFECT — unlike the
        # reference, where the option is parallelism-only and bitwise
        # neutral, here it changes the CALU tournament width and hence
        # WHICH pivots win: a wider panel factors more columns without
        # interchanges between tournament rounds, so pivot quality (and
        # the element growth bound) degrades as the width grows.  Results
        # remain backward-stable in the CALU sense but are NOT invariant
        # under this option.  Clamped to 8x: past ~512-wide panels the
        # tournament factors without interchanges over too many columns
        # (pivot-growth risk) and the block LUs blow up compile time.
        threads = int(get_option(opts, Option.MaxPanelThreads, 1))
        f = getrf_tntpiv_array(ad, nb=_PANEL_W * min(max(1, threads), 8))
    elif method == MethodLU.NoPiv:
        f = getrf_nopiv_array(ad)
    else:
        f = getrf_array(ad)
    return Matrix(data=f.lu), f


def gesv(a: ArrayLike, b: ArrayLike, opts: Optional[Options] = None):
    ad = a.array if isinstance(a, BaseMatrix) else jnp.asarray(a)
    bd = b.array if isinstance(b, BaseMatrix) else jnp.asarray(b)
    method = get_option(opts, Option.MethodLU, MethodLU.PartialPiv)
    x, f = gesv_array(ad, bd, method)
    if isinstance(b, BaseMatrix):
        x = replace(b, data=x)
    return x, f


# ---------------------------------------------------------------------------
# Band LU (src/gbtrf.cc, gbtrs.cc, gbsv.cc)
# ---------------------------------------------------------------------------


def gbtrf_array(a: jax.Array, kl: int, ku: int) -> LUFactors:
    """Band LU with partial pivoting. Pivoting widens U's band to kl + ku
    (LAPACK gbtrf semantics), so U is projected to that band; L's multiplier
    columns have at most kl nonzeros each but pivoting scatters them to
    arbitrary rows (Golub & Van Loan band-LU), so the strictly-lower part is
    kept dense — projecting it would corrupt the factorization."""
    f = getrf_array(band_project(a, kl, ku))
    l_part = tri_project(f.lu, Uplo.Lower, Diag.Unit) - jnp.eye(*f.lu.shape, dtype=f.lu.dtype)
    u_part = band_project(tri_project(f.lu, Uplo.Upper), 0, kl + ku)
    return LUFactors(l_part + u_part, f.perm, f.info)


def gbtrs_array(f, b: jax.Array, kl: int, ku: int, op: Op = Op.NoTrans) -> jax.Array:
    from .band import BandLU, gbtrs_band

    if isinstance(f, BandLU):  # narrow-band factor from gbsv_array's routing
        if op != Op.NoTrans:
            raise ValueError("windowed band factors support op=NoTrans only")
        return gbtrs_band(f, b)
    return getrs_array(f, b, op)


def gbsv_array(a: jax.Array, b: jax.Array, kl: int, ku: int):
    """Band solve (src/gbsv.cc).  Narrow bands take the windowed
    O(n kl (kl+ku)) path (linalg.band, LAPACK gbtrf pivot semantics —
    its factor carries per-window permutations, not a global one); wide
    bands fall back to the dense partial-pivot factorization."""
    from .band import band_worthwhile

    if band_worthwhile(a.shape[0], max(kl, 1) + max(ku, 1)):
        from .band import gbsv_band

        x, f, info = gbsv_band(a, b, kl, ku)
        return x, f
    f = gbtrf_array(a, kl, ku)
    return gbtrs_array(f, b, kl, ku), f
