"""QR / LQ factorization and least squares.

Analogues of ``src/{geqrf,gelqf,unmqr,unmlq,cholqr,gels,gels_qr,
gels_cholqr}.cc`` and internal panels ``internal_geqrf.cc`` /
``Tile_geqrf.hh`` / the CAQR tree ``internal_ttqrt.cc``.

Design inversion: the reference does CAQR — each rank factors its tile stack
(geqrf panel), then a binary tree of triangle-triangle QRs (ttqrt) merges the
per-rank R factors over MPI (geqrf.cc:191-230, SURVEY.md P6).  The TPU form
is recursive compact-WY (Elmroth-Gustavson): factor the left half, apply
``I - Y T Y^H`` to the right half with three matmuls, recurse, and merge
T blocks — the same communication-avoiding tree, but the "tree" is the
recursion and the merges are matmuls XLA schedules over the mesh (sharded
runs get their collectives from GSPMD; the explicit mesh-axis ttqrt tree
lives in slate_tpu.parallel.dist_qr).  The unblocked base panel is a masked
``lax.fori_loop`` of Householder reflections (LAPACK larfg/larf semantics,
complex-safe).

Factors are packed LAPACK-style: V below the diagonal (unit first element
implicit), R on/above; plus the n x n upper-triangular WY accumulator T such
that Q = I - V T V^H.
"""

from __future__ import annotations

from ..obs import instrument

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..blas3.blas3 import trsm_array
from ..core.matrix import tri_project
from ..ops.matmul import matmul
from ..types import Diag, MethodGels, Op, Option, Options, Side, SlateError, Uplo, get_option

Array = jax.Array

_QR_PANEL = 64


class QRFactors(NamedTuple):
    """Packed QR: ``vr`` has V below diag / R above; ``t`` is the WY
    accumulator, upper triangular (n, n): Q = I - V T V^H."""

    vr: Array
    t: Array


class LQFactors(NamedTuple):
    """Packed LQ: ``lv`` has L on/below diag, V^H above (rows are
    reflectors); ``t`` as in QR for the transposed problem."""

    lv: Array
    t: Array


def _sign_safe(x: Array) -> Array:
    """sign(x) with sign(0) = 1, complex-safe (LAPACK larfg convention)."""
    if jnp.issubdtype(x.dtype, jnp.complexfloating):
        mag = jnp.abs(x)
        return jnp.where(mag == 0, jnp.ones_like(x), x / jnp.where(mag == 0, 1, mag))
    return jnp.where(x >= 0, jnp.ones_like(x), -jnp.ones_like(x))


def _panel_qr(a: Array) -> Tuple[Array, Array]:
    """Unblocked Householder QR of (m, w). Returns (packed VR, tau)."""
    m, w = a.shape
    rows = jnp.arange(m)
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)

    def step(j, carry):
        a, tau = carry
        col = a[:, j]
        below = rows > j
        alpha = col[j]
        xnorm2 = jnp.sum(jnp.where(below, jnp.abs(col) ** 2, 0))
        anorm = jnp.sqrt(jnp.abs(alpha) ** 2 + xnorm2)
        s = _sign_safe(alpha if not cplx else jnp.where(jnp.real(alpha) == 0, jnp.asarray(1, a.dtype), alpha))
        beta = -s * anorm.astype(a.dtype)
        zero_col = (anorm == 0)
        beta = jnp.where(zero_col, jnp.ones_like(beta), beta)
        tj = (beta - alpha) / beta
        tj = jnp.where(zero_col, jnp.zeros_like(tj), tj)
        denom = alpha - beta
        denom = jnp.where(denom == 0, jnp.ones_like(denom), denom)
        v = jnp.where(below, col / denom, jnp.zeros_like(col))
        v = v.at[j].set(1)
        # apply H = I - tau v v^H to remaining columns (mask cols <= j)
        w_row = matmul(jnp.conj(v)[None, :], a)[0]  # v^H A
        cmask = (jnp.arange(w) > j).astype(a.dtype)
        a = a - jnp.outer(tj * v, w_row * cmask)
        # store: R entry at (j, j) = beta, v below diagonal
        newcol = jnp.where(below, v, a[:, j])
        newcol = newcol.at[j].set(jnp.where(zero_col, alpha, beta))
        a = a.at[:, j].set(newcol)
        tau = tau.at[j].set(tj)
        return a, tau

    tau0 = jnp.zeros(w, a.dtype)
    a, tau = jax.lax.fori_loop(0, min(m, w), step, (a, tau0))
    return a, tau


def _panel_qr_offset(a: Array, row0) -> Tuple[Array, Array, Array]:
    """Householder QR of a full-height column block whose pivot row for
    column j is the (traced) global row ``row0 + j``.

    Rows < row0 of ``a`` must be zero (caller masks its history out); the
    elimination never touches them, so the result can be scattered back
    into a larger matrix without disturbing already-factored content.
    Dead columns (no weight at or below the pivot) get tau = 0.

    Returns (r, v, tau): ``r`` is ``a`` with R at rows row0..row0+w and
    zeros below each pivot; ``v`` holds the explicit reflectors (unit
    pivot entries, zeros above); ``tau`` the w scalar factors.

    This is the fixed-shape panel the scanned two-stage reductions
    (he2hb / ge2tb) loop over — the reference runs the same panel QR per
    block column inside its task DAG (internal_geqrf.cc, he2hb.cc:207).
    """
    m, w = a.shape
    rows = jnp.arange(m)
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)

    def step(j, carry):
        a, vmat, tau = carry
        gi = row0 + j
        col = jax.lax.dynamic_slice(a, (0, j), (m, 1))[:, 0]
        below = rows > gi
        alpha = col[gi]
        xnorm2 = jnp.sum(jnp.where(below, jnp.abs(col) ** 2, 0))
        anorm = jnp.sqrt(jnp.abs(alpha) ** 2 + xnorm2)
        s = _sign_safe(
            alpha if not cplx else jnp.where(jnp.real(alpha) == 0, jnp.asarray(1, a.dtype), alpha)
        )
        beta = -s * anorm.astype(a.dtype)
        zero_col = anorm == 0
        beta = jnp.where(zero_col, jnp.ones_like(beta), beta)
        tj = (beta - alpha) / beta
        tj = jnp.where(zero_col, jnp.zeros_like(tj), tj)
        denom = alpha - beta
        denom = jnp.where(denom == 0, jnp.ones_like(denom), denom)
        v = jnp.where(below, col / denom, jnp.zeros_like(col))
        v = v.at[gi].set(jnp.where(zero_col, jnp.zeros((), a.dtype), jnp.ones((), a.dtype)))
        w_row = matmul(jnp.conj(v)[None, :], a)[0]
        cmask = (jnp.arange(w) > j).astype(a.dtype)
        a = a - jnp.outer(tj * v, w_row * cmask)
        newcol = jnp.where(below, jnp.zeros_like(col), col)
        newcol = newcol.at[gi].set(jnp.where(zero_col, alpha, beta))
        a = jax.lax.dynamic_update_slice(a, newcol[:, None], (0, j))
        return a, vmat.at[:, j].set(v), tau.at[j].set(tj)

    r, v, tau = jax.lax.fori_loop(
        0, w, step, (a, jnp.zeros_like(a), jnp.zeros(w, a.dtype))
    )
    return r, v, tau


def _larft_v(v: Array, tau: Array) -> Array:
    """Compact-WY T from explicit reflectors (columns of ``v``)."""
    w = v.shape[1]
    vhv = matmul(jnp.conj(v).T, v)

    def step(j, t):
        tcol = -tau[j] * matmul(t, vhv[:, j][:, None])[:, 0]
        mask = (jnp.arange(w) < j).astype(v.dtype)
        t = t.at[:, j].set(tcol * mask)
        return t.at[j, j].set(tau[j])

    return jax.lax.fori_loop(0, w, step, jnp.zeros((w, w), v.dtype))


def _larft(vr: Array, tau: Array) -> Array:
    """Build the compact-WY T from packed reflectors (LAPACK larft forward
    columnwise): T[:j, j] = -tau_j * T[:j, :j] @ (V^H v_j)."""
    m, w = vr.shape
    rows = jnp.arange(m)[:, None]
    cols = jnp.arange(w)[None, :]
    v = jnp.where(rows > cols, vr, jnp.where(rows == cols, jnp.ones_like(vr), jnp.zeros_like(vr)))
    vhv = matmul(jnp.conj(v).T, v)  # (w, w)

    def step(j, t):
        tcol = -tau[j] * matmul(t, vhv[:, j][:, None])[:, 0]
        mask = (jnp.arange(w) < j).astype(vr.dtype)
        t = t.at[:, j].set(tcol * mask)
        return t.at[j, j].set(tau[j])

    t0 = jnp.zeros((w, w), vr.dtype)
    return jax.lax.fori_loop(0, w, step, t0)


def _panel_qr_t(a: Array) -> Tuple[Array, Array, Array]:
    """(packed VR, tau, T) of one panel: the ``_panel_qr`` + ``_larft``
    pair."""
    vr, tau = _panel_qr(a)
    return vr, tau, _larft(vr, tau)


def _panel_qr_offset_t(a: Array, row0) -> Tuple[Array, Array, Array, Array]:
    """(r, v, tau, T) of one offset-pivot panel: the
    ``_panel_qr_offset`` + ``_larft_v`` pair (``row0`` may be traced)."""
    r, v, tau = _panel_qr_offset(a, row0)
    return r, v, tau, _larft_v(v, tau)


def _v_of(vr: Array, k: Optional[int] = None) -> Array:
    """Extract unit-lower V from packed storage (first k reflectors)."""
    m, n = vr.shape
    k = n if k is None else k
    rows = jnp.arange(m)[:, None]
    cols = jnp.arange(k)[None, :]
    block = vr[:, :k]
    return jnp.where(rows > cols, block, jnp.where(rows == cols, jnp.ones_like(block), jnp.zeros_like(block)))


def _split_qr(n: int) -> int:
    h = _QR_PANEL
    while h * 2 < n:
        h *= 2
    return h


def _geqrf_rec(a: Array) -> Tuple[Array, Array]:
    """Recursive blocked QR. Returns (packed VR, T)."""
    m, n = a.shape
    if n <= _QR_PANEL:
        vr, _, t = _panel_qr_t(a)
        return vr, t
    h = _split_qr(n)
    vr1, t1 = _geqrf_rec(a[:, :h])
    v1 = _v_of(vr1)
    # apply Q1^H to the right block: A2 -= V1 T1^H V1^H A2
    a2 = a[:, h:]
    w = matmul(jnp.conj(v1).T, a2)
    a2 = a2 - matmul(v1, matmul(jnp.conj(t1).T, w)).astype(a.dtype)
    r12, a2b = a2[:h], a2[h:]
    vr2, t2 = _geqrf_rec(a2b)
    v2 = jnp.concatenate([jnp.zeros((h, a2b.shape[1]), a.dtype), _v_of(vr2)], axis=0)
    # merged T: [[T1, -T1 (V1^H V2) T2], [0, T2]]
    t12 = -matmul(t1, matmul(matmul(jnp.conj(v1).T, v2), t2)).astype(a.dtype)
    nt = h + t2.shape[0]
    t = jnp.zeros((nt, nt), a.dtype)
    t = t.at[:h, :h].set(t1).at[:h, h:].set(t12).at[h:, h:].set(t2)
    top = jnp.concatenate([vr1[:h], r12], axis=1)
    bot = jnp.concatenate([vr1[h:], vr2], axis=1)
    return jnp.concatenate([top, bot], axis=0), t


@instrument("geqrf_array")
def geqrf_array(a: Array) -> QRFactors:
    """slate::geqrf (src/geqrf.cc) — A = Q R."""
    vr, t = _geqrf_rec(a)
    return QRFactors(vr, t)


class QRScanFactors(NamedTuple):
    """Scanned QR: R in ``r`` (upper), stacked per-panel global-coordinate
    reflectors ``v`` (K, mp, nb) + WY accumulators ``t`` (K, nb, nb) — the
    same storage the scanned two-stage reductions use (cf. eig.he2hb)."""

    r: Array
    v: Array
    t: Array
    nb: int


def geqrf_scan_array(a: Array, nb: int = _QR_PANEL) -> QRScanFactors:
    """Single-program scanned QR: one lax.fori_loop over panels with
    static shapes (O(1) HLO size in n) — the recursive trace explodes at
    north-star sizes.  Per panel: offset-pivot Householder QR of the
    masked full-height block column, then one global compact-WY update of
    the trailing columns."""
    from jax import lax

    m, n = a.shape
    if m < n:
        raise ValueError(f"geqrf_scan_array requires m >= n, got {a.shape}")
    nblocks = -(-n // nb)
    mp = max(m, (nblocks + 1) * nb)
    np_ = max(n, (nblocks + 1) * nb)
    ap = jnp.pad(a, ((0, mp - m), (0, np_ - n)))
    rows = jnp.arange(mp)
    cols = jnp.arange(np_)

    def body(k, carry):
        ap, vs, ts = carry
        j0 = k * nb
        j1 = j0 + nb
        colblk = lax.dynamic_slice(ap, (0, j0), (mp, nb))
        masked = jnp.where((rows >= j0)[:, None], colblk, 0)
        r_a, v, tau, t = _panel_qr_offset_t(masked, j0)
        w1 = matmul(jnp.conj(v).T, ap)
        upd = matmul(v, matmul(jnp.conj(t).T, w1)).astype(ap.dtype)
        ap = ap - upd * (cols >= j1)[None, :].astype(ap.dtype)
        newcols = jnp.where((rows >= j0)[:, None], r_a, colblk)
        ap = lax.dynamic_update_slice(ap, newcols, (0, j0))
        return ap, vs.at[k].set(v), ts.at[k].set(t)

    carry0 = (
        ap,
        jnp.zeros((nblocks, mp, nb), a.dtype),
        jnp.zeros((nblocks, nb, nb), a.dtype),
    )
    ap, vs, ts = lax.fori_loop(0, nblocks, body, carry0)
    return QRScanFactors(tri_project(ap[:m, :n], Uplo.Upper), vs, ts, nb)


def unmqr_scan_array(f: QRScanFactors, c: Array, op: Op = Op.NoTrans) -> Array:
    """Apply Q (or Q^H) from scanned factors: a fori_loop over the panel
    stack, each step three matmuls (cf. svd.unmbr_ge2tb_u)."""
    from jax import lax

    if op == Op.Trans and jnp.issubdtype(f.v.dtype, jnp.complexfloating):
        raise SlateError("unmqr_scan: Op.Trans unsupported for complex")
    nsteps, mp, _ = f.v.shape
    n0 = c.shape[0]
    cp = jnp.pad(c, ((0, mp - n0),) + ((0, 0),) * (c.ndim - 1))
    adjoint = op != Op.NoTrans

    def body(i, cp):
        k = i if adjoint else nsteps - 1 - i
        v, t = f.v[k], f.t[k]
        t = jnp.conj(t).T if adjoint else t
        return cp - matmul(v, matmul(t, matmul(jnp.conj(v).T, cp))).astype(cp.dtype)

    if nsteps:
        cp = lax.fori_loop(0, nsteps, body, cp)
    return cp[:n0]


def unmqr_array(side: Side, op: Op, f: QRFactors, c: Array) -> Array:
    """Apply Q / Q^H from geqrf factors (src/unmqr.cc): 3 matmuls.  Op.Trans
    on complex factors is undefined for compact-WY (LAPACK unmqr allows only
    'N'/'C' for complex) — rejected rather than silently computing Q^H."""
    if op == Op.Trans and jnp.issubdtype(f.vr.dtype, jnp.complexfloating):
        raise SlateError("unmqr: Op.Trans unsupported for complex; use ConjTrans")
    v = _v_of(f.vr, f.t.shape[0])
    t = f.t if op == Op.NoTrans else jnp.conj(f.t).T
    if side == Side.Left:
        w = matmul(jnp.conj(v).T, c)
        return c - matmul(v, matmul(t, w)).astype(c.dtype)
    w = matmul(c, v)
    return c - matmul(matmul(w, t), jnp.conj(v).T).astype(c.dtype)


def qr_multiply_by_q(f: QRFactors, c: Array, side: Side = Side.Left, op: Op = Op.NoTrans) -> Array:
    return unmqr_array(side, op, f, c)


def geqrf_r(f: QRFactors) -> Array:
    """Extract R (min(m,n) x n upper triangular)."""
    n = f.vr.shape[1]
    return tri_project(f.vr[: min(f.vr.shape[0], n)], Uplo.Upper)


def geqrf_q(f: QRFactors, full: bool = False) -> Array:
    """Materialize Q — thin (m, k) by default."""
    m = f.vr.shape[0]
    k = f.t.shape[0] if not full else m
    eye = jnp.eye(m, k, dtype=f.vr.dtype)
    return unmqr_array(Side.Left, Op.NoTrans, f, eye)


# ---------------------------------------------------------------------------
# LQ (src/gelqf.cc, unmlq.cc): A = L Q via QR of A^H
# ---------------------------------------------------------------------------


def gelqf_array(a: Array) -> LQFactors:
    """slate::gelqf — A = L Q.  Reduction: QR of A^H gives A^H = Qr R, so
    A = R^H Qr^H: L = R^H and the LQ reflectors are the QR reflectors
    conjugate-transposed (same V, applied from the right)."""
    f = geqrf_array(jnp.conj(a).T)
    lv = jnp.conj(f.vr).T
    return LQFactors(lv, f.t)


def unmlq_array(side: Side, op: Op, f: LQFactors, c: Array) -> Array:
    """Apply Q from gelqf: Q = (I - V T V^H)^H with V from the QR of A^H;
    i.e. Q_lq^H = Qr so multiply by Qr with flipped op.  Op.Trans on a
    complex factor would need conj(Qr), which compact-WY can't express by
    op-flipping; LAPACK unmlq likewise only defines 'N'/'C' for complex."""
    if op == Op.Trans and jnp.issubdtype(f.lv.dtype, jnp.complexfloating):
        raise SlateError("unmlq: Op.Trans unsupported for complex; use ConjTrans")
    qr_f = QRFactors(jnp.conj(f.lv).T, f.t)
    flip = {Op.NoTrans: Op.ConjTrans, Op.ConjTrans: Op.NoTrans, Op.Trans: Op.NoTrans}[op]
    return unmqr_array(side, flip, qr_f, c)


def gelqf_l(f: LQFactors) -> Array:
    m = f.lv.shape[0]
    return tri_project(f.lv[:, : min(m, f.lv.shape[1])], Uplo.Lower)


# ---------------------------------------------------------------------------
# CholeskyQR (src/cholqr.cc, MethodCholQR) — the TPU-favourite tall-skinny QR
# ---------------------------------------------------------------------------


def cholqr_array(a: Array) -> Tuple[Array, Array]:
    """Q, R with R from Cholesky of the Gram matrix (A^H A = R^H R):
    one herk + one chol + one trsm — minimal collectives, ideal on a mesh."""
    from .chol import potrf_array

    g = matmul(jnp.conj(a).T, a).astype(a.dtype)
    u, info = potrf_array(g, Uplo.Upper)
    q = trsm_array(Side.Right, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, u, a)
    return q, u


# ---------------------------------------------------------------------------
# Least squares (src/gels.cc, gels_qr.cc, gels_cholqr.cc)
# ---------------------------------------------------------------------------


@instrument("gels_array")
def gels_array(
    a: Array, b: Array, opts: Optional[Options] = None
) -> Array:
    """Least-squares / minimum-norm solve of op(A) X ~= B (src/gels.cc).
    m >= n: QR; m < n: minimum-norm via LQ."""
    m, n = a.shape
    method = get_option(opts, Option.MethodGels, MethodGels.QR)
    if m >= n:
        if method == MethodGels.CholQR:
            q, r = cholqr_array(a)
            y = matmul(jnp.conj(q).T, b).astype(b.dtype)
            return trsm_array(Side.Left, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, r, y)
        f = geqrf_array(a)
        qhb = unmqr_array(Side.Left, Op.ConjTrans, f, b)
        r = f.vr[:n]
        return trsm_array(Side.Left, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, r, qhb[:n])
    # minimum norm: A = L Q, x = Q^H L^-1 b
    f = gelqf_array(a)
    l = f.lv[:, :m]
    y = trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.NonUnit, 1.0, l, b)
    ypad = jnp.concatenate([y, jnp.zeros((n - m,) + y.shape[1:], y.dtype)], axis=0)
    return unmlq_array(Side.Left, Op.ConjTrans, f, ypad)


def gels_qr_array(a: Array, b: Array) -> Array:
    return gels_array(a, b, {Option.MethodGels: MethodGels.QR})


def gels_cholqr_array(a: Array, b: Array) -> Array:
    return gels_array(a, b, {Option.MethodGels: MethodGels.CholQR})
