"""Cholesky family: potrf / potrs / posv / potri + band pbtrf / pbtrs / pbsv.

Analogue of reference drivers ``src/{potrf,potrs,posv,potri,pbtrf,pbtrs,
pbsv}.cc`` and ``src/internal/internal_potrf.cc``.

Design inversion: the reference potrf is an OpenMP task DAG — per-k panel
factor of the diagonal tile, column trsm, listBcastMT of the panel, herk
trailing update with lookahead queues (src/potrf.cc:91-196).  The TPU-native
form is a *recursive blocked* factorization: split at a power-of-two
boundary, factor the leading block, one big trsm, one big herk, recurse on
the trailing block.  Same flops (n^3/3), O(log n) distinct subproblem shapes
(static shapes for XLA), and the lookahead/broadcast pipeline is recovered by
XLA's scheduler + GSPMD collectives instead of a runtime.  The nb x nb base
case delegates to XLA's Cholesky op exactly as the reference delegates the
diagonal-tile factor to vendor LAPACK (internal_potrf.cc -> lapack::potrf).
"""

from __future__ import annotations

from ..obs import instrument

import functools
from dataclasses import replace
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..blas3.blas3 import _NB, _split, trsm_array
from ..core.matrix import (
    TriangularBandMatrix,
    BaseMatrix,
    HermitianBandMatrix,
    HermitianMatrix,
    TriangularMatrix,
    band_project,
    symmetrize,
    tri_project,
)
from ..ops.matmul import matmul
from ..ops.tile_ops import row_major
from ..types import Diag, Op, Options, Side, Uplo

ArrayLike = Union[jax.Array, BaseMatrix]


def _potrf_lower(a: jax.Array) -> jax.Array:
    """Recursive lower Cholesky of a full Hermitian array; NaN-poisons on
    non-SPD input (converted to an info code by the driver).  Leaf
    factors and panel solves sit under the ``panel`` phase scope, the
    herk updates under ``bulk``."""
    from ..parallel.comm import phase_scope

    n = a.shape[0]
    if n <= _NB:
        with phase_scope("panel"):
            return jax.lax.linalg.cholesky(a)
    h = _split(n)
    a11, a21, a22 = a[:h, :h], a[h:, :h], a[h:, h:]
    l11 = _potrf_lower(a11)
    with phase_scope("panel"):
        # L21 = A21 * L11^-H  (solve X L11^H = A21)
        l21 = trsm_array(Side.Right, Uplo.Lower, Op.ConjTrans, Diag.NonUnit, 1.0, l11, a21)
    with phase_scope("bulk"):
        # trailing update: A22 - L21 L21^H (herk)
        upd = matmul(l21, jnp.conj(l21).T)
        a22 = a22 - upd.astype(a.dtype)
    l22 = _potrf_lower(a22)
    z = jnp.zeros((h, n - h), a.dtype)
    return jnp.block([[l11, z], [l21, l22]])


def _scan_factor(a: jax.Array, nb: int, nbuckets: int, step) -> jax.Array:
    """The step driver of the scanned single-chip factors (``_potrf_scan``,
    ``lu._getrf_nopiv_scan``): one ``lax.fori_loop`` over nb-wide panels
    with static shapes, O(1) HLO size in n (the recursive trace explodes
    at north-star sizes).  ``a`` is padded with an identity tail to a
    multiple of nb; the k-range is segmented into ``nbuckets``
    statically-shrinking trailing views (cf. parallel.dist_chol), cutting
    the HBM-bound masked trailing traffic to ~0.47x of the full-width form
    at 4 buckets.  ``step(k, view, off, rows)`` factors panel k of the
    bucket's view (``off`` its global head, ``rows`` its row indices) and
    returns the view updated in place; the driver pins the carry
    row-major around it (``row_major``) and puts the bucket boundaries
    under the ``regroup`` phase scope."""
    from ..parallel.comm import phase_scope

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
        with phase_scope("regroup"):
            view = ap[off:, off:]
        rows = jnp.arange(np_ - off)

        def body(k, view, off=off, rows=rows):
            return row_major(step(k, row_major(view), off, rows))

        view = jax.lax.fori_loop(k0, k1, body, view)
        with phase_scope("regroup"):
            ap = ap.at[off:, off:].set(view)
    return ap[:n, :n]


def _potrf_scan(a: jax.Array, nb: int = 256, nbuckets: int = 4) -> jax.Array:
    """Single-program scanned lower Cholesky over ``_scan_factor``'s
    shrinking bucketed views; every flop is an MXU gemm.  Input must be
    full Hermitian.

    Each k-step updates the loop's trailing view in place, and its order
    is what lets it: (1) the panel column leaves the carry as one
    materialized (nv, nb) value (``optimization_barrier``), and the
    diagonal block, the panel solve and ``l21`` are built from it; (2)
    ``view - l21 l21^H`` is the step's only op that reads or writes the
    whole view; (3) the finished column goes back with one
    ``dynamic_update_slice``, last.  Written the other way round (column
    written first, then the update over a view whose column a fusion
    still reads), copy insertion cannot alias the update's output with
    the carry and copies the whole view every step; without the barrier
    a TPU reads the diagonal block through a column-major copy of the
    whole view.  The order changes no finite value: ``l21`` is zero in the
    panel rows and above, so ``l21 l21^H`` is zero in the panel columns
    (a failed pivot still NaN-poisons the diagonal from its block on).  The
    carry is pinned row-major, the update's output layout (``row_major``):
    left to layout assignment, a TPU carries it column-major, as the
    panel column's ops prefer, and converts the whole view to and from
    that every step.  Each such copy reads and writes the whole view, as
    the update does.

    Each step's work sits under the ``panel`` / ``bulk`` phase scopes and
    the bucket boundaries under ``regroup`` (``comm.phase_scope``), so a
    profile of the compiled program names every op's phase."""
    from ..parallel.comm import phase_scope

    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)

    def step(k, view, off, rows):
        nv = rows.shape[0]
        with phase_scope("panel", k):
            kk = k * nb - off  # view-local panel head
            col = jax.lax.optimization_barrier(
                jax.lax.dynamic_slice(view, (0, kk), (nv, nb))
            )
            dblk = jax.lax.dynamic_slice(col, (kk, 0), (nb, nb))
            # panel solve as explicit-inverse gemm (MAGMA-style
            # trtri+gemm): XLA's big-rhs triangular_solve runs at ~1/10
            # the MXU matmul rate at (32768, 256) (measured 46 vs 4
            # ms), and inverting only the nb x nb diag block keeps the
            # backward error at the same O(eps * cond(L_kk)) class.
            ld = jax.lax.linalg.cholesky(dblk)
            eye_nb = jnp.eye(nb, dtype=view.dtype)
            linv = jax.lax.linalg.triangular_solve(
                ld[None], eye_nb[None], left_side=True, lower=True,
                transpose_a=False,
            )[0]
            linv_h = jnp.conj(linv).T if cplx else linv.T
            sol = matmul(col, linv_h).astype(view.dtype)
            below = (rows >= kk + nb)[:, None]
            ondiag = ((rows >= kk) & (rows < kk + nb))[:, None]
            dpat = jax.lax.dynamic_update_slice(
                jnp.zeros((nv, nb), view.dtype), jnp.tril(ld), (kk, 0)
            )
            newcol = jnp.where(below, sol, jnp.where(ondiag, dpat, col))
        with phase_scope("bulk", k):
            l21 = newcol * below.astype(view.dtype)
            upd = matmul(l21, jnp.conj(l21).T if cplx else l21.T)
            view = view - upd.astype(view.dtype)
        with phase_scope("panel", k):
            view = jax.lax.dynamic_update_slice(view, newcol, (0, kk))
        return view

    return _scan_factor(a, nb, nbuckets, step)


def _potrf_and_inv(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(L, L^-1) of a full Hermitian block, jointly, ALL-GEMM.

    The plain recursive factor (_potrf_lower) spends its time in f64
    triangular solves (XLA's emulated trsm crawls: measured 52 GF/s for
    the whole 4096 diag factor while the surrounding Ozaki updates run
    2-3 TF/s-eq).  Computing the inverse ALONGSIDE the factor removes
    every solve: l21 = a21 inv11^H and inv21 = -inv22 l21 inv11 are
    gemms, so the recursion's O(n^3) all rides the matmul dispatch
    (Ozaki above the win gate, tuned f32-pair emulation below), and the
    panel solve gets L^-1 for free — no separate trtri recursion.
    Error class is the explicit-inverse O(eps cond) trade already used by
    the scan panels (ADVICE r3: bounded by the ill-conditioned fixture
    tests)."""
    n = a.shape[0]
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)
    if n <= _NB:
        if a.dtype == jnp.dtype(jnp.float64):
            return _potrf_inv_base_f64(a)
        l = jax.lax.linalg.cholesky(a)
        eye = jnp.eye(n, dtype=a.dtype)
        linv = jax.lax.linalg.triangular_solve(
            l[None], eye[None], left_side=True, lower=True, transpose_a=False
        )[0]
        return l, linv
    h = _split(n)
    l11, i11 = _potrf_and_inv(a[:h, :h])
    l21 = matmul(a[h:, :h], jnp.conj(i11).T if cplx else i11.T).astype(a.dtype)
    upd = matmul(l21, jnp.conj(l21).T if cplx else l21.T)
    l22, i22 = _potrf_and_inv(a[h:, h:] - upd.astype(a.dtype))
    i21 = -matmul(i22, matmul(l21, i11).astype(a.dtype)).astype(a.dtype)
    z = jnp.zeros((h, n - h), a.dtype)
    l = jnp.block([[l11, z], [l21, l22]])
    linv = jnp.block([[i11, z], [i21, i22]])
    return l, linv


def _potrf_inv_base_f64(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """f32-seeded, f64-refined (L, L^-1) of a small f64 block.

    TPU has no native f64 LAPACK ops: lax.linalg.cholesky/triangular_solve
    under the x64 rewriter unroll into ~16k serialized micro-ops per
    256-block (profiled: the leaf chains were 1.8s of the 2.0s n = 16384
    f64 factorization — the MXU gemms around them are ~0.4s).  Here the
    leaf runs the NATIVE f32 cholesky + inverse (fast, few ops), then
    three coupled refinement sweeps in f64 — each a handful of vectorized
    small gemms:

        E = X (A - L L^T) X^T          (backward error in L-coordinates)
        L <- L (I + low(E)),  low = strict lower + half diagonal
        X <- X (2 I - L X)             (Newton resync of the inverse)

    ||E|| starts at ~eps32 * cond(block) and squares per sweep, so three
    sweeps reach the eps64 * cond floor for cond(block) up to ~1e4; a
    residual-gated lax.cond falls back to the exact (slow) f64 path for
    blocks where the seed failed or refinement stalled — correctness never
    depends on the block's conditioning, only speed does."""
    n = a.shape[0]
    dt = a.dtype
    a32 = a.astype(jnp.float32)
    l32 = jax.lax.linalg.cholesky(a32)
    x32 = jax.lax.linalg.triangular_solve(
        l32[None], jnp.eye(n, dtype=jnp.float32)[None], left_side=True, lower=True
    )[0]
    seed_ok = jnp.all(jnp.isfinite(l32))
    l = jnp.tril(jnp.where(jnp.isfinite(l32), l32, 0)).astype(dt)
    x = jnp.tril(jnp.where(jnp.isfinite(x32), x32, 0)).astype(dt)
    eye = jnp.eye(n, dtype=dt)
    half_low = jnp.tril(jnp.ones((n, n), dt), -1) + 0.5 * eye
    for _ in range(3):
        r = a - l @ l.T
        e = x @ r @ x.T
        l = l + l @ (e * half_low)
        x = x @ (2.0 * eye - l @ x)
    resid = jnp.linalg.norm(a - l @ l.T)
    tol = 1e3 * n * jnp.finfo(dt).eps * jnp.linalg.norm(a)
    # gate the INVERSE too (ADVICE r4): X feeds every panel solve via
    # below = panel @ linv.T, and a stalled Newton resync can leave X
    # several digits behind while L alone passes its residual gate
    resid_x = jnp.linalg.norm(eye - l @ x)
    tol_x = 1e3 * n * jnp.finfo(dt).eps * jnp.linalg.norm(x) * jnp.linalg.norm(l)
    good = (
        seed_ok
        & jnp.isfinite(resid)
        & (resid <= tol)
        & jnp.isfinite(resid_x)
        & (resid_x <= tol_x)
    )

    def exact():
        le = jax.lax.linalg.cholesky(a)
        xe = jax.lax.linalg.triangular_solve(
            le[None], eye[None], left_side=True, lower=True
        )[0]
        return le, xe

    return jax.lax.cond(good, lambda: (jnp.tril(l), jnp.tril(x)), exact)


def _potrf_left_looking(a: jax.Array, nb: Optional[int] = None) -> jax.Array:
    """Left-looking blocked lower Cholesky with STATIC per-panel shapes.

    Built for f64 on TPU (VERDICT r4 item 1): every O(n^3) flop lands in a
    large-k gemm — panel update ``A[k:,k] -= L[k:,:k] L[k,:k]^H`` has
    k = j*nb contraction and an nb-wide output, exactly the shapes where
    the int8-MXU Ozaki dispatch (ops/matmul.py gate) wins — while the
    right-looking forms spend the same flops at rank-nb thin-k shapes
    where f64 pays ~5x.  The Python panel loop unrolls n/nb static
    steps (no masking waste, exact n^3/3 flops); only the nb x nb
    diagonal factor recurses.  Same math as the reference's potrf task
    graph read column-wise (src/potrf.cc:91-196)."""
    n = a.shape[0]
    if nb is None:
        # measured on v5e (round 4, n=16384 f64): nb=4096 -> 724 GF/s,
        # nb=2048 -> 569; the bigger panel amortizes the recursive diag
        # factor against far larger Ozaki updates
        nb = 4096 if n >= 16384 else 2048
    if n <= nb:
        return _potrf_lower(a)
    nsteps = -(-n // nb)
    ap, _ = _potrf_ll_pad(a, nsteps, nb)
    for j in range(nsteps):
        ap = _potrf_ll_panel_step(ap, j * nb, nb)
    return tri_project(ap[:n, :n], Uplo.Lower)


def _potrf_ll_pad(a: jax.Array, nsteps: int, nb: int):
    """Shared left-looking prelude: pad to a panel multiple with a unit
    diagonal in the pad block (exact: diag(A, I) factors to diag(L, I)).
    Returns (padded matrix, fresh_buffer) — fresh_buffer False means the
    result IS the caller's array."""
    n = a.shape[0]
    np_ = nsteps * nb
    if np_ == n:
        return a, False
    ap = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
    dpad = jnp.arange(n, np_)
    return ap.at[dpad, dpad].set(1), True


def _potrf_ll_panel_step(ap: jax.Array, r0: int, nb: int) -> jax.Array:
    """One left-looking panel step on the padded in-place matrix: subtract
    the factored history's contribution (a large-k gemm), factor the
    diagonal block jointly with its inverse, solve the below-panel rows
    as a gemm, write back."""
    cplx = jnp.issubdtype(ap.dtype, jnp.complexfloating)
    panel = ap[r0:, r0 : r0 + nb]
    if r0:
        left = ap[r0:, :r0]  # factored L[r0:, :r0]
        lrow = left[:nb]  # rows r0..r0+nb of L's first r0 columns
        upd = matmul(left, jnp.conj(lrow).T if cplx else lrow.T)
        panel = panel - upd.astype(ap.dtype)
    dblk, linv = _potrf_and_inv(panel[:nb])
    if panel.shape[0] > nb:
        below = matmul(panel[nb:], jnp.conj(linv).T if cplx else linv.T)
        panel = jnp.concatenate([dblk, below.astype(ap.dtype)], axis=0)
    else:
        panel = dblk
    return jax.lax.dynamic_update_slice(ap, panel, (r0, r0))


@functools.partial(jax.jit, static_argnames=("r0", "nb"), donate_argnums=0)
def _potrf_ll_step_jit(ap, r0: int, nb: int):
    return _potrf_ll_panel_step(ap, r0, nb)


@functools.partial(jax.jit, static_argnames=("n",), donate_argnums=0)
def _potrf_ll_finale_jit(ap, n: int):
    # donated: an EAGER tri_project here would allocate a second full
    # matrix next to ap, breaking the staged form's one-matrix peak
    return tri_project(ap[:n, :n], Uplo.Lower)


@functools.partial(jax.jit, static_argnames=("n",))
def _potrf_ll_finale_pad_jit(ap, n: int):
    # padded runs: the (n, n) output cannot alias the larger padded buffer,
    # so donating ap would only trip XLA's unusable-donation warning; the
    # output here is strictly smaller than ap, keeping peak < 2 matrices
    return tri_project(ap[:n, :n], Uplo.Lower)


def potrf_left_looking_staged(
    a: jax.Array, nb: Optional[int] = None, donate: bool = False
) -> jax.Array:
    """Left-looking f64 Cholesky with ONE DONATED XLA PROGRAM PER PANEL.

    The fused single-program form keeps ~7 live copies of the matrix
    (XLA's buffer assignment across the unrolled panel chain: measured
    14.4 GB peak for the 2 GB n = 16384 problem — the calibration point
    of ``obs.memmodel.FUSED_LL_COPIES``), which OOMs v5e at n = 32768
    (8 GB matrix).  Dispatching each panel as its own jit with the
    matrix donated caps peak HBM at one matrix + one panel's transients
    (``memmodel.potrf_staged_peak``).  Call EAGERLY (under an outer jit
    the stages inline and the fused-liveness problem returns) — cf.
    eig.heev_staged.

    ``donate=True`` CONSUMES the caller's array (required at n = 32768 on
    v5e: a defensive copy next to the 8 GB input would itself OOM; the
    caller must not reuse ``a``).  The default keeps the input intact by
    copying when the padding step would not already produce a fresh
    buffer."""
    n = a.shape[0]
    if nb is None:
        nb = 4096 if n >= 16384 else 2048
    if n <= nb:
        return _potrf_lower(a)
    nsteps = -(-n // nb)
    ap, fresh = _potrf_ll_pad(a, nsteps, nb)
    if not fresh and not donate:
        ap = jnp.array(ap, copy=True)  # first step's donation eats a copy
    for j in range(nsteps):
        ap = _potrf_ll_step_jit(ap, r0=j * nb, nb=nb)
    if ap.shape[0] == n:  # donation aliasable only when shapes match
        return _potrf_ll_finale_jit(ap, n=n)
    return _potrf_ll_finale_pad_jit(ap, n=n)


def _potrf_ll_ozaki(a: jax.Array, nb: Optional[int] = None, n_slices: Optional[int] = None) -> jax.Array:
    """Left-looking f64 lower Cholesky with a persistent Ozaki digit cache.

    The plain left-looking form (above) re-splits the factored history into
    int8 digit planes inside every panel-update GEMM (ops/ozaki.py splits
    per call).  Cholesky admits an exact a-priori row bound that makes the
    splits cacheable: sum_j L[i,j]^2 = A[i,i], so |L[i,j]| <= sqrt(A[i,i])
    for every j — fixing each row's digit grid at 2^e[i] > sqrt(A[i,i])
    BEFORE factoring means every panel's planes share the row scaling and
    concatenate exactly along the contraction axis.  Each factored panel is
    split ONCE into a (S, n, n) int8 cache; each panel update is then ONE
    plane-level GEMM (ops/ozaki.matmul_planes) over the full history with a
    single epilogue — no per-use splits, no per-panel partial sums.

    The bound is looser than the true row max by at most sqrt(row length)
    (mass-spread worst case), i.e. <= 7 lost top bits at n = 16384.  S = 9
    matches the split-per-call path's measured accuracy on well- AND
    ill-conditioned fixtures (the residual floor is the explicit-inverse
    panel solve, not the digit tail; test_chol.py gates both); above
    n = 8192 the default is S = 10 (+22% MXU work), which covers even the
    mass-spread worst case where the bound's slack exceeds one 6-bit plane.
    Peak HBM is modeled by ``obs.memmodel.potrf_ozaki_cache_peak``: the
    S n^2 int8 plane cache next to ~4 full f64 buffers — the dispatch in
    potrf_array gates this path to sizes where
    ``memmodel.potrf_f64_form`` says cache + matrix fit the HBM budget
    and falls back to the split-per-call form above.

    Same math as the reference potrf task graph read column-wise
    (src/potrf.cc:91-196); the digit cache is the TPU-native analogue of
    keeping the factored panels resident on-device for the trailing herk.
    """
    from ..ops.ozaki import _row_exp, matmul_planes, split_rows

    n = a.shape[0]
    if n_slices is None:
        # ADVICE r4: the sqrt(diag) row bound can be loose by up to
        # log2(sqrt(n)) bits in the mass-spread worst case — more than one
        # 6-bit plane past n ~ 8192 — so S = 10 there (+22% MXU work)
        # keeps the worst case inside the digit tail; S = 9 matches the
        # split-per-call accuracy below that
        n_slices = 10 if n > 8192 else 9
    if nb is None:
        nb = 4096 if n >= 16384 else 2048
    if n <= nb:
        return _potrf_lower(a)
    nsteps = -(-n // nb)
    np_ = nsteps * nb
    if np_ != n:
        ap = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
        dpad = jnp.arange(n, np_)
        ap = ap.at[dpad, dpad].set(1)
    else:
        ap = a
    # fixed per-row digit grid from the exact row bound sqrt(diag)
    e = _row_exp(jnp.sqrt(jnp.maximum(jnp.real(jnp.diagonal(ap)), 0)).astype(jnp.float32))[:, None]
    q = jnp.zeros((n_slices, np_, np_), jnp.int8)
    for j in range(nsteps):
        r0 = j * nb
        panel = ap[r0:, r0 : r0 + nb]
        if j:
            upd = matmul_planes(q[:, r0:, :r0], e[r0:], q[:, r0 : r0 + nb, :r0], e[r0 : r0 + nb])
            panel = panel - upd
        dblk, linv = _potrf_and_inv(panel[:nb])
        dblk = jnp.tril(dblk)
        if panel.shape[0] > nb:
            below = matmul(panel[nb:], linv.T)
            cpanel = jnp.concatenate([dblk, below.astype(ap.dtype)], axis=0)
        else:
            cpanel = dblk
        if j + 1 < nsteps:  # the last panel is never read back
            qc, _ = split_rows(cpanel, n_slices, e[r0:])
            q = jax.lax.dynamic_update_slice(q, qc, (0, r0, r0))
        ap = jax.lax.dynamic_update_slice(ap, cpanel, (r0, r0))
    return tri_project(ap[:n, :n], Uplo.Lower)


_POTRF_SCAN_MIN_N = 16384  # above this the recursive trace is too large
_POTRF_LL_MIN_N = 4096  # f64/c128: left-looking beats recursion from here


def _is_f64(dtype) -> bool:
    return dtype in (jnp.dtype(jnp.float64), jnp.dtype(jnp.complex128))


def _potrf_f64_form(n: int, concrete: bool, ozaki_dispatch: bool,
                    itemsize: int = 8) -> str:
    """ozaki | staged | fused for one big-f64/c128 factorization, by
    MODELED peak HBM against the live budget — the hand-computed
    digit-cache / staged ceilings this module used to hard-code.  The
    routing rules and their on-chip calibration points are documented at
    the single source, ``obs.memmodel.potrf_f64_form``."""
    from ..obs import memmodel

    return memmodel.potrf_f64_form(n, concrete, ozaki_dispatch,
                                   itemsize=itemsize)


def _pivot_info(l: jax.Array) -> jax.Array:
    """info of a factor read off its diagonal: 0, else 1 + the index of
    the first pivot that is not finite and positive (every factor form
    NaN-poisons the diagonal from a failed diagonal block on)."""
    d = jnp.real(jnp.diagonal(l))
    bad = ~(jnp.isfinite(d) & (d > 0))
    return jnp.where(jnp.any(bad), jnp.argmax(bad) + 1, 0).astype(jnp.int32)


@instrument("potrf_array")
def potrf_array(a: jax.Array, uplo: Uplo = Uplo.Lower) -> Tuple[jax.Array, jax.Array]:
    """Factor A = L L^H (or U^H U). ``a`` holds the uplo triangle (other
    triangle ignored). Returns (factor triangle, info); info = 0 on success
    else 1 + index of first non-positive pivot (src/potrf.cc:253-256)."""
    full = symmetrize(a, uplo, conj=jnp.issubdtype(a.dtype, jnp.complexfloating))
    if _is_f64(a.dtype) and a.shape[0] >= _POTRF_LL_MIN_N:
        # f64 rides the left-looking form: large-k updates hit the Ozaki
        # dispatch win region (measured 235 vs 211 GF/s at n=8192, 569
        # GF/s at 16384 vs 82 for the right-looking scan, v5e round 4).
        # Which left-looking variant is a MEMORY decision, made by the
        # analytic model against the HBM budget (_potrf_f64_form).
        from ..ops.matmul import _F64_DISPATCH, _tpu_is_default

        ozaki_ok = (
            a.dtype == jnp.dtype(jnp.float64)
            and _F64_DISPATCH["ozaki"]
            and _tpu_is_default()
        )
        form = _potrf_f64_form(
            a.shape[0], not isinstance(full, jax.core.Tracer), ozaki_ok,
            itemsize=jnp.dtype(a.dtype).itemsize,  # c128 peaks 2x f64
        )
        if form == "ozaki":
            l = _potrf_ll_ozaki(full)
        elif form == "staged":
            # ``full`` is the symmetrize intermediate owned here, so
            # donating it never touches the caller's array.
            l = potrf_left_looking_staged(full, donate=True)
        else:
            l = _potrf_left_looking(full)
    elif a.shape[0] > _POTRF_SCAN_MIN_N:
        l = _potrf_scan(full)
    else:
        l = _potrf_lower(full)
    info = _pivot_info(l)
    l = tri_project(l, Uplo.Lower)
    if uplo == Uplo.Upper:
        return jnp.conj(l).T, info
    return l, info


def potrf(a: ArrayLike, opts: Optional[Options] = None):
    """slate::potrf driver (src/potrf.cc:261)."""
    if isinstance(a, BaseMatrix):
        f, info = potrf_array(a.data, a.uplo)
        return TriangularMatrix(data=f, uplo=a.uplo), info
    f, info = potrf_array(jnp.asarray(a), Uplo.Lower)
    return TriangularMatrix(data=f, uplo=Uplo.Lower), info


def potrs_array(l: jax.Array, b: jax.Array, uplo: Uplo = Uplo.Lower) -> jax.Array:
    """Solve A X = B given the Cholesky factor (src/potrs.cc)."""
    if uplo == Uplo.Lower:
        y = trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.NonUnit, 1.0, l, b)
        return trsm_array(Side.Left, Uplo.Lower, Op.ConjTrans, Diag.NonUnit, 1.0, l, y)
    y = trsm_array(Side.Left, Uplo.Upper, Op.ConjTrans, Diag.NonUnit, 1.0, l, b)
    return trsm_array(Side.Left, Uplo.Upper, Op.NoTrans, Diag.NonUnit, 1.0, l, y)


def potrs(factor: TriangularMatrix, b: ArrayLike):
    out = potrs_array(factor.data, b.array if isinstance(b, BaseMatrix) else jnp.asarray(b), factor.uplo)
    if isinstance(b, BaseMatrix):
        return replace(b, data=out)
    return out


@instrument("posv_array")
def posv_array(a: jax.Array, b: jax.Array, uplo: Uplo = Uplo.Lower):
    """Factor + solve (src/posv.cc). Returns (x, factor, info).  The two
    halves sit under the ``potrf`` / ``potrs`` stage scopes."""
    with jax.named_scope("potrf"):
        f, info = potrf_array(a, uplo)
    with jax.named_scope("potrs"):
        x = potrs_array(f, b, uplo)
    return x, f, info


def posv(a: ArrayLike, b: ArrayLike, opts: Optional[Options] = None):
    uplo = a.uplo if isinstance(a, BaseMatrix) else Uplo.Lower
    ad = a.data if isinstance(a, BaseMatrix) else jnp.asarray(a)
    bd = b.array if isinstance(b, BaseMatrix) else jnp.asarray(b)
    x, f, info = posv_array(ad, bd, uplo)
    if isinstance(b, BaseMatrix):
        x = replace(b, data=x)
    return x, TriangularMatrix(data=f, uplo=uplo), info


def potri_array(l: jax.Array, uplo: Uplo = Uplo.Lower) -> jax.Array:
    """A^-1 from the Cholesky factor (src/potri.cc): trtri then trtrm
    (lauum-style triangle product)."""
    from .tri import trtri_array, trtrm_array

    linv = trtri_array(l, uplo, Diag.NonUnit)
    if uplo == Uplo.Lower:
        # A^-1 = L^-H L^-1: lower-stored result
        return trtrm_array(linv, Uplo.Lower)
    return trtrm_array(linv, Uplo.Upper)


def potri(factor: TriangularMatrix):
    inv = potri_array(factor.data, factor.uplo)
    return HermitianMatrix(data=inv, uplo=factor.uplo)


# ---------------------------------------------------------------------------
# Band Cholesky (src/pbtrf.cc, pbtrs.cc, pbsv.cc)
# ---------------------------------------------------------------------------


def _band_worthwhile(n: int, band: int) -> bool:
    from .band import band_worthwhile

    return band_worthwhile(n, band)


def pbtrf_array(a: jax.Array, kd: int, uplo: Uplo = Uplo.Lower) -> Tuple[jax.Array, jax.Array]:
    """Band Cholesky (src/pbtrf.cc).  Narrow bands take the windowed
    O(n kd^2) path (linalg.band.pbtrf_band); wide bands ride the dense
    recursive MXU factorization + band projection (exact either way)."""
    kl, ku = (kd, 0) if uplo == Uplo.Lower else (0, kd)
    if uplo == Uplo.Lower and _band_worthwhile(a.shape[0], kd):
        from .band import pbtrf_band

        f = pbtrf_band(a, kd)
        return f.l, f.info
    f, info = potrf_array(band_project(a, kl, ku), uplo)
    return band_project(f, kl, ku), info


def pbtrs_array(f: jax.Array, b: jax.Array, kd: int, uplo: Uplo = Uplo.Lower) -> jax.Array:
    if uplo == Uplo.Lower and _band_worthwhile(f.shape[0], kd):
        from .band import BandChol, pbtrs_band, _pick_nb

        fb = BandChol(f, kd, _pick_nb(kd), jnp.zeros((), jnp.int32))
        return pbtrs_band(fb, b)
    return potrs_array(f, b, uplo)


def pbsv_array(a: jax.Array, b: jax.Array, kd: int, uplo: Uplo = Uplo.Lower):
    f, info = pbtrf_array(a, kd, uplo)
    return pbtrs_array(f, b, kd, uplo), f, info


def pbsv(a: HermitianBandMatrix, b: ArrayLike, opts: Optional[Options] = None):
    bd = b.array if isinstance(b, BaseMatrix) else jnp.asarray(b)
    x, f, info = pbsv_array(a.data, bd, a.kd, a.uplo)
    if isinstance(b, BaseMatrix):
        x = replace(b, data=x)
    return x, TriangularBandMatrix.from_array(f, a.uplo, a.kd), info
