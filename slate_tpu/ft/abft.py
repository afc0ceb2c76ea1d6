"""Checksum-carrying distributed kernels + the verify/locate/repair drivers.

Three ABFT variants of the core mesh kernels, each running the SAME
communication schedule as its plain sibling — the checksum tiles are
ordinary tiles of the block-cyclic grid, so they ride the existing
``comm.prefetch_bcast`` (SUMMA) / ``comm.pipelined_factor_loop``
(potrf / LU-nopiv) pipelines and every panel broadcast simply carries
one extra augmented tile row/column:

- ``_ft_summa_jit``: stationary-C SUMMA over row-augmented A and
  column-augmented B (+ an augmented C accumulator), so the product
  arrives with its own row and column checksums attached.
- ``_ft_potrf_jit``: the right-looking mesh Cholesky k-loop on a matrix
  with two checksum tile rows appended below — forward-substituted by
  the panel solves into the checksums of L (Du et al., PPoPP 2012).
  Unbucketed: FT mode trades the bucketing flop cut for a single
  full-view loop (the trailing-view re-slicing would strand the
  checksum rows; the masked-update overhead is the documented cost).
- ``_ft_lu_jit``: the LU-nopiv k-loop on a doubly-augmented matrix
  (checksum rows verify L, checksum columns verify U), reusing
  ``dist_lu._nopiv_panel/_narrow/_bulk`` directly.

Each kernel takes a replicated fault spec (see ``inject``) and applies
pure-JAX perturbations at the panel / bcast / trailing hook points, so
deterministic fault injection works under jit at any lookahead depth:
the trailing hook is keyed to the PAYLOAD's step, firing in whichever
narrow/bulk split the deferred update lands in.

The host drivers verify the carried checksums against recomputed tile
sums, locate damage via the ramp/unit discrepancy ratio, apply the exact
algebraic repair where the corruption could not have propagated (GEMM
output tiles, finalized factor panels), and escalate per ``FtPolicy``:
one full recompute for live-data corruption, ``FtError`` when that still
verifies dirty (multi-tile / persistent faults).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs import instrument
from ..parallel.comm import (
    PRECISE,
    all_gather_a,
    bcast_diag_tile,
    bcast_from_col,
    bcast_from_row,
    bcast_impl_scope,
    la_depth,
    local_indices,
    pipelined_factor_loop,
    prefetch_bcast,
    resolve_bcast_impl,
    shard_map_compat,
)
from ..parallel.dist import DistMatrix, from_dense, padded_tiles, to_dense
from ..parallel.dist_chol import _chol_panel_factor_solve
from ..parallel.dist_lu import _nopiv_bulk, _nopiv_narrow, _nopiv_panel
from ..parallel.mesh import COL_AXIS, ROW_AXIS, mesh_shape
from ..types import Options
from . import checksum as cks
from . import inject
from .inject import MAX_FAULTS, PH_BCAST, PH_PANEL, PH_TRAIL
from .policy import FtError, FtPolicy, FtReport, count, resolve_policy

CSR = 2  # checksum tile rows/cols appended per protected side


# ---------------------------------------------------------------------------
# pure-JAX fault application (shared by all three kernels)
# ---------------------------------------------------------------------------


def _slots(fi, fv):
    """Unpack the (MAX_FAULTS, 8) int spec + (MAX_FAULTS,) values into
    per-slot traced scalars: (active, k, phase, ti, tj, r, c, mode, val)."""
    return [
        tuple(fi[s, i] for i in range(8)) + (fv[s],)
        for s in range(MAX_FAULTS)
    ]


def _corrupt(x, mode, value):
    """Perturb every tile of ``x`` (..., nb, nb) per the fault mode:
    1 = zero the tile, 2 = scale it, 3 = bitflip-style add to element
    (0, 0).  The caller's mask selects which tile actually changes."""
    v = value.astype(x.dtype)
    delta = jnp.zeros(x.shape[-2:], x.dtype).at[0, 0].set(v)
    return jnp.where(
        mode == 1, jnp.zeros_like(x), jnp.where(mode == 2, x * v, x + delta)
    )


def _hit4(x, hit, li, lj, mode, value):
    """Apply one fault to local tile slot (li, lj) of a (I, J, nb, nb)
    stack when the traced predicate ``hit`` holds."""
    mask = (
        hit
        & (jnp.arange(x.shape[0]) == li)[:, None]
        & (jnp.arange(x.shape[1]) == lj)[None, :]
    )[:, :, None, None]
    return jnp.where(mask, _corrupt(x, mode, value), x)


def _hit3(x, hit, li, mode, value):
    """Same for a (L, nb, nb) panel stack at slot ``li``."""
    mask = (hit & (jnp.arange(x.shape[0]) == li))[:, None, None]
    return jnp.where(mask, _corrupt(x, mode, value), x)


# ---------------------------------------------------------------------------
# checksum-carrying SUMMA (stationary-C; summa._summa_jit + fault hooks)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _ft_summa_jit(at, bt, ct, alpha, beta, mesh, p, q, kt, la, bi, fi, fv):
    """Checksum-carrying SUMMA over the augmented grid (checksum tile
    rows and columns ride the product like data tiles; the host verify
    on the dense output detects, locates and repairs)."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(a_loc, b_loc, fi, fv):
        mtl, _, nb, _ = a_loc.shape
        ntl = b_loc.shape[1]
        dtype = a_loc.dtype
        r, c, _, _ = local_indices(p, q, mtl, ntl)
        slots = _slots(fi, fv)

        def fetch(k):
            acol_own = lax.dynamic_slice_in_dim(a_loc, k // q, 1, axis=1)[:, 0]
            acol = bcast_from_col(acol_own, k % q)
            brow_own = lax.dynamic_slice_in_dim(b_loc, k // p, 1, axis=0)[0]
            brow = bcast_from_row(brow_own, k % p)
            # bcast-phase fault: one device's RECEIVED copy of the A
            # column panel rots before its MXU update consumes it
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_BCAST) & (k == fk)
                    & (r == fr) & (c == fc)
                )
                acol = _hit3(acol, hit & (r == fti % p), fti // p, fmode, val)
            return acol, brow

        def trail_hits(k, acc):
            # trailing-phase fault: one accumulator tile rots right after
            # step k's update lands (final data for GEMM — correctable)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_TRAIL) & (k == fk)
                    & (r == fti % p) & (c == ftj % q)
                )
                acc = _hit4(acc, hit, fti // p, ftj // q, fmode, val)
            return acc

        def consume(k, panels, acc):
            acol, brow = panels
            acc = acc + jnp.einsum(
                "iab,jbc->ijac", acol, brow, precision=PRECISE
            ).astype(dtype)
            return trail_hits(k, acc)

        acc0 = jnp.zeros((mtl, ntl, nb, nb), dtype)
        return prefetch_bcast(kt, la, fetch, consume, acc0)

    with bcast_impl_scope(bi):
        prod = shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec, spec, P(), P()),
            out_specs=spec,
            check_vma=False,
        )(at, bt, fi, fv)
    return (alpha * prod + beta * ct).astype(at.dtype)


# ---------------------------------------------------------------------------
# checksum-carrying mesh Cholesky (dist_chol phases, unbucketed full view)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _ft_potrf_jit(at, mesh, p, q, nt, la, bi, fi, fv):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, fi, fv):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype
        cplx = jnp.issubdtype(dtype, jnp.complexfloating)
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
        lower = (i_log[:, None] >= j_log[None, :])[:, :, None, None]
        slots = _slots(fi, fv)

        def trail_hits(view, kprev, refreshed_kc, in_refresh):
            """Apply trailing-phase faults belonging to step ``kprev``,
            restricted to (or excluding) the narrow-refreshed column so
            every lookahead depth corrupts the tile exactly once, right
            after that step's update lands on it."""
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_TRAIL) & (kprev == fk)
                    & (r == fti % p) & (c == ftj % q)
                )
                if refreshed_kc is not None:
                    in_col = (ftj // q) == refreshed_kc
                    hit = hit & (in_col if in_refresh else ~in_col)
                view = _hit4(view, hit, fti // p, ftj // q, fmode, val)
            return view

        def panel(k, view):
            kc = k // q
            dtile = bcast_diag_tile(view, k, p, q, nb)
            pcol = lax.dynamic_slice_in_dim(view, kc, 1, axis=1)[:, 0]
            # factor + panel solve — the checksum rows ride the solved
            # stack like any other tile
            lkk, solved = _chol_panel_factor_solve(dtile, pcol, cplx)
            below = (i_log > k)[:, None, None]
            on_diag = (i_log == k)[:, None, None]
            newcol = jnp.where(below, solved, jnp.where(on_diag, lkk, pcol))
            mine = (c == k % q)
            view = lax.dynamic_update_slice_in_dim(
                view, jnp.where(mine, newcol, pcol)[:, None], kc, axis=1
            )
            pan = bcast_from_col(jnp.where(below & mine, newcol, 0), k % q)
            # panel-phase fault: the owner's STORED finalized panel tile
            # rots AFTER the broadcast was issued — consumers saw clean
            # data, so the damage stays in one output tile
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_PANEL) & (k == fk)
                    & (r == fti % p) & (c == ftj % q)
                )
                view = _hit4(view, hit, fti // p, ftj // q, fmode, val)
            # bcast-phase fault: one device's received panel copy
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_BCAST) & (k == fk)
                    & (r == fr) & (c == fc)
                )
                pan = _hit3(pan, hit & (r == fti % p), fti // p, fmode, val)
            allpan = all_gather_a(pan, ROW_AXIS, axis=0)
            panT = allpan[j_log % p, j_log // p]
            return view, (pan, panT, jnp.asarray(k, jnp.int32))

        def narrow(k, view, payload):
            pan_p, panT_p, kprev = payload
            kc = k // q
            pT = lax.dynamic_slice_in_dim(panT_p, kc, 1, axis=0)
            upd = jnp.einsum(
                "iab,jcb->ijac", pan_p, jnp.conj(pT) if cplx else pT,
                precision=PRECISE,
            ).astype(dtype)
            lcol = lax.dynamic_slice_in_dim(lower, kc, 1, axis=1)
            colv = lax.dynamic_slice_in_dim(view, kc, 1, axis=1)
            view = lax.dynamic_update_slice_in_dim(
                view, colv - jnp.where(lcol, upd, 0), kc, axis=1
            )
            return trail_hits(view, kprev, kc, in_refresh=True)

        def bulk(k, view, payload):
            pan_p, panT_p, kprev = payload
            upd = jnp.einsum(
                "iab,jcb->ijac", pan_p,
                jnp.conj(panT_p) if cplx else panT_p,
                precision=PRECISE,
            ).astype(dtype)
            mask = lower
            kc = None
            if k is not None:
                kc = k // q
                mask = mask & (jnp.arange(ntl) != kc)[None, :, None, None]
            view = view - jnp.where(mask, upd, 0)
            return trail_hits(view, kprev, kc, in_refresh=False)

        zero_pl = (
            jnp.zeros((mtl, nb, nb), dtype),
            jnp.zeros((ntl, nb, nb), dtype),
            jnp.asarray(-1, jnp.int32),
        )
        t_loc = pipelined_factor_loop(0, nt, la, panel, narrow, bulk, t_loc, zero_pl)

        # info over the DATA diagonal only (aug/checksum rows never hold
        # pivots); granularity caveat as in dist_chol._potrf_jit
        diag_tiles = (
            (i_log[:, None] == j_log[None, :]) & (i_log[:, None] < nt)
        )[:, :, None]
        dvals = jnp.einsum("ijaa->ija", jnp.real(t_loc))
        bad = (~jnp.isfinite(dvals) | (dvals <= 0)) & diag_tiles
        gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :] + 1
        big = nt * nb + 1
        # int32 before the reduction: TPU lowers 64-bit all-reduces for sum only
        local_info = jnp.min(jnp.where(bad, gidx, big)).astype(jnp.int32)
        info = lax.pmin(lax.pmin(local_info, ROW_AXIS), COL_AXIS)
        info = jnp.where(info >= big, 0, info).astype(jnp.int32)
        return t_loc, info[None, None]

    with bcast_impl_scope(bi):
        lt, info = shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec, P(), P()),
            out_specs=(spec, P(ROW_AXIS, COL_AXIS)),
            check_vma=False,
        )(at, fi, fv)
    return lt, jnp.max(info)


# ---------------------------------------------------------------------------
# checksum-carrying mesh LU-nopiv (reuses dist_lu's panel/narrow/bulk)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _ft_lu_jit(at, mesh, p, q, nt, la, bi, fi, fv):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, fi, fv):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
        slots = _slots(fi, fv)

        def trail_hits(view, kprev, kr, kc, in_refresh):
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_TRAIL) & (kprev == fk)
                    & (r == fti % p) & (c == ftj % q)
                )
                if kr is not None:
                    in_ref = ((ftj // q) == kc) | ((fti // p) == kr)
                    hit = hit & (in_ref if in_refresh else ~in_ref)
                view = _hit4(view, hit, fti // p, ftj // q, fmode, val)
            return view

        def panel(k, view):
            view, (pan, urow) = _nopiv_panel(view, k, p, q, i_log, j_log, r, c)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_PANEL) & (k == fk)
                    & (r == fti % p) & (c == ftj % q)
                )
                view = _hit4(view, hit, fti // p, ftj // q, fmode, val)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_BCAST) & (k == fk)
                    & (r == fr) & (c == fc)
                )
                pan = _hit3(pan, hit & (r == fti % p), fti // p, fmode, val)
            return view, (pan, urow, jnp.asarray(k, jnp.int32))

        def narrow(k, view, payload):
            pan_p, urow_p, kprev = payload
            view = _nopiv_narrow(view, (pan_p, urow_p), k, p, q)
            return trail_hits(view, kprev, k // p, k // q, in_refresh=True)

        def bulk(k, view, payload):
            pan_p, urow_p, kprev = payload
            if k is None:
                view = _nopiv_bulk(view, (pan_p, urow_p))
                return trail_hits(view, kprev, None, None, in_refresh=False)
            view = _nopiv_bulk(view, (pan_p, urow_p), k // p, k // q)
            return trail_hits(view, kprev, k // p, k // q, in_refresh=False)

        zero_pl = (
            jnp.zeros((mtl, nb, nb), dtype),
            jnp.zeros((ntl, nb, nb), dtype),
            jnp.asarray(-1, jnp.int32),
        )
        t_loc = pipelined_factor_loop(0, nt, la, panel, narrow, bulk, t_loc, zero_pl)

        # info: first zero/non-finite U diagonal, data region only
        diag_tiles = (
            (i_log[:, None] == j_log[None, :]) & (i_log[:, None] < nt)
        )[:, :, None]
        dvals = jnp.einsum("ijaa->ija", t_loc)
        bad = (~jnp.isfinite(jnp.abs(dvals)) | (dvals == 0)) & diag_tiles
        gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :] + 1
        big = nt * nb + 1
        # int32 before the reduction: TPU lowers 64-bit all-reduces for sum only
        local_info = jnp.min(jnp.where(bad, gidx, big)).astype(jnp.int32)
        info = lax.pmin(lax.pmin(local_info, ROW_AXIS), COL_AXIS)
        info = jnp.where(info >= big, 0, info).astype(jnp.int32)
        return t_loc, info[None, None]

    with bcast_impl_scope(bi):
        lut, info = shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec, P(), P()),
            out_specs=(spec, P(ROW_AXIS, COL_AXIS)),
            check_vma=False,
        )(at, fi, fv)
    return lut, jnp.max(info)


# ---------------------------------------------------------------------------
# checksum-carrying distributed triangular solve (ISSUE 12 satellite: the
# ROADMAP's first long-tail ABFT op).  The solution-checksum invariant
# rides the RHS: appending the weighted column sums of B as extra RHS
# tile columns makes the solve produce X augmented with its own column
# checksums — op(A) X_ck = B_ck and X_ck = X W by linearity — on the
# UNCHANGED broadcast schedule (the A-panel and solved-row broadcasts of
# dist_trsm._trsm_jit simply carry CSR more tiles).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _ft_trsm_jit(at, bt, mesh, p, q, nt, uplo_lower, trans, unit, la, bi,
                 fi, fv):
    """The dist_trsm TrsmB left-solve schedule (prefetch_bcast over A's
    read-only per-step panels) with the pure-JAX fault hooks: ``bcast``
    corrupts one device's received A-panel copy, ``trailing`` one stored
    B/X tile right after step k's update lands.  ``trans`` covers
    op(A) = A^T (real); conjugation is out of scope for the f64 serving
    path this protects."""
    spec = P(ROW_AXIS, COL_AXIS)
    eff_lower = bool(uplo_lower) != bool(trans)
    forward = eff_lower

    def kernel(a_loc, b_loc, fi, fv):
        mtl, ntl, nb, _ = a_loc.shape
        r, c, i_log, _ = local_indices(p, q, mtl, ntl)
        slots = _slots(fi, fv)

        def opt(t):
            return jnp.swapaxes(t, -1, -2)

        def fetch(s):
            k = s if forward else nt - 1 - s
            kr, kc = k // p, k // q
            dtile = bcast_diag_tile(a_loc, k, p, q, nb)
            if trans:
                dtile = opt(dtile)
            remaining = (i_log > k) if forward else (i_log < k)
            if not trans:
                acol = lax.dynamic_slice_in_dim(a_loc, kc, 1, axis=1)[:, 0]
                mine_c = (c == k % q)
                pan = bcast_from_col(
                    jnp.where(remaining[:, None, None] & mine_c, acol, 0),
                    k % q,
                )
            else:
                arow = lax.dynamic_slice_in_dim(a_loc, kr, 1, axis=0)[0]
                mine_r2 = (r == k % p)
                arow = bcast_from_row(jnp.where(mine_r2, arow, 0), k % p)
                allrow = all_gather_a(arow, COL_AXIS, axis=0)
                pan = opt(allrow[i_log % q, i_log // q])
                pan = jnp.where(remaining[:, None, None], pan, 0)
            # bcast-phase fault: one device's RECEIVED panel copy rots
            # before its update consumes it (propagates; recompute class)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_BCAST) & (k == fk)
                    & (r == fr) & (c == fc)
                )
                pan = _hit3(pan, hit & (r == fti % p), fti // p, fmode, val)
            return dtile, pan

        def consume(s, panels, b_loc):
            k = s if forward else nt - 1 - s
            kr = k // p
            dtile, pan = panels
            brow = lax.dynamic_slice_in_dim(b_loc, kr, 1, axis=0)[0]
            xrow = lax.linalg.triangular_solve(
                jnp.broadcast_to(dtile, brow.shape), brow,
                left_side=True, lower=eff_lower, transpose_a=False,
                unit_diagonal=bool(unit),
            )
            mine_r = (r == k % p)
            b_loc = lax.dynamic_update_slice_in_dim(
                b_loc, jnp.where(mine_r, xrow, brow)[None], kr, axis=0
            )
            xrow = bcast_from_row(jnp.where(mine_r, xrow, 0), k % p)
            upd = jnp.einsum("iab,jbc->ijac", pan, xrow, precision=PRECISE)
            b_loc = b_loc - upd.astype(b_loc.dtype)
            # trailing-phase fault: one stored B/X tile rots right after
            # step k's update (final for already-solved rows — exactly
            # correctable; live for remaining rows — recompute class)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & ((fph == PH_TRAIL) | (fph == PH_PANEL))
                    & (k == fk) & (r == fti % p) & (c == ftj % q)
                )
                b_loc = _hit4(b_loc, hit, fti // p, ftj // q, fmode, val)
            return b_loc

        return prefetch_bcast(nt, la, fetch, consume, b_loc)

    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, spec, P(), P()),
            out_specs=spec, check_vma=False,
        )(at, bt, fi, fv)


def _encode_trsm_rhs(a: jax.Array, b: jax.Array, nb: int, mesh):
    """Pad B to A's padded row extent, tile-pad its columns, and append
    the CSR weighted column-checksum tile columns (the solution-checksum
    carrier).  Pad rows of the identity-padded A solve to exact zeros."""
    n = a.shape[0]
    mt = padded_tiles(n, nb, mesh)
    N = mt * nb
    ntb = max(1, -(-int(b.shape[1]) // nb))
    Nc = ntb * nb
    bp = cks.pad_dense(b, N, Nc)
    return jnp.concatenate([bp, cks.col_checksums(bp, nb)], axis=1), mt, ntb


def _trsm_residual(out_dense, nb: int, N: int, Nc: int):
    """(X, carried column checksums minus recomputed X column sums)."""
    x = out_dense[:N, :Nc]
    dc = out_dense[:N, Nc : Nc + CSR * nb] - cks.col_checksums(x, nb)
    return x, dc


def trsm_ft(
    a, b, mesh, nb: int = 256, uplo=None, op=None, diag=None,
    policy: FtPolicy = FtPolicy.Correct, lookahead=None, bcast_impl=None,
    _rerun: bool = False,
):
    """ABFT distributed triangular solve op(A) X = B (left side, TrsmB
    schedule).  Returns (dense X, FtReport); raises FtError per policy.

    Detection: the carried solution checksums X_ck (solved alongside as
    extra RHS columns) are differenced against the recomputed column
    sums of X.  A corrupted ALREADY-SOLVED tile is final data — the
    unit-weight discrepancy restores it exactly (rounding included); a
    corrupted not-yet-solved tile (or a received-panel fault) feeds
    later substitution steps and escalates to one recompute, then
    ``FtError`` if the rerun still verifies dirty."""
    from ..types import Diag, Op, Uplo

    uplo = uplo or Uplo.Lower
    op = op or Op.NoTrans
    diag = diag or Diag.NonUnit
    if op == Op.ConjTrans:
        raise ValueError("trsm_ft covers NoTrans/Trans (real data)")
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.ndim != 2 or a.shape[0] != a.shape[1] or b.shape[0] != a.shape[0]:
        raise ValueError(f"trsm_ft shape mismatch: A {a.shape}, B {b.shape}")
    if policy == FtPolicy.Off:
        from ..parallel.dist import from_dense as _fd, to_dense as _td
        from ..parallel.dist_trsm import trsm_dist
        from ..types import MethodTrsm

        ad = _fd(a, mesh, nb, diag_pad_one=True)
        bd = _fd(b, mesh, nb)
        x = trsm_dist(ad, bd, uplo, op, diag, method=MethodTrsm.TrsmB,
                      lookahead=lookahead, bcast_impl=bcast_impl)
        return _td(x)[: a.shape[0], : b.shape[1]], FtReport(op="trsm")
    n, ncols = int(a.shape[0]), int(b.shape[1])
    p, q = mesh_shape(mesh)
    b_aug, mt, ntb = _encode_trsm_rhs(a, b, nb, mesh)
    ad = from_dense(a, mesh, nb, diag_pad_one=True)
    bd = from_dense(b_aug, mesh, nb)
    la = la_depth(lookahead, mt)
    ints, vals = inject.spec_arrays("trsm")
    out_t = _ft_trsm_jit(
        ad.tiles, bd.tiles, mesh, p, q, mt,
        uplo == Uplo.Lower, op == Op.Trans, diag == Diag.Unit, la,
        resolve_bcast_impl(bcast_impl),
        jnp.asarray(ints), jnp.asarray(vals, jnp.result_type(float)),
    )
    inject.consume("trsm")
    out_full = to_dense(DistMatrix(
        tiles=out_t, m=b_aug.shape[0], n=b_aug.shape[1], nb=nb, mesh=mesh,
    ))
    N, Nc = mt * nb, ntb * nb
    x, dc = _trsm_residual(out_full, nb, N, Nc)
    x_np, dcn = np.asarray(x), np.asarray(dc)
    fmax = max(1.0, cks.finite_max(x_np), cks.finite_max(np.asarray(b)))
    tol1 = cks.threshold(N, x_np.dtype, ntb * fmax)
    tol2 = cks.threshold(N, x_np.dtype, ntb * ntb * fmax)
    verdC = _verdict_rows(dcn, nb, ntb, tol1, tol2, "X-tile")
    report = FtReport(op="trsm")
    if verdC.clean:
        return jnp.asarray(x_np[:n, :ncols]), report
    dets = verdC.detections
    count("ft.detected", "trsm", len(dets))
    if policy == FtPolicy.Detect:
        raise FtError("trsm", "corruption detected (policy=detect)", dets)
    if policy == FtPolicy.Correct and not _rerun:
        # exact repair, valid only for damage in an ALREADY-SOLVED tile:
        # one flagged tile row, one located column — add the unit
        # discrepancy back and let re-verification judge it
        if len(verdC.flagged) == 1 and verdC.located != {-1}:
            (i_star,) = verdC.flagged
            (j_star,) = verdC.located
            fixed = x_np.copy()
            _add_row_disc(fixed, dcn, nb, int(i_star), int(j_star))
            dc2 = np.asarray(
                out_full[:N, Nc : Nc + CSR * nb]
                - cks.col_checksums(jnp.asarray(fixed), nb)
            )
            if _verdict_rows(dc2, nb, ntb, tol1, tol2, "X-tile").clean:
                count("ft.corrected", "trsm", len(dets))
                report.action, report.detections = "corrected", dets
                return jnp.asarray(fixed[:n, :ncols]), report
    if _rerun:
        count("ft.uncorrectable", "trsm")
        raise FtError("trsm", "recompute still fails verification", dets)
    # live-data corruption (the fault fed later substitution steps):
    # one full recompute — transient faults have disarmed
    count("ft.recomputed", "trsm")
    out2, rep2 = trsm_ft(a, b, mesh, nb, uplo, op, diag, policy, lookahead,
                         bcast_impl, _rerun=True)
    rep2.action = "recomputed"
    rep2.detections = dets + rep2.detections
    return out2, rep2


# ---------------------------------------------------------------------------
# checksum-carrying her2k/syr2k (ISSUE 13: the eig chain's dominant
# trailing-update op).  Augmenting BOTH rank-2k operands with checksum
# tile ROWS makes the product carry checksums on BOTH sides for free:
#
#   [A; WA][B; WB]^H + [B; WB][A; WA]^H
#     = [ C       C W^H ]      with C = A B^H + B A^H,
#       [ W C   W C W^H ]
#
# i.e. the augmented her2k of the augmented operands IS the her2k of the
# data block wearing its own row (WC) and column (C W^H) checksums plus
# the cross block — the exact structure _gemm_verify/_gemm_try_repair
# already judge and repair.  The kernel is dist_blas3's her2k SUMMA
# schedule verbatim (the shared ``_her2k_panels`` fetch — two rooted
# column-panel broadcasts + two transposed gathers per step; checksum
# tiles are just more tiles of the augmented grid), computed FULL so the
# mirrored checksum columns materialize.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _ft_her2k_jit(at, bt, ct, alpha, beta, mesh, p, q, kt, k_true, conj,
                  la, bi, fi, fv):
    """Checksum-carrying her2k/syr2k over row-augmented operands (the
    checksum tile rows need no in-kernel special-casing: they are
    ordinary tiles of the full rank-2k accumulation).  Fault hooks:
    ``bcast`` corrupts one device's RECEIVED copy
    of A's column panel before its updates consume it (propagates into
    one tile row of that device's accumulator — the single-row repair
    class), ``trailing`` one accumulator tile right after step k's
    update lands (final data for the rank-2k accumulation — exactly
    correctable, the GEMM class)."""
    from ..parallel.dist_blas3 import _her2k_panels

    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(a_loc, b_loc, fi, fv):
        mtl, _ktl, nb, _ = a_loc.shape
        dtype = a_loc.dtype
        r, c, i_log, _ = local_indices(p, q, mtl, mtl)
        slots = _slots(fi, fv)

        def fetch(k):
            acol, aT = _her2k_panels(a_loc, k, p, q, k_true, conj)
            bcol, bT = _her2k_panels(b_loc, k, p, q, k_true, conj)
            # bcast-phase fault: one device's RECEIVED copy of the A
            # column panel rots before its MXU updates consume it
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & (fph == PH_BCAST) & (k == fk)
                    & (r == fr) & (c == fc)
                )
                acol = _hit3(acol, hit & (r == fti % p), fti // p, fmode, val)
            return (acol, aT), (bcol, bT)

        def consume(k, prefetched, acc):
            (acol, aT), (bcol, bT) = prefetched
            u1 = jnp.einsum("iab,jcb->ijac", acol, bT, precision=PRECISE)
            u2 = jnp.einsum("iab,jcb->ijac", bcol, aT, precision=PRECISE)
            al2 = jnp.conj(alpha) if conj else alpha
            acc = acc + (alpha * u1 + al2 * u2).astype(dtype)
            # trailing-phase fault: one accumulator tile rots right after
            # step k's update lands (final data — correctable)
            for act, fk, fph, fti, ftj, fr, fc, fmode, val in slots:
                hit = (
                    (act == 1) & ((fph == PH_TRAIL) | (fph == PH_PANEL))
                    & (k == fk) & (r == fti % p) & (c == ftj % q)
                )
                acc = _hit4(acc, hit, fti // p, ftj // q, fmode, val)
            return acc

        ntl_c = -(-at.shape[0] // q)
        acc0 = jnp.zeros((mtl, ntl_c, nb, nb), dtype)
        # FULL accumulation: the checksum rows live below the data block
        # and their mirrored columns right of it — no triangle mask
        return prefetch_bcast(kt, la, fetch, consume, acc0)

    with bcast_impl_scope(bi):
        prod = shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, spec, P(), P()),
            out_specs=spec, check_vma=False,
        )(at, bt, fi, fv)
    if ct is None:
        return prod.astype(at.dtype)
    return (prod + beta * ct).astype(at.dtype)


def _encode_her2k(a: jax.Array, b: jax.Array, c, nb: int, mesh):
    """Rank-2k operands gain checksum tile ROWS; an optional C gains the
    full GEMM-output augmentation (row + column checksums + cross), so
    beta C folds consistently into the carried checksums (linearity)."""
    n, kdim = int(a.shape[0]), int(a.shape[1])
    mt = padded_tiles(n, nb, mesh)
    kt = padded_tiles(kdim, nb, mesh)
    Nm, Kp = mt * nb, kt * nb
    ap = cks.pad_dense(a, Nm, Kp)
    bp = cks.pad_dense(b, Nm, Kp)
    a_aug = jnp.concatenate([ap, cks.row_checksums(ap, nb)], axis=0)
    b_aug = jnp.concatenate([bp, cks.row_checksums(bp, nb)], axis=0)
    c_aug = None
    if c is not None:
        cp = cks.pad_dense(jnp.asarray(c), Nm, Nm)
        crow = cks.row_checksums(cp, nb)
        c_aug = jnp.concatenate(
            [
                jnp.concatenate([cp, cks.col_checksums(cp, nb)], axis=1),
                jnp.concatenate([crow, cks.col_checksums(crow, nb)], axis=1),
            ],
            axis=0,
        )
    return a_aug, b_aug, c_aug, mt, kt


def her2k_ft(
    alpha, a, b, mesh, nb: int = 256, beta=0.0, c=None, conj: bool = True,
    policy: FtPolicy = FtPolicy.Correct, lookahead=None, bcast_impl=None,
    _rerun: bool = False,
):
    """ABFT distributed rank-2k update C = alpha A op(B) + op(alpha) B
    op(A) + beta C (conj=True: her2k, op = ^H; conj=False: syr2k).
    Returns (dense FULL C — both triangles, n x n — and FtReport);
    raises FtError per policy.  Detection/location/repair reuse the GEMM
    machinery: the augmented output has exactly the GEMM checksum
    structure (see the module-section comment), and accumulator damage
    is always final data, so single-row/column/tile patterns repair
    exactly and received-panel corruption escalates to one recompute."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape:
        raise ValueError(f"her2k_ft: A and B must be same-shape, got "
                         f"{a.shape} vs {b.shape}")
    n = int(a.shape[0])  # rank-2k output is square: C is n x n
    p, q = mesh_shape(mesh)
    if policy == FtPolicy.Off:
        from ..parallel.dist_blas3 import her2k_dist

        ad = from_dense(a, mesh, nb)
        bd = from_dense(b, mesh, nb)
        cd = from_dense(jnp.asarray(c), mesh, nb) if c is not None else None
        out = her2k_dist(alpha, ad, bd, beta, cd, conj=conj, full=True,
                         lookahead=lookahead, bcast_impl=bcast_impl)
        return to_dense(out)[:n, :n], FtReport(op="her2k")
    a_aug, b_aug, c_aug, mt, kt = _encode_her2k(a, b, c, nb, mesh)
    ad = from_dense(a_aug, mesh, nb)
    bd = from_dense(b_aug, mesh, nb)
    cd = from_dense(c_aug, mesh, nb) if c_aug is not None else None
    la = la_depth(lookahead, kt)
    ints, vals = inject.spec_arrays("her2k")
    out_t = _ft_her2k_jit(
        ad.tiles, bd.tiles, (None if cd is None else cd.tiles), alpha, beta,
        mesh, p, q, kt, int(a.shape[1]), conj, la,
        resolve_bcast_impl(bcast_impl),
        jnp.asarray(ints), jnp.asarray(vals, jnp.result_type(float)),
    )
    inject.consume("her2k")
    out_np = np.asarray(to_dense(DistMatrix(
        tiles=out_t, m=a_aug.shape[0], n=a_aug.shape[0], nb=nb, mesh=mesh,
    )))
    verdR, verdC, drn, dcn = _gemm_verify(out_np, nb, mt, mt, kt)
    report = FtReport(op="her2k")
    if verdR.clean and verdC.clean:
        return jnp.asarray(out_np[:n, :n]), report
    dets = verdR.detections + verdC.detections
    count("ft.detected", "her2k", len(dets))
    if policy == FtPolicy.Detect:
        raise FtError("her2k", "corruption detected (policy=detect)", dets)
    if policy == FtPolicy.Correct and not _rerun:
        fixed = _gemm_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, mt)
        if fixed is not None:
            v2R, v2C, _, _ = _gemm_verify(fixed, nb, mt, mt, kt)
            if v2R.clean and v2C.clean:
                count("ft.corrected", "her2k", len(dets))
                report.action, report.detections = "corrected", dets
                return jnp.asarray(fixed[:n, :n]), report
    if _rerun:
        count("ft.uncorrectable", "her2k")
        raise FtError("her2k", "recompute still fails verification", dets)
    count("ft.recomputed", "her2k")
    out2, rep2 = her2k_ft(alpha, a, b, mesh, nb, beta, c, conj, policy,
                          lookahead, bcast_impl, _rerun=True)
    rep2.action = "recomputed"
    rep2.detections = dets + rep2.detections
    return out2, rep2


def _encode_factor(a: jax.Array, nb: int, mesh, with_cols: bool):
    """Square factorization input -> checksum-augmented dense, with the
    grid padding + identity pad diagonal applied BEFORE encoding so the
    checksums cover exactly what the kernel factors."""
    n = a.shape[0]
    mt = padded_tiles(n, nb, mesh)
    N = mt * nb
    ap = cks.pad_dense(a, N, N)
    d = jnp.arange(n, N)
    ap = ap.at[d, d].set(1)
    csr = cks.row_checksums(ap, nb)
    if not with_cols:
        return jnp.concatenate([ap, csr], axis=0), mt, N
    csc = cks.col_checksums(ap, nb)
    cross = cks.col_checksums(csr, nb)
    top = jnp.concatenate([ap, csc], axis=1)
    bot = jnp.concatenate([csr, cross], axis=1)
    return jnp.concatenate([top, bot], axis=0), mt, N


def _encode_gemm(a, b, c, nb: int, mesh):
    """A gains checksum rows, B checksum columns, C (the accumulator)
    both — checksums are linear, so alpha*A_aug@B_aug + beta*C_aug is
    the augmentation of alpha*A@B + beta*C."""
    mt = padded_tiles(a.shape[0], nb, mesh)
    kt = padded_tiles(a.shape[1], nb, mesh)
    nt = padded_tiles(b.shape[1], nb, mesh)
    Nm, Kp, Nn = mt * nb, kt * nb, nt * nb
    ap = cks.pad_dense(a, Nm, Kp)
    bp = cks.pad_dense(b, Kp, Nn)
    a_aug = jnp.concatenate([ap, cks.row_checksums(ap, nb)], axis=0)
    b_aug = jnp.concatenate([bp, cks.col_checksums(bp, nb)], axis=1)
    cp = cks.pad_dense(c, Nm, Nn) if c is not None else jnp.zeros((Nm, Nn), ap.dtype)
    crow = cks.row_checksums(cp, nb)
    c_aug = jnp.concatenate(
        [
            jnp.concatenate([cp, cks.col_checksums(cp, nb)], axis=1),
            jnp.concatenate([crow, cks.col_checksums(crow, nb)], axis=1),
        ],
        axis=0,
    )
    return a_aug, b_aug, c_aug, mt, kt, nt


# ---------------------------------------------------------------------------
# traceable verification: carried checksums minus recomputed tile sums
# ---------------------------------------------------------------------------


def _gemm_residual(out_dense, nb: int, mt: int, nt: int):
    Nm, Nn = mt * nb, nt * nb
    cdata = out_dense[:Nm, :Nn]
    dr = out_dense[Nm : Nm + CSR * nb, :Nn] - cks.row_checksums(cdata, nb)
    dc = out_dense[:Nm, Nn : Nn + CSR * nb] - cks.col_checksums(cdata, nb)
    return cdata, dr, dc


def _potrf_residual(out_dense, nb: int, mt: int):
    N = mt * nb
    l_eff = jnp.tril(out_dense[:N, :N])
    dr = out_dense[N : N + CSR * nb, :N] - cks.row_checksums(l_eff, nb)
    return dr


def _lu_residual(out_dense, nb: int, mt: int):
    N = mt * nb
    lu = out_dense[:N, :N]
    l_eff = jnp.tril(lu, -1) + jnp.eye(N, dtype=lu.dtype)
    u_eff = jnp.triu(lu)
    dr = out_dense[N : N + CSR * nb, :N] - cks.row_checksums(l_eff, nb)
    dc = out_dense[:N, N : N + CSR * nb] - cks.col_checksums(u_eff, nb)
    return dr, dc


# ---------------------------------------------------------------------------
# host-side verify / locate / repair
# ---------------------------------------------------------------------------


def _tile_disc_cols(drn: np.ndarray, nb: int):
    """(2nb, N) row-checksum residual -> per-tile-column (d1, d2) maxes."""
    nt = drn.shape[1] // nb
    d = np.abs(drn).reshape(2, nb, nt, nb).max(axis=(1, 3))
    return d[0], d[1]


def _tile_disc_rows(dcn: np.ndarray, nb: int):
    mt = dcn.shape[0] // nb
    d = np.abs(dcn).reshape(mt, nb, 2, nb).max(axis=(1, 3))
    return d[:, 0], d[:, 1]


def _col_block(drn: np.ndarray, nb: int, j: int, weighted: bool):
    base = nb if weighted else 0
    return drn[base : base + nb, j * nb : (j + 1) * nb]


def _row_block(dcn: np.ndarray, nb: int, i: int, weighted: bool):
    base = nb if weighted else 0
    return dcn[i * nb : (i + 1) * nb, base : base + nb]


class _Verdict:
    """One side's verification outcome: flagged tile indices + located
    cross index (the corrupted row for column flags, vice versa)."""

    def __init__(self, flagged, located, detections):
        self.flagged = list(flagged)
        self.located = located
        self.detections = detections

    @property
    def clean(self):
        return not self.flagged


def _verdict_cols(drn: np.ndarray, nb: int, axis_len: int, tol1, tol2, kind):
    d1, d2 = _tile_disc_cols(drn, nb)
    flagged = sorted(
        set(cks.flag_mismatches(d1, tol1)) | set(cks.flag_mismatches(d2, tol2))
    )
    located = set()
    dets = []
    for j in flagged:
        i_star = cks.ratio_locate(
            _col_block(drn, nb, j, False), _col_block(drn, nb, j, True), axis_len
        )
        located.add(i_star)
        dets.append(
            {"kind": kind, "where": (i_star, int(j)), "magnitude": float(d1[j])}
        )
    return _Verdict(flagged, located, dets)


def _verdict_rows(dcn: np.ndarray, nb: int, axis_len: int, tol1, tol2, kind):
    d1, d2 = _tile_disc_rows(dcn, nb)
    flagged = sorted(
        set(cks.flag_mismatches(d1, tol1)) | set(cks.flag_mismatches(d2, tol2))
    )
    located = set()
    dets = []
    for i in flagged:
        j_star = cks.ratio_locate(
            _row_block(dcn, nb, i, False), _row_block(dcn, nb, i, True), axis_len
        )
        located.add(j_star)
        dets.append(
            {"kind": kind, "where": (int(i), j_star), "magnitude": float(d1[i])}
        )
    return _Verdict(flagged, located, dets)


def _add_col_disc(data: np.ndarray, drn: np.ndarray, nb: int, i: int, j: int, mask=None):
    """Exact repair: the unit-weight discrepancy of column j IS the
    negated error of the (single) corrupted tile (i, j) — add it back."""
    blk = _col_block(drn, nb, j, False)
    if mask is not None:
        blk = blk * mask
    data[i * nb : (i + 1) * nb, j * nb : (j + 1) * nb] += blk


def _add_row_disc(data: np.ndarray, dcn: np.ndarray, nb: int, i: int, j: int, mask=None):
    blk = _row_block(dcn, nb, i, False)
    if mask is not None:
        blk = blk * mask
    data[i * nb : (i + 1) * nb, j * nb : (j + 1) * nb] += blk


# ---------------------------------------------------------------------------
# factorization drivers: encode -> augmented kernel -> verify -> repair
# ---------------------------------------------------------------------------


def _factor_verify(op: str, out_full, nb: int, mt: int):
    """Verdicts for a factor run: carried vs recomputed checksums of the
    output factor(s), thresholded at the dtype's accumulated-rounding
    scale.  Returns (row verdict, col verdict | None, out_np, drn, dcn)."""
    is_lu = op == "getrf_nopiv"
    out_np = np.asarray(out_full)
    N = mt * nb
    fmax = max(1.0, cks.finite_max(out_np[:N, :N]))
    tol1 = cks.threshold(N, out_np.dtype, mt * fmax)
    tol2 = cks.threshold(N, out_np.dtype, mt * mt * fmax)
    if is_lu:
        dr, dc = _lu_residual(jnp.asarray(out_np), nb, mt)
        drn, dcn = np.asarray(dr), np.asarray(dc)
        verdR = _verdict_cols(drn, nb, mt, tol1, tol2, "L-tile")
        verdC = _verdict_rows(dcn, nb, mt, tol1, tol2, "U-tile")
        return verdR, verdC, out_np, drn, dcn
    drn = np.asarray(_potrf_residual(jnp.asarray(out_np), nb, mt))
    return _verdict_cols(drn, nb, mt, tol1, tol2, "L-tile"), None, out_np, drn, None


def _factor_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, is_lu):
    """Exact algebraic repair, valid only for damage in FINALIZED factor
    tiles: a single located tile row on the L side (resp. column on the
    U side), each flagged column's unit-weight discrepancy added back.
    Returns the repaired full array, or None when the pattern indicates
    propagated (live-data) corruption — the recompute class."""
    okR = verdR.clean or (verdR.located != {-1} and len(verdR.located) == 1)
    okC = verdC is None or verdC.clean or (
        verdC.located != {-1} and len(verdC.located) == 1
    )
    if not (okR and okC):
        return None
    fixed = out_np.copy()
    N = mt * nb
    data = fixed[:N, :N]
    if not verdR.clean:
        i_star = next(iter(verdR.located))
        for j in verdR.flagged:
            if i_star < j:
                return None  # L damage must sit at/below the diagonal
            mask = None
            if i_star == j:  # diag tile: only the L part of the packed tile
                mask = np.tril(np.ones((nb, nb)), -1 if is_lu else 0)
            _add_col_disc(data, drn, nb, i_star, int(j), mask)
    if verdC is not None and not verdC.clean:
        j_star = next(iter(verdC.located))
        for i in verdC.flagged:
            if j_star < i:
                return None  # U damage must sit at/above the diagonal
            mask = np.triu(np.ones((nb, nb))) if int(i) == j_star else None
            _add_row_disc(data, dcn, nb, int(i), j_star, mask)
    return fixed


def _factor_result(out_np, n: int, nb: int, mesh) -> DistMatrix:
    """Crop the data region to the logical size and re-distribute with
    the factorization padding contract (same output shape as the plain
    mesh drivers: downstream trsm sweeps mask by uplo)."""
    return from_dense(jnp.asarray(out_np[:n, :n]), mesh, nb, diag_pad_one=True)


def _factor_ft(
    op: str, a, mesh, nb: int, policy: FtPolicy, lookahead,
    bcast_impl=None, _rerun: bool = False,
):
    is_lu = op == "getrf_nopiv"
    a = jnp.asarray(a)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"{op}_ft needs a square matrix, got {a.shape}")
    n = a.shape[0]
    p, q = mesh_shape(mesh)
    aug, mt, _N = _encode_factor(a, nb, mesh, with_cols=is_lu)
    d = from_dense(aug, mesh, nb)
    la = la_depth(lookahead, mt)
    ints, vals = inject.spec_arrays(op)
    kern = _ft_lu_jit if is_lu else _ft_potrf_jit
    out_t, info = kern(
        d.tiles, mesh, p, q, mt, la, resolve_bcast_impl(bcast_impl),
        jnp.asarray(ints), jnp.asarray(vals, jnp.result_type(float)),
    )
    inject.consume(op)
    out_full = to_dense(
        DistMatrix(tiles=out_t, m=aug.shape[0], n=aug.shape[1], nb=nb, mesh=mesh)
    )
    if int(info) != 0:
        # The factorization itself reports breakdown (non-SPD / singular
        # pivot).  The factor is NaN/garbage past the bad pivot, so the
        # checksum verify cannot distinguish legitimate breakdown from a
        # fault that CAUSED the breakdown — one recompute separates them:
        # a transient fault vanishes on the rerun, a genuinely bad matrix
        # fails again and is returned with the plain driver's semantics
        # (caller checks info; never FtError for honest numerics).
        if _rerun:
            return (
                _factor_result(np.asarray(out_full), n, nb, mesh),
                info,
                FtReport(op=op),
            )
        res2, info2, rep2 = _factor_ft(
            op, a, mesh, nb, policy, lookahead, bcast_impl, _rerun=True,
        )
        if int(info2) == 0:  # first breakdown was fault-induced
            count("ft.detected", op)
            if policy == FtPolicy.Detect:
                raise FtError(op, "fault-induced breakdown (policy=detect)")
            count("ft.recomputed", op)
            rep2.action = "recomputed"
        return res2, info2, rep2
    verdR, verdC, out_np, drn, dcn = _factor_verify(op, out_full, nb, mt)
    report = FtReport(op=op)
    if verdR.clean and (verdC is None or verdC.clean):
        return _factor_result(out_np, n, nb, mesh), info, report
    dets = verdR.detections + (verdC.detections if verdC is not None else [])
    count("ft.detected", op, len(dets))
    if policy == FtPolicy.Detect:
        raise FtError(op, "corruption detected (policy=detect)", dets)
    if policy == FtPolicy.Correct and not _rerun:
        fixed = _factor_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, is_lu)
        if fixed is not None:
            v2R, v2C, fixed_np, _, _ = _factor_verify(op, jnp.asarray(fixed), nb, mt)
            if v2R.clean and (v2C is None or v2C.clean):
                count("ft.corrected", op, len(dets))
                report.action, report.detections = "corrected", dets
                return _factor_result(fixed_np, n, nb, mesh), info, report
    if _rerun:
        count("ft.uncorrectable", op)
        raise FtError(op, "recompute still fails verification", dets)
    # live-data corruption (the fault fed later panels): one full
    # recompute — transient faults have disarmed, persistent ones
    # re-detect on the rerun and escalate above
    count("ft.recomputed", op)
    res, info2, rep2 = _factor_ft(
        op, a, mesh, nb, policy, lookahead, bcast_impl, _rerun=True,
    )
    rep2.action = "recomputed"
    rep2.detections = dets + rep2.detections
    return res, info2, rep2


# ---------------------------------------------------------------------------
# GEMM driver (shared verify/repair also serves the dense api path)
# ---------------------------------------------------------------------------


def _gemm_verify(out_np: np.ndarray, nb: int, mt: int, nt: int, kt: int):
    cdata, dr, dc = _gemm_residual(jnp.asarray(out_np), nb, mt, nt)
    drn, dcn = np.asarray(dr), np.asarray(dc)
    cmax = max(1.0, cks.finite_max(np.asarray(cdata)))
    ops = (kt + max(mt, nt)) * nb
    verdR = _verdict_cols(
        drn, nb, mt,
        cks.threshold(ops, drn.dtype, mt * cmax),
        cks.threshold(ops, drn.dtype, mt * mt * cmax),
        "C-tile",
    )
    verdC = _verdict_rows(
        dcn, nb, nt,
        cks.threshold(ops, dcn.dtype, nt * cmax),
        cks.threshold(ops, dcn.dtype, nt * nt * cmax),
        "C-tile",
    )
    return verdR, verdC, drn, dcn


def _gemm_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, nt):
    """GEMM output damage is always final data, so every single-row /
    single-column / single-tile pattern repairs exactly; damage confined
    to a checksum tile itself leaves the data verified by the other side
    and is repaired by rewriting the carried checksum."""
    Nm, Nn = mt * nb, nt * nb
    fixed = out_np.copy()
    data = fixed[:Nm, :Nn]
    if verdR.clean != verdC.clean:
        # one side clean => the data region is intact (a data-tile fault
        # flags BOTH sides); the damage hit a carried checksum tile
        if verdR.clean:
            fixed[:Nm, Nn : Nn + CSR * nb] = np.asarray(
                cks.col_checksums(jnp.asarray(data), nb)
            )
        else:
            fixed[Nm : Nm + CSR * nb, :Nn] = np.asarray(
                cks.row_checksums(jnp.asarray(data), nb)
            )
        return fixed
    if len(verdC.flagged) == 1:  # single corrupted tile row
        (i_star,) = verdC.flagged
        if verdR.located != {int(i_star)}:
            return None
        for j in verdR.flagged:
            _add_col_disc(data, drn, nb, int(i_star), int(j))
        # a bcast-phase fault corrupts every tile the faulty device wrote
        # at that step — including the CARRIED column-checksum tiles of
        # row i_star when that device owns them.  The bottom checksums
        # (the repair authority here) are computed on other coordinates;
        # rewrite the repaired row's carried column checksums from the
        # fixed data so re-verification judges the repair, not the stale
        # carried copy.
        i0 = int(i_star) * nb
        fixed[i0 : i0 + nb, Nn:] = np.asarray(
            cks.col_checksums(jnp.asarray(data), nb)
        )[i0 : i0 + nb]
        return fixed
    if len(verdR.flagged) == 1:  # single corrupted tile column
        (j_star,) = verdR.flagged
        if verdC.located != {int(j_star)}:
            return None
        for i in verdC.flagged:
            _add_row_disc(data, dcn, nb, int(i), int(j_star))
        j0 = int(j_star) * nb
        fixed[mt * nb :, j0 : j0 + nb] = np.asarray(
            cks.row_checksums(jnp.asarray(data), nb)
        )[:, j0 : j0 + nb]
        return fixed
    return None


def _gemm_ft(
    alpha, a, b, mesh, nb: int, beta, cin, policy: FtPolicy, lookahead,
    bcast_impl=None, _rerun: bool = False,
):
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape[1] != b.shape[0]:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    p, q = mesh_shape(mesh)
    a_aug, b_aug, c_aug, mt, kt, nt = _encode_gemm(a, b, cin, nb, mesh)
    ad = from_dense(a_aug, mesh, nb)
    bd = from_dense(b_aug, mesh, nb)
    cd = from_dense(c_aug, mesh, nb)
    la = la_depth(lookahead, kt)
    ints, vals = inject.spec_arrays("gemm")
    out_t = _ft_summa_jit(
        ad.tiles, bd.tiles, cd.tiles, alpha, beta, mesh, p, q, kt, la,
        resolve_bcast_impl(bcast_impl),
        jnp.asarray(ints), jnp.asarray(vals, jnp.result_type(float)),
    )
    inject.consume("gemm")
    out_np = np.asarray(
        to_dense(DistMatrix(tiles=out_t, m=a_aug.shape[0], n=b_aug.shape[1],
                            nb=nb, mesh=mesh))
    )
    m_out, n_out = int(a.shape[0]), int(b.shape[1])
    verdR, verdC, drn, dcn = _gemm_verify(out_np, nb, mt, nt, kt)
    report = FtReport(op="gemm")
    if verdR.clean and verdC.clean:
        return jnp.asarray(out_np[:m_out, :n_out]), report
    dets = verdR.detections + verdC.detections
    count("ft.detected", "gemm", len(dets))
    if policy == FtPolicy.Detect:
        raise FtError("gemm", "corruption detected (policy=detect)", dets)
    if policy == FtPolicy.Correct and not _rerun:
        fixed = _gemm_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, nt)
        if fixed is not None:
            v2R, v2C, _, _ = _gemm_verify(fixed, nb, mt, nt, kt)
            if v2R.clean and v2C.clean:
                count("ft.corrected", "gemm", len(dets))
                report.action, report.detections = "corrected", dets
                return jnp.asarray(fixed[:m_out, :n_out]), report
    if _rerun:
        count("ft.uncorrectable", "gemm")
        raise FtError("gemm", "recompute still fails verification", dets)
    count("ft.recomputed", "gemm")
    out2, rep2 = _gemm_ft(
        alpha, a, b, mesh, nb, beta, cin, policy, lookahead, bcast_impl,
        _rerun=True,
    )
    rep2.action = "recomputed"
    rep2.detections = dets + rep2.detections
    return out2, rep2


# ---------------------------------------------------------------------------
# public drivers
# ---------------------------------------------------------------------------


def _la_opt(opts: Optional[Options]):
    from ..types import Option, get_option

    return get_option(opts, Option.Lookahead)


def _bi_opt(opts: Optional[Options]):
    from ..types import Option, get_option

    return get_option(opts, Option.BcastImpl)


def gemm_ft(
    alpha, a, b, mesh, nb: int = 256, beta=0.0, c=None,
    policy: FtPolicy = FtPolicy.Correct, lookahead=None, bcast_impl=None,
) -> Tuple[jax.Array, FtReport]:
    """ABFT SUMMA: C = alpha A B + beta C with carried checksums.
    Returns (dense C, FtReport); raises FtError per policy.  The checksum
    panels ride the same broadcast engine as the plain kernels, so
    ``bcast_impl`` (Option.BcastImpl) applies unchanged."""
    if policy == FtPolicy.Off:
        from ..parallel.drivers import gemm_mesh

        return gemm_mesh(alpha, a, b, mesh, nb, beta, c), FtReport(op="gemm")
    return _gemm_ft(alpha, a, b, mesh, nb, beta, c, policy, lookahead,
                    bcast_impl)


def potrf_ft(
    a, mesh, nb: int = 256, policy: FtPolicy = FtPolicy.Correct, lookahead=None,
    bcast_impl=None,
) -> Tuple[DistMatrix, jax.Array, FtReport]:
    """ABFT mesh Cholesky.  Returns (L DistMatrix, info, FtReport)."""
    if policy == FtPolicy.Off:
        from ..parallel.drivers import potrf_mesh

        l, info = potrf_mesh(a, mesh, nb)
        return l, info, FtReport(op="potrf")
    return _factor_ft("potrf", a, mesh, nb, policy, lookahead, bcast_impl)


def getrf_nopiv_ft(
    a, mesh, nb: int = 256, policy: FtPolicy = FtPolicy.Correct, lookahead=None,
    bcast_impl=None,
) -> Tuple[DistMatrix, jax.Array, FtReport]:
    """ABFT mesh LU-nopiv.  Returns (LU DistMatrix, info, FtReport)."""
    if policy == FtPolicy.Off:
        from ..parallel.drivers import getrf_nopiv_mesh

        lu, info = getrf_nopiv_mesh(a, mesh, nb)
        return lu, info, FtReport(op="getrf_nopiv")
    return _factor_ft("getrf_nopiv", a, mesh, nb, policy, lookahead,
                      bcast_impl)


# opts-driven wrappers with the plain mesh-driver signatures, used by
# parallel.drivers when Option.FaultTolerance is not off


@instrument("gemm_mesh_ft")
def gemm_mesh_ft(alpha, a, b, mesh, nb=256, beta=0.0, c=None,
                 opts: Optional[Options] = None) -> jax.Array:
    out, _ = gemm_ft(alpha, a, b, mesh, nb, beta, c,
                     policy=resolve_policy(opts), lookahead=_la_opt(opts),
                     bcast_impl=_bi_opt(opts))
    return out


@instrument("her2k_mesh_ft")
def her2k_mesh_ft(alpha, a, b, mesh, nb=256, beta=0.0, c=None,
                  conj: bool = True,
                  opts: Optional[Options] = None) -> jax.Array:
    out, _ = her2k_ft(alpha, a, b, mesh, nb, beta, c, conj=conj,
                      policy=resolve_policy(opts), lookahead=_la_opt(opts),
                      bcast_impl=_bi_opt(opts))
    return out


@instrument("potrf_mesh_ft")
def potrf_mesh_ft(a, mesh, nb=256, opts: Optional[Options] = None):
    l, info, _ = potrf_ft(a, mesh, nb, policy=resolve_policy(opts),
                          lookahead=_la_opt(opts), bcast_impl=_bi_opt(opts))
    return l, info


@instrument("getrf_nopiv_mesh_ft")
def getrf_nopiv_mesh_ft(a, mesh, nb=256, opts: Optional[Options] = None):
    lu, info, _ = getrf_nopiv_ft(a, mesh, nb, policy=resolve_policy(opts),
                                 lookahead=_la_opt(opts),
                                 bcast_impl=_bi_opt(opts))
    return lu, info


# ---------------------------------------------------------------------------
# dense single-array ABFT (the api.multiply path: no mesh, same checks)
# ---------------------------------------------------------------------------


def gemm_checked(
    alpha, a, b, beta=0.0, c=None, nb: int = 32,
    policy: FtPolicy = FtPolicy.Detect, _rerun: bool = False,
) -> jax.Array:
    """Checksum-verified dense GEMM for the single-array facade: the
    product and its checksums are computed by independent XLA programs,
    so a silent corruption in either is caught by the comparison; single
    tile/row/column damage repairs exactly under ``correct``, other
    patterns (and everything under ``recompute``) re-execute once —
    the same policy ladder as the mesh drivers."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    m, n = int(a.shape[0]), int(b.shape[1])
    mt, kt, nt = -(-m // nb), -(-int(a.shape[1]) // nb), -(-n // nb)
    ap = cks.pad_dense(a, mt * nb, kt * nb)
    bp = cks.pad_dense(b, kt * nb, nt * nb)
    cp = (cks.pad_dense(jnp.asarray(c), mt * nb, nt * nb) if c is not None
          else jnp.zeros((mt * nb, nt * nb), ap.dtype))
    cdata = (alpha * jnp.matmul(ap, bp, precision=PRECISE) + beta * cp).astype(ap.dtype)
    crow = (alpha * jnp.matmul(cks.row_checksums(ap, nb), bp, precision=PRECISE)
            + beta * cks.row_checksums(cp, nb)).astype(ap.dtype)
    ccol = (alpha * jnp.matmul(ap, cks.col_checksums(bp, nb), precision=PRECISE)
            + beta * cks.col_checksums(cp, nb)).astype(ap.dtype)
    out_np = np.zeros((mt * nb + CSR * nb, nt * nb + CSR * nb),
                      np.asarray(cdata).dtype)
    out_np[: mt * nb, : nt * nb] = np.asarray(cdata)
    out_np[mt * nb :, : nt * nb] = np.asarray(crow)
    out_np[: mt * nb, nt * nb :] = np.asarray(ccol)
    verdR, verdC, drn, dcn = _gemm_verify(out_np, nb, mt, nt, kt)
    if verdR.clean and verdC.clean:
        return cdata[:m, :n]
    dets = verdR.detections + verdC.detections
    count("ft.detected", "gemm_dense", len(dets))
    if policy == FtPolicy.Detect:
        raise FtError("gemm_dense", "corruption detected (policy=detect)", dets)
    if policy == FtPolicy.Correct and not _rerun:
        fixed = _gemm_try_repair(out_np, drn, dcn, verdR, verdC, nb, mt, nt)
        if fixed is not None:
            v2R, v2C, _, _ = _gemm_verify(fixed, nb, mt, nt, kt)
            if v2R.clean and v2C.clean:
                count("ft.corrected", "gemm_dense", len(dets))
                return jnp.asarray(fixed[:m, :n])
    if _rerun:
        count("ft.uncorrectable", "gemm_dense")
        raise FtError("gemm_dense", "recompute still fails verification", dets)
    count("ft.recomputed", "gemm_dense")
    return gemm_checked(alpha, a, b, beta, c, nb, policy, _rerun=True)
