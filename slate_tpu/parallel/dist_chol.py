"""Distributed right-looking Cholesky over the block-cyclic mesh.

TPU-native analogue of ``src/potrf.cc`` (impl::potrf task DAG,
potrf.cc:91-196): per k — factor the diagonal tile, trsm the panel column,
broadcast the panel along process rows *and* columns (the symmetric
listBcastMT pattern, potrf.cc:124-134), herk the trailing matrix.

Design inversion: the OpenMP task graph + MOSI tile migration becomes ONE
``lax.fori_loop`` inside ``shard_map_compat``.  Per iteration:

- diagonal tile -> all devices via a two-hop rooted broadcast
  (comm.bcast_diag_tile; ppermute ring/doubling under Option.BcastImpl,
  masked double psum under the legacy lowering); every device factors the
  nb x nb tile redundantly (replicated flops are cheaper than a second
  broadcast — the panel is latency-bound, reference P4).
- panel trsm happens on the owning mesh column, then one rooted broadcast
  along axis 'q' gives every device the panel tiles for its row set
  (tileBcast down rows).
- the her-k update needs the panel indexed by *column* too: an all_gather
  over axis 'p' (n * nb elements — small) plus a cyclic index-map gather
  replaces the reference's transposed bcast list (potrf.cc:129-133).
- trailing update = one masked batched einsum over the local tile stack.

Static shapes: the update runs on trailing views with i/j > k masks
(SURVEY §7 "masked full-size updates"), segmented into comm.BUCKETS
statically-shrinking buckets — ~1.4x the optimal n^3/3 flops at 4
buckets (measured 1.7x step-time reduction vs the unbucketed kernel;
artifacts/README.md).  The work-optimal single-chip path is linalg.chol;
this kernel is the scaling path where the mesh amortizes the masked
flops.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec as P

from ..obs import instrument
from ..obs.numerics import resolve_num_monitor
from ..ops.pallas_ops import (
    chol_trailing_update_pallas,
    resolve_update_impl,
    update_engaged,
    update_impl_scope,
)
from .dist import DistMatrix
from .mesh import COL_AXIS, ROW_AXIS, mesh_shape
from .comm import (
    PRECISE,
    num_gauge_dtype,
    all_gather_a,
    audit_scope,
    bcast_diag_tile,
    bcast_from_col,
    bcast_impl_scope,
    bucket_plan,
    la_depth,
    local_indices,
    phase_scope,
    pipelined_factor_loop,
    resolve_bcast_impl,
    shard_map_compat,
)

from typing import Optional

@instrument("potrf_dist")
def potrf_dist(
    a: DistMatrix, lookahead: Optional[int] = None,
    bcast_impl: Optional[str] = None, num_monitor: Optional[str] = None,
    update_impl: Optional[str] = None,
) -> Tuple[DistMatrix, jax.Array]:
    """Factor A = L L^H (lower). ``a`` holds the lower triangle (upper tile
    content ignored). Returns (L as DistMatrix, info).

    ``lookahead`` (Option.Lookahead; None = the option default, 1)
    software-pipelines the k-loop: each step's trailing herk is deferred
    into the next iteration so the panel broadcasts overlap it
    (potrf.cc:129-133's lookahead queues).  Results are bitwise-identical
    at any depth.  ``bcast_impl`` (Option.BcastImpl) picks the panel /
    diag-tile broadcast lowering — masked psum or the ppermute engine —
    also bitwise-identical.  ``num_monitor``
    (Option.NumMonitor) threads the in-carry numerics gauges: ``on``
    accumulates the Schur-diagonal near-breakdown margin in the loop
    carry (each pivot tile's diagonal sampled right before its own panel
    factorization — a strict-schedule intermediate at ANY lookahead
    depth, so the gauge is depth-invariant) plus the final factor's diag
    min/max, reduced once at loop exit; ``off`` (and the flight
    step-dispatch path) is jaxpr-identical and records nothing.
    ``update_impl`` (Option.UpdateImpl) picks the trailing-herk lowering:
    ``xla`` (today's masked einsum bulk, jaxpr-identical) or ``pallas``
    (one fused grid dispatch per k-step; comm bytes invariant by
    construction)."""
    p, q = mesh_shape(a.mesh)
    if a.mt != a.nt:
        raise ValueError("potrf_dist needs a square tile grid")
    a.require_diag_pad("potrf_dist")
    from ..obs import flight as _flight
    from ..obs import numerics as _num

    nm = resolve_num_monitor(num_monitor) == "on"
    if _flight.step_dispatch_active():
        # flight-recorder step dispatch: same arithmetic, fenced per phase
        # (the per-phase programs carry no gauges — monitoring is the
        # fused kernels' surface)
        lt, info = _flight.potrf_steps(
            a.tiles, a.mesh, p, q, a.nt, la_depth(lookahead, a.nt),
            resolve_bcast_impl(bcast_impl),
            resolve_update_impl(update_impl),
        )
    elif nm:
        lt, info, gz = _potrf_jit(
            a.tiles, a.mesh, p, q, a.nt, la_depth(lookahead, a.nt),
            resolve_bcast_impl(bcast_impl),
            resolve_update_impl(update_impl), True, a.n,
        )
        _num.record_chol_gauges("potrf", gz[0], gz[1], gz[2])
    else:
        lt, info = _potrf_jit(
            a.tiles, a.mesh, p, q, a.nt, la_depth(lookahead, a.nt),
            resolve_bcast_impl(bcast_impl),
            resolve_update_impl(update_impl), False, 0,
        )
    return DistMatrix(
        tiles=lt, m=a.m, n=a.n, nb=a.nb, mesh=a.mesh, diag_pad=True
    ), info


def _chol_panel_factor_solve(dtile, pcol, cplx):
    """Diag-tile factor + panel-column tile solves: cholesky (in f32 for
    bf16), then one batched trsm."""
    dtype = dtile.dtype
    if dtype == jnp.bfloat16:
        lkk = lax.linalg.cholesky(dtile.astype(jnp.float32)).astype(dtype)
    else:
        lkk = lax.linalg.cholesky(dtile)
    lkk_h = jnp.conj(lkk).T if cplx else lkk.T
    solved = lax.linalg.triangular_solve(
        jnp.broadcast_to(lkk_h, pcol.shape), pcol,
        left_side=False, lower=False, transpose_a=False,
    )
    return lkk, solved


def _chol_panel_compute(view, k, p, q, i_log, c, cplx, roff=0, coff=0):
    """Compute half of the right-looking step-k panel phase: diag-tile
    broadcast + factor + panel-column tile solves + write-back.  Reads
    only column slot k // q - coff (refreshed by ``_chol_narrow`` when
    the update is deferred).  Returns (view,
    pan_own): the owner-masked solved panel column (zeros off the owning
    mesh column), ready for the broadcast half."""
    nb = view.shape[2]
    kc = k // q - coff
    dtile = bcast_diag_tile(view, k, p, q, nb, roff, coff)
    pcol = lax.dynamic_slice_in_dim(view, kc, 1, axis=1)[:, 0]
    lkk, solved = _chol_panel_factor_solve(dtile, pcol, cplx)
    below = (i_log > k)[:, None, None]
    on_diag = (i_log == k)[:, None, None]
    newcol = jnp.where(below, solved, jnp.where(on_diag, lkk, pcol))
    mine = (c == k % q)
    view = lax.dynamic_update_slice_in_dim(
        view, jnp.where(mine, newcol, pcol)[:, None], kc, axis=1
    )
    return view, jnp.where(below & mine, newcol, 0)


def _chol_panel_bcast(pan_own, k, p, q, j_log, roff=0):
    """Broadcast half of the panel phase: one rooted broadcast along the
    column axis plus the transposed gather the herk needs (all_gather
    over 'p' + cyclic index map — the reference's transposed bcast list,
    potrf.cc:129-133).  Returns the (pan, panT) update payload."""
    pan = bcast_from_col(pan_own, k % q)
    allpan = all_gather_a(pan, ROW_AXIS, axis=0)
    # logical row j sits at local slot j // p - roff of its owner mesh
    # row j % p; columns below the view's row cut (slot < 0 would wrap)
    # are finished (j <= k) and zero
    slot = j_log // p - roff
    panT = allpan[j_log % p, jnp.maximum(slot, 0)]
    panT = jnp.where((slot >= 0)[:, None, None], panT, 0)
    return pan, panT


def _chol_narrow(view, payload, k, q, lower, cplx, coff=0):
    """Apply the deferred step-(k-1) herk to the one local column slot
    the step-k panel phase reads — same per-element products as the full
    einsum, sliced to a single j.  ``lower`` is the trailing-view lower-
    triangle tile mask (i_log >= j_log)."""
    pan_p, panT_p = payload
    kc = k // q - coff
    pT = lax.dynamic_slice_in_dim(panT_p, kc, 1, axis=0)
    upd = jnp.einsum(
        "iab,jcb->ijac", pan_p, jnp.conj(pT) if cplx else pT,
        precision=PRECISE,
    ).astype(view.dtype)
    lcol = lax.dynamic_slice_in_dim(lower, kc, 1, axis=1)
    colv = lax.dynamic_slice_in_dim(view, kc, 1, axis=1)
    return lax.dynamic_update_slice_in_dim(
        view, colv - jnp.where(lcol, upd, 0), kc, axis=1
    )


def _chol_info_dist(t_loc, i_log, j_log, nt, nb):
    """info: 1 + global index of first bad pivot (potrf.cc:253-256), 0 if
    ok.  Granularity caveat: XLA's cholesky NaN-fills the whole failing
    tile, so on failure info points at the failing *tile*'s first bad
    diagonal entry (a lower bound within nb of the exact LAPACK index)."""
    diag_tiles = (i_log[:, None] == j_log[None, :])[:, :, None]
    dvals = jnp.einsum("ijaa->ija", jnp.real(t_loc))
    bad = (~jnp.isfinite(dvals) | (dvals <= 0)) & diag_tiles
    gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :] + 1
    big = nt * nb + 1
    # int32 before the reduction: TPU lowers 64-bit all-reduces for sum only
    local_info = jnp.min(jnp.where(bad, gidx, big)).astype(jnp.int32)
    info = lax.pmin(lax.pmin(local_info, ROW_AXIS), COL_AXIS)
    return jnp.where(info >= big, 0, info).astype(jnp.int32)


def _chol_bulk(view, payload, lower, cplx, excl_kc=None):
    """The trailing herk, dispatched by the active Option.UpdateImpl
    scope.  ``excl_kc`` None: the strict/drain full update; otherwise
    exclude the column slot ``_chol_narrow`` already refreshed.  The
    pallas branch folds the lower/exclusion select into a per-tile mask
    and runs one fused grid dispatch (bitwise vs the einsum form under
    interpret mode); complex stays on the xla form."""
    pan_p, panT_p = payload
    nb = view.shape[-1]
    if not cplx and update_engaged(
        view.dtype,
        (pan_p.shape[0] + panT_p.shape[0]) * nb * nb * view.dtype.itemsize,
    ):
        mask = lower[:, :, 0, 0]
        if excl_kc is not None:
            mask = mask & (jnp.arange(lower.shape[1]) != excl_kc)[None, :]
        return chol_trailing_update_pallas(view, pan_p, panT_p, mask)
    upd = jnp.einsum(
        "iab,jcb->ijac", pan_p, jnp.conj(panT_p) if cplx else panT_p,
        precision=PRECISE,
    ).astype(view.dtype)
    mask = lower
    if excl_kc is not None:
        ntl_v = lower.shape[1]
        mask = mask & (jnp.arange(ntl_v) != excl_kc)[None, :, None, None]
    return view - jnp.where(mask, upd, 0)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8, 9))
def _potrf_jit(at, mesh, p, q, nt, la, bi, ui, nm=False, n_true=0):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype
        cplx = jnp.issubdtype(dtype, jnp.complexfloating)
        r, c, _, _ = local_indices(p, q, mtl, ntl)
        rdt = num_gauge_dtype(dtype)  # Option.NumMonitor gauge carries

        def diag_probe(k, view, i_v, j_v):
            """Min Schur-complement diagonal entry of the not-yet-factored
            trailing part (logical tile >= k, true extent only) — the
            near-breakdown margin gauge.  Sampled at panel entry of step
            k, where tile (k, k)'s diagonal holds exactly the pivots the
            factor is about to take sqrt of."""
            dvals = jnp.einsum("ijaa->ija", jnp.real(view)).astype(rdt)
            gidx = i_v[:, None, None] * nb + jnp.arange(nb)[None, None, :]
            m = ((i_v[:, None] == j_v[None, :])[:, :, None]
                 & (i_v >= k)[:, None, None] & (gidx < n_true))
            return jnp.min(jnp.where(m, dvals, jnp.inf))

        def phases_on(i_log, j_log, roff, coff):
            """Panel / narrow / bulk phases of one right-looking step
            (the module-level ``_chol_*`` helpers, shared with the
            obs.flight step-dispatch drivers), restricted to a trailing
            view whose local tile (0, 0) is logical tile
            (i_log[0], j_log[0]) — the carry triple
            ``comm.pipelined_factor_loop`` schedules."""
            lower = (i_log[:, None] >= j_log[None, :])[:, :, None, None]

            def panel(k, view):
                view, pan_own = _chol_panel_compute(
                    view, k, p, q, i_log, c, cplx, roff, coff
                )
                # tag the broadcast half for the obs.schedule capture
                # (trace-time bookkeeping only; no jaxpr change)
                with phase_scope("bcast", k):
                    return view, _chol_panel_bcast(
                        pan_own, k, p, q, j_log, roff
                    )

            def narrow(k, view, payload):
                return _chol_narrow(view, payload, k, q, lower, cplx, coff)

            def bulk(k, view, payload):
                if k is None:
                    return _chol_bulk(view, payload, lower, cplx)
                return _chol_bulk(view, payload, lower, cplx, k // q - coff)

            return panel, narrow, bulk

        # Trailing-update bucketing: the masked full-size update costs ~3x
        # the optimal n^3/3; segmenting the k-range into comm.BUCKETS Python
        # buckets lets each run on a STATICALLY smaller trailing view
        # (finished tile rows/cols are sliced off between buckets), cutting
        # the masked flops to ~0.47x of full at 4 buckets (~1.4x optimal).
        # The reference gets the same effect from its shrinking task DAG
        # (potrf.cc:94).  Lookahead (Option.Lookahead) pipelines within
        # each bucket: the deferred update drains at the bucket boundary
        # before the view is re-sliced.

        margin = jnp.asarray(jnp.inf, rdt)
        for k0, k1, s0r, s0c in bucket_plan(nt, p, q):
            view = t_loc[s0r:, s0c:]
            i_log_v = r + (s0r + jnp.arange(mtl - s0r)) * p
            j_log_v = c + (s0c + jnp.arange(ntl - s0c)) * q
            panel, narrow, bulk = phases_on(i_log_v, j_log_v, s0r, s0c)
            zero_pl = (
                jnp.zeros((mtl - s0r, nb, nb), dtype),
                jnp.zeros((ntl - s0c, nb, nb), dtype),
            )
            if nm:
                # thread the margin gauge through the pipelined loop's
                # carry: probe at panel ENTRY (each pivot tile's column
                # was just refreshed by ``narrow``, so its sample is the
                # strict-schedule Schur diagonal at every depth); zero
                # extra collectives — the scalar rides the carry
                def panel_nm(k, st, panel=panel, i_v=i_log_v, j_v=j_log_v):
                    view, g = st
                    g = jnp.minimum(g, diag_probe(k, view, i_v, j_v))
                    view, pl = panel(k, view)
                    return (view, g), pl

                def narrow_nm(k, st, pl, narrow=narrow):
                    return (narrow(k, st[0], pl), st[1])

                def bulk_nm(k, st, pl, bulk=bulk):
                    return (bulk(k, st[0], pl), st[1])

                view, margin = pipelined_factor_loop(
                    k0, k1, la, panel_nm, narrow_nm, bulk_nm,
                    (view, margin), zero_pl
                )
            else:
                view = pipelined_factor_loop(
                    k0, k1, la, panel, narrow, bulk, view, zero_pl
                )
            t_loc = t_loc.at[s0r:, s0c:].set(view)

        _, _, i_log, j_log = local_indices(p, q, mtl, ntl)
        info = _chol_info_dist(t_loc, i_log, j_log, nt, nb)
        if nm:
            # final factor diag extrema + the carried margin, reduced once
            # at loop exit through the same unaudited pmin/pmax class the
            # info computation uses (no audited wire bytes)
            dvals = jnp.einsum("ijaa->ija", jnp.real(t_loc)).astype(rdt)
            gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :]
            dm = (i_log[:, None] == j_log[None, :])[:, :, None] & (gidx < n_true)
            lmin = jnp.min(jnp.where(dm, dvals, jnp.inf))
            lmax = jnp.max(jnp.where(dm, dvals, -jnp.inf))

            def allr(x, op):
                return op(op(x, ROW_AXIS), COL_AXIS)

            gauges = jnp.stack([
                allr(margin, lax.pmin), allr(lmin, lax.pmin),
                allr(lmax, lax.pmax),
            ])
            return t_loc, info[None, None], gauges[None, None]
        return t_loc, info[None, None]

    out_specs = (spec, P(ROW_AXIS, COL_AXIS))
    if nm:
        out_specs = out_specs + (P(ROW_AXIS, COL_AXIS),)
    with bcast_impl_scope(bi), update_impl_scope(ui):
        out = shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec,),
            out_specs=out_specs,
            check_vma=False,
        )(at)
    if nm:
        lt, info, gz = out
        return lt, jnp.max(info), gz[0, 0]
    lt, info = out
    return lt, jnp.max(info)


@instrument("pbtrf_band_dist")
def pbtrf_band_dist(
    a: DistMatrix, kd: int, lookahead: Optional[int] = None,
    bcast_impl: Optional[str] = None,
) -> Tuple[DistMatrix, jax.Array]:
    """Band Cholesky on the mesh at band cost (src/pbtrf.cc): the k-loop
    only ever touches the O(wd^2) tile window inside the bandwidth —
    tiles outside kd are never read or written (VERDICT r5 item 8), so
    total work is O(n (kd + nb)^2) (the nb term is tile granularity) and
    per-step communication O(wd nb^2) instead of the dense kernel's
    O(n^2)-class step.  ``a`` holds the lower triangle with bandwidth kd
    scalars (Cholesky preserves the band)."""
    p, q = mesh_shape(a.mesh)
    if a.mt != a.nt:
        raise ValueError("pbtrf_band_dist needs a square tile grid")
    a.require_diag_pad("pbtrf_band_dist")
    nb = a.nb
    # last tile row touched by column k*nb..k*nb+nb-1 under bandwidth kd
    wd = min(((nb - 1) + kd) // nb + 1, a.nt)
    lt, info = _pbtrf_band_jit(
        a.tiles, a.mesh, p, q, a.nt, wd, la_depth(lookahead, a.nt),
        resolve_bcast_impl(bcast_impl),
    )
    return DistMatrix(
        tiles=lt, m=a.m, n=a.n, nb=a.nb, mesh=a.mesh, diag_pad=True
    ), info


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _pbtrf_band_jit(at, mesh, p, q, nt, wd, la, bi):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc):
        mtl, ntl, nb, _ = t_loc.shape
        # local slots covering any wd-row/col window (clamped: a wide band
        # degenerates to the dense schedule)
        wlr = min(-(-wd // p) + 1, mtl)
        wlc = min(-(-wd // q) + 1, ntl)
        dtype = t_loc.dtype
        cplx = jnp.issubdtype(dtype, jnp.complexfloating)
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
        zero = jnp.zeros((), jnp.int32)

        def srow(k):
            """Local row-slot base covering logical tile rows [k, k+wd)."""
            return jnp.asarray(
                jnp.clip((k - r + p - 1) // p, 0, mtl - wlr), jnp.int32
            )

        def scol(k):
            return jnp.asarray(
                jnp.clip((k - c + q - 1) // q, 0, ntl - wlc), jnp.int32
            )

        def panel(k, t_loc):
            """Diag factor + windowed column trsm + panel broadcasts of
            step k.  The deferred-update payload carries its own window
            offsets (the band window slides with k, unlike the dense
            kernel's bucket-fixed view)."""
            kc = jnp.asarray(k // q, jnp.int32)
            dtile = bcast_diag_tile(t_loc, k, p, q, nb)
            lkk = lax.linalg.cholesky(
                dtile.astype(jnp.float32) if dtype == jnp.bfloat16 else dtile
            ).astype(dtype)
            s_r = srow(k)
            i_win = r + (s_r + jnp.arange(wlr)) * p
            colwin = lax.dynamic_slice(t_loc, (s_r, kc, zero, zero), (wlr, 1, nb, nb))[:, 0]
            lkk_h = jnp.conj(lkk).T if cplx else lkk.T
            solved = lax.linalg.triangular_solve(
                jnp.broadcast_to(lkk_h, colwin.shape), colwin,
                left_side=False, lower=False, transpose_a=False,
            )
            below = (i_win > k)[:, None, None]
            on_diag = (i_win == k)[:, None, None]
            newcol = jnp.where(below, solved, jnp.where(on_diag, lkk, colwin))
            mine = c == k % q
            t_loc = lax.dynamic_update_slice(
                t_loc, jnp.where(mine, newcol, colwin)[:, None], (s_r, kc, zero, zero)
            )
            pan = bcast_from_col(jnp.where(below & mine, newcol, 0), k % q)
            allpan = all_gather_a(pan, ROW_AXIS, axis=0)  # (p, wlr, nb, nb)

            s_c = scol(k)
            j_win = c + (s_c + jnp.arange(wlc)) * q
            slot0 = jnp.clip((k - jnp.arange(p) + p - 1) // p, 0, mtl - wlr)
            idx = j_win // p - slot0[j_win % p]
            valid = (idx >= 0) & (idx < wlr) & (j_win > k)
            panT = allpan[j_win % p, jnp.clip(idx, 0, wlr - 1)]
            panT = jnp.where(valid[:, None, None], panT, 0)
            return t_loc, (pan, panT, s_r, s_c)

        def narrow(k, t_loc, payload):
            """Refresh what panel(k) reads — the column-slot-k piece of
            the deferred step-(k-1) window update."""
            pan_p, panT_p, s_r_p, s_c_p = payload
            kc = jnp.asarray(k // q, jnp.int32)
            i_win_p = r + (s_r_p + jnp.arange(wlr)) * p
            jcol = c + q * kc  # logical column of my slot kc
            oc = kc - s_c_p  # column's offset inside the pending window
            in_win = (oc >= 0) & (oc < wlc)
            pT = lax.dynamic_slice_in_dim(
                panT_p, jnp.clip(oc, 0, wlc - 1), 1, axis=0
            )
            upd = jnp.einsum(
                "iab,jcb->ijac", pan_p, jnp.conj(pT) if cplx else pT,
                precision=PRECISE,
            ).astype(dtype)
            mask = (in_win & (i_win_p >= jcol))[:, None, None, None]
            colv = lax.dynamic_slice(
                t_loc, (s_r_p, kc, zero, zero), (wlr, 1, nb, nb)
            )
            return lax.dynamic_update_slice(
                t_loc, colv - jnp.where(mask, upd, 0), (s_r_p, kc, zero, zero)
            )

        def bulk(k, t_loc, payload):
            """The windowed trailing update at the payload's own offsets;
            k = None applies everywhere (strict/drain), otherwise the
            column slot narrow(k) refreshed is excluded."""
            pan_p, panT_p, s_r_p, s_c_p = payload
            i_win_p = r + (s_r_p + jnp.arange(wlr)) * p
            j_win_p = c + (s_c_p + jnp.arange(wlc)) * q
            upd = jnp.einsum(
                "iab,jcb->ijac", pan_p, jnp.conj(panT_p) if cplx else panT_p,
                precision=PRECISE,
            ).astype(dtype)
            mask = (i_win_p[:, None] >= j_win_p[None, :])[:, :, None, None]
            if k is not None:
                kc = jnp.asarray(k // q, jnp.int32)
                mask = mask & ((s_c_p + jnp.arange(wlc)) != kc)[None, :, None, None]
            win = lax.dynamic_slice(
                t_loc, (s_r_p, s_c_p, zero, zero), (wlr, wlc, nb, nb)
            )
            win = win - jnp.where(mask, upd, 0)
            return lax.dynamic_update_slice(t_loc, win, (s_r_p, s_c_p, zero, zero))

        zero_pl = (
            jnp.zeros((wlr, nb, nb), dtype),
            jnp.zeros((wlc, nb, nb), dtype),
            zero,
            zero,
        )
        t_loc = pipelined_factor_loop(
            0, nt, la, panel, narrow, bulk, t_loc, zero_pl
        )

        _, _, i_l, j_l = local_indices(p, q, mtl, ntl)
        diag_tiles = (i_l[:, None] == j_l[None, :])[:, :, None]
        dvals = jnp.einsum("ijaa->ija", jnp.real(t_loc))
        bad = (~jnp.isfinite(dvals) | (dvals <= 0)) & diag_tiles
        gidx = i_l[:, None, None] * nb + jnp.arange(nb)[None, None, :] + 1
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
            in_specs=(spec,),
            out_specs=(spec, P(ROW_AXIS, COL_AXIS)),
            check_vma=False,
        )(at)
    return lt, jnp.max(info)
