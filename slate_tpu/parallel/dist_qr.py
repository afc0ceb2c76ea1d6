"""Distributed CAQR (communication-avoiding QR) over the block-cyclic mesh.

TPU-native analogue of ``src/geqrf.cc:191-230`` + the ttqrt tree
``src/internal/internal_ttqrt.cc``: per tile-column panel,

1. each mesh ROW factors its local stack of panel tiles with one
   offset-pivot Householder QR (the rank-local ``internal::geqrf``), giving
   a local R at its first valid tile slot and reflectors packed below;
2. the per-row R factors are all_gathered over axis 'p' (p * nb * nb —
   tiny) and every device runs the SAME binary merge tree over them
   (replicated compute replaces the reference's pairwise MPI ttqrt rounds;
   with p <= 16 the tree is p-1 small (2nb, nb) QRs);
3. trailing columns get the local compact-WY update with zero
   communication (each device's reflectors span only its own rows), then
   the tree update on the p gathered "R-row" slices of C.

Factor storage mirrors LAPACK/SLATE: V packed below the R slots inside the
A tiles, the per-(row, panel) T_loc accumulators sharded over 'p', and the
tree (V2, T2) factors replicated — O(nt * p * 2nb^2) memory; a
triangular-packed variant (Tile_tpqrt.hh's implicit-identity top block)
would halve it and is left as an optimization note.

``unmqr_dist`` replays the stored factors against any conformally
distributed B (the ``internal::unmqr`` + ``internal::ttmqr`` pair), and
``gels_mesh`` composes Q^H B with an upper trsm_dist for least squares
(src/gels_qr.cc).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..linalg.qr import _larft, _larft_v, _panel_qr, _panel_qr_offset, _v_of
from ..obs import instrument
from ..obs.numerics import resolve_num_monitor
from ..types import Op
from .dist import DistMatrix
from .mesh import COL_AXIS, ROW_AXIS, mesh_shape
from .comm import (
    PRECISE,
    all_gather_a,
    audit_scope,
    bcast_from_col,
    bcast_impl_scope,
    local_indices,
    num_gauge_dtype,
    phase_scope,
    resolve_bcast_impl,
    shard_map_compat,
)


class DistQR(NamedTuple):
    """Distributed CAQR factors: ``fact`` holds R in the upper triangle and
    the local-QR reflectors packed below their R slots; ``tloc`` the
    per-(mesh-row, panel) WY accumulators; ``treev``/``treet`` the merge
    reflectors, indexed by (panel, merge id in tree order)."""

    fact: DistMatrix
    tloc: jax.Array  # (p * nt, nb, nb), sharded over 'p'
    treev: jax.Array  # (nt, p, 2nb, nb), replicated (merge-id slots)
    treet: jax.Array  # (nt, p, nb, nb)


def _tree_rounds(p: int) -> List[List[Tuple[int, int]]]:
    """Static binary-merge schedule over p participants."""
    rounds, d = [], 1
    while d < p:
        rounds.append([(r, r + d) for r in range(0, p, 2 * d) if r + d < p])
        d *= 2
    return rounds


def _merge_ids(p: int) -> List[List[int]]:
    """Merge-id numbering matching _tree_rounds order."""
    ids, nxt = [], 0
    for rnd in _tree_rounds(p):
        ids.append(list(range(nxt, nxt + len(rnd))))
        nxt += len(rnd)
    return ids


@instrument("geqrf_dist")
def geqrf_dist(a: DistMatrix, bcast_impl=None, num_monitor=None) -> DistQR:
    """Factor A = Q R across the mesh (m >= n).  ``bcast_impl``
    (Option.BcastImpl) picks the panel-broadcast lowering — the rooted
    ppermute engine or the legacy masked psum — bitwise-identical
    (PR 5's engine, threaded here per the ROADMAP "finish the collective
    story" item).

    ``num_monitor`` (Option.NumMonitor, ISSUE 15): ``on`` carries the
    per-panel reflector/τ orthogonality-loss proxy (``_qr_orth_loss``)
    as a running max through the FUSED k-loop — results stay bitwise,
    the gauge is local per mesh row so the only reduction is the
    unaudited exit pmax (the ``_lu_info_dist`` class) — recorded as the
    ``num.qr_orth_margin`` gauge, bitwise-equal to the checkpointed
    chain's gauge on the same operand.  ``off`` is jaxpr-IDENTICAL."""
    from ..obs import flight as _flight
    from ..obs import numerics as _num

    p, q = mesh_shape(a.mesh)
    if a.m < a.n:
        raise ValueError(f"geqrf_dist requires m >= n, got {a.m}x{a.n}")
    bi = resolve_bcast_impl(bcast_impl)
    nm = resolve_num_monitor(num_monitor) == "on"
    if _flight.step_dispatch_active():
        # flight-recorder step dispatch: same arithmetic, fenced per
        # phase (the per-phase programs carry no gauges — monitoring is
        # the fused kernels' surface, the potrf/LU contract)
        fact, tloc, tvs, tts = _flight.geqrf_steps(
            a.tiles, a.mesh, p, q, a.nt, a.m, a.n, bi)
        fd = DistMatrix(
            tiles=fact, m=a.m, n=a.n, nb=a.nb, mesh=a.mesh, diag_pad=True
        )
        return DistQR(fd, tloc, tvs, tts)
    if nm:
        fact, tloc, treev, treet, g = _geqrf_jit(
            a.tiles, a.mesh, p, q, a.nt, a.m, a.n, bi, True)
        _num.record_qr_orth("geqrf", jnp.max(g))
    else:
        fact, tloc, treev, treet = _geqrf_jit(
            a.tiles, a.mesh, p, q, a.nt, a.m, a.n, bi, False)
    fd = DistMatrix(
        tiles=fact, m=a.m, n=a.n, nb=a.nb, mesh=a.mesh, diag_pad=True
    )
    return DistQR(fd, tloc, treev[0, 0], treet[0, 0])


def _local_panel_geometry(k, r, p: int, mtl: int, nb: int):
    """(row0, has_rows): start of my first valid tile slot in the local
    flat row space for panel k, and whether I own any panel rows."""
    s0 = jnp.maximum(0, -(-(k - r) // p))  # ceil((k - r) / p), >= 0
    has = s0 < mtl
    return jnp.minimum(s0, mtl - 1) * nb, has


def _v_replay(panel_flat: jax.Array, row0, nb: int):
    """Reconstruct the local-QR reflectors from packed panel storage:
    strictly below the pivot rows, unit diagonal at row0 + j."""
    mfl = panel_flat.shape[0]
    fr = jnp.arange(mfl)[:, None]
    cj = jnp.arange(nb)[None, :]
    v = jnp.where(fr > row0 + cj, panel_flat, 0)
    unit = (fr == row0 + cj).astype(panel_flat.dtype)
    return v + unit


def _rot(k, p: int):
    """Participant rotation placing the panel's diagonal-owner mesh row at
    tree position 0, so the merged R collapses onto the diagonal tile."""
    return (k % p + jnp.arange(p)) % p


def _apply_tree_tops(tops, treev_k, treet_k, k, p, nb, adjoint: bool):
    """Apply the panel's merge tree to the gathered (p, nb, w) R-row
    slices (ordered by mesh row).  adjoint=True applies Q_tree^H (rounds
    ascending), False applies Q_tree (rounds descending, T un-transposed).
    Tree positions are the rotated participant order (_rot)."""
    rot = _rot(k, p)
    tops = tops[rot]
    rounds = _tree_rounds(p)
    mids = _merge_ids(p)
    order = range(len(rounds)) if adjoint else range(len(rounds) - 1, -1, -1)
    for d in order:
        for (root, partner), mid in zip(rounds[d], mids[d]):
            v2 = treev_k[mid]  # (2nb, nb)
            t2 = treet_k[mid]  # (nb, nb)
            t2 = jnp.conj(t2).T if adjoint else t2
            stacked = jnp.concatenate([tops[root], tops[partner]], axis=0)
            w = jnp.einsum("ri,rw->iw", jnp.conj(v2), stacked, precision=PRECISE)
            stacked = stacked - jnp.einsum(
                "ri,ij,jw->rw", v2, t2, w, precision=PRECISE
            ).astype(stacked.dtype)
            tops = tops.at[root].set(stacked[:nb]).at[partner].set(stacked[nb:])
    return tops[jnp.argsort(rot)]


def _qr_orth_loss(v, tl, rdt):
    """Cheap per-panel reflector/τ consistency margin — the QR-chain
    orthogonality-loss proxy gauge (ISSUE 14 satellite; ROADMAP
    "NumMonitor gauges through the QR/eig segment chains").

    For an exact compact-WY pair, T^{-1} + T^{-H} = V^H V, equivalently
    T (V^H V) T^H = T + T^H — an identity between quantities the panel
    step already holds (no extra factorization, no collective: V spans
    only this mesh row's rows and T was built FROM this V, so the
    identity is local).  Floating-point drift in that residual tracks
    the loss of orthogonality of the panel's implicit Q: ~eps for a
    healthy panel, growing when cancellation degrades the reflectors.
    Returned relative to max|T| in the gauge dtype."""
    s = jnp.einsum("ri,rj->ij", jnp.conj(v), v, precision=PRECISE)
    e = jnp.einsum("ij,jk,lk->il", tl, s, jnp.conj(tl),
                   precision=PRECISE) - tl - jnp.conj(tl).T
    denom = jnp.maximum(jnp.max(jnp.abs(tl)).astype(rdt),
                        jnp.asarray(jnp.finfo(rdt).tiny, rdt))
    return (jnp.max(jnp.abs(e)).astype(rdt) / denom)


def _qr_panel_factor(k, t_loc, p, q, m_true):
    """Local panel QR of step k (the pre-broadcast half of the panel
    phase): my stacked valid rows through the offset-pivot panel QR plus
    the compact-WY T, results masked to the owner column — exactly the
    bytes the broadcasts have always moved.  Module-level (the
    dist_chol/_lu phase-helper contract) so the fused loop, the
    checkpointed segments, and the flight recorder's per-step dispatches
    share one arithmetic."""
    mtl, ntl, nb, _ = t_loc.shape
    r, c, i_log, _j_log = local_indices(p, q, mtl, ntl)
    mfl = mtl * nb
    flat_gids = (i_log[:, None] * nb + jnp.arange(nb)[None, :]).reshape(-1)
    kc = k // q
    mine_c = c == k % q
    row0, _has = _local_panel_geometry(k, r, p, mtl, nb)
    pcol = lax.dynamic_slice_in_dim(t_loc, kc, 1, axis=1)[:, 0]
    flat = pcol.reshape(mfl, nb)
    valid = (flat_gids >= k * nb) & (flat_gids < m_true)
    masked = jnp.where((valid & mine_c)[:, None], flat, 0)
    r_a, v, tau = _panel_qr_offset(masked, row0)
    tl = _larft_v(v, tau)
    return (jnp.where(mine_c, r_a, 0), jnp.where(mine_c, v, 0),
            jnp.where(mine_c, tl, 0))


def _qr_panel_bcast(pan_own, k, q):
    """Share step k's panel factors across 'q' (three rooted column
    broadcasts — the comm-audit volume of the CAQR bcast phase) so every
    column runs the same trailing update."""
    r_a, v, tl = pan_own
    return (bcast_from_col(r_a, k % q), bcast_from_col(v, k % q),
            bcast_from_col(tl, k % q))


def _qr_panel_update(k, carry, pan, p, q, m_true):
    """The remainder of the strict-schedule panel step on the broadcast
    factors: packed V\\R write, local compact-WY trailing update, tree
    merge of the per-row R factors (the all_gather'd tree reduction),
    and the tree update on the gathered R-row slices of C."""
    t_loc, tls, tvs, tts = carry
    r_a, v, tl = pan
    mtl, ntl, nb, _ = t_loc.shape
    dtype = t_loc.dtype
    nmerge = tvs.shape[1]
    r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
    mfl = mtl * nb
    flat_gids = (i_log[:, None] * nb + jnp.arange(nb)[None, :]).reshape(-1)
    kc = k // q
    mine_c = c == k % q
    row0, has_rows = _local_panel_geometry(k, r, p, mtl, nb)
    pcol = lax.dynamic_slice_in_dim(t_loc, kc, 1, axis=1)[:, 0]
    flat = pcol.reshape(mfl, nb)
    valid = (flat_gids >= k * nb) & (flat_gids < m_true)

    # ---- write packed V\R into the panel column ----
    fr = jnp.arange(mfl)[:, None]
    cj = jnp.arange(nb)[None, :]
    packed = r_a + jnp.where(fr > row0 + cj, v, 0)
    packed = jnp.where(valid[:, None], packed, flat)
    t_loc = lax.dynamic_update_slice_in_dim(
        t_loc,
        jnp.where(mine_c, packed, flat).reshape(mtl, 1, nb, nb),
        kc,
        axis=1,
    )

    # ---- local trailing update: C -= V T^H (V^H C), cols > k ----
    cflat = jnp.transpose(t_loc, (0, 2, 1, 3)).reshape(mfl, ntl * nb)
    w1 = jnp.einsum("ri,rw->iw", jnp.conj(v), cflat, precision=PRECISE)
    upd = jnp.einsum(
        "ri,ij,jw->rw", v, jnp.conj(tl).T, w1, precision=PRECISE
    ).astype(dtype)
    colmask = jnp.repeat(j_log > k, nb)[None, :]
    cflat = cflat - jnp.where(colmask, upd, 0)

    # ---- tree merge of the per-row local R factors, in rotated
    # participant order (diag owner = tree root) ----
    rblk = lax.dynamic_slice(r_a, (row0, jnp.zeros_like(row0)), (nb, nb))
    rblk = jnp.where(has_rows, jnp.triu(rblk), 0)
    rs = all_gather_a(rblk, ROW_AXIS, axis=0)[_rot(k, p)]
    tv = jnp.zeros((nmerge, 2 * nb, nb), dtype)
    tt = jnp.zeros((nmerge, nb, nb), dtype)
    for rnd, midl in zip(_tree_rounds(p), _merge_ids(p)):
        for (root, partner), mid in zip(rnd, midl):
            stack = jnp.concatenate([rs[root], rs[partner]], axis=0)
            vr2, tau2 = _panel_qr(stack)
            t2 = _larft(vr2, tau2)
            tv = tv.at[mid].set(_v_of(vr2))
            tt = tt.at[mid].set(t2)
            rs = rs.at[root].set(jnp.triu(vr2[:nb]))

    # ---- tree update on the gathered R-row slices of C (cols > k
    # only: earlier columns hold finished R/V history) ----
    myrow = lax.dynamic_slice(cflat, (row0, jnp.zeros_like(row0)), (nb, ntl * nb))
    myrow0 = jnp.where(has_rows, myrow, 0)
    tops = all_gather_a(myrow0, ROW_AXIS, axis=0)  # (p, nb, w)
    tops = _apply_tree_tops(tops, tv, tt, k, p, nb, adjoint=True)
    newrow = jnp.where(has_rows & colmask, tops[r], myrow)
    cflat = lax.dynamic_update_slice(cflat, newrow, (row0, jnp.zeros_like(row0)))
    t_loc = jnp.transpose(cflat.reshape(mtl, nb, ntl, nb), (0, 2, 1, 3))
    # the diag-owner row overwrites its R slot's upper triangle
    # with the tree-final R (its V entries below stay)
    final_r = rs[0]
    mine_diag = (r == k % p) & mine_c
    pcol2 = lax.dynamic_slice_in_dim(t_loc, kc, 1, axis=1)[:, 0]
    pflat = pcol2.reshape(mfl, nb)
    cur = lax.dynamic_slice(pflat, (row0, jnp.zeros_like(row0)), (nb, nb))
    tri = jnp.arange(nb)[:, None] <= jnp.arange(nb)[None, :]
    newblk = jnp.where(tri & mine_diag, final_r, cur)
    pflat = lax.dynamic_update_slice(pflat, newblk, (row0, jnp.zeros_like(row0)))
    t_loc = lax.dynamic_update_slice_in_dim(
        t_loc, pflat.reshape(mtl, 1, nb, nb), kc, axis=1
    )
    return (t_loc, tls.at[k].set(tl), tvs.at[k].set(tv), tts.at[k].set(tt))


def _qr_panel_step(k, carry, p, q, m_true, nm=False):
    """One CAQR panel step of the strict schedule on the full local view
    (carry = (tile stack, T_loc stack, tree-V stack, tree-T stack)) —
    the composition of the module-level phase helpers above, with
    ``phase_scope`` tags (pure trace-time bookkeeping, no jaxpr change)
    so one ``sched_audit`` trace of the fused kernel yields the
    per-phase communication schedule the flight recorder's
    ``ScheduleModel`` consumes.

    Module-level so the fused ``_geqrf_jit`` loop and the checkpointed
    segment chain (``ft/ckpt._qr_seg_jit``) run the IDENTICAL per-element
    arithmetic — chained segments reproduce the fused kernel bitwise at
    any boundary set (the dist_chol/_lu step-helper contract).

    ``nm=True`` (the monitored fused loop and segment chain,
    ``ft/ckpt._qr_seg_nm_jit``) additionally returns this step's
    ``_qr_orth_loss`` scalar; the default leaves the computation — and
    hence the fused kernel's and the plain chain's jaxpr — untouched."""
    with phase_scope("panel", k):
        pan_own = _qr_panel_factor(k, carry[0], p, q, m_true)
    with phase_scope("bcast", k):
        pan = _qr_panel_bcast(pan_own, k, q)
    with phase_scope("bulk", k):
        out = _qr_panel_update(k, carry, pan, p, q, m_true)
    if nm:
        return out, _qr_orth_loss(pan[1], pan[2],
                                  num_gauge_dtype(carry[0].dtype))
    return out


def _qr_pad_identity(t_loc, p, q, n_true, dtype):
    """Identity on the padded diagonal so R solves stay nonsingular —
    the fused kernel's exit computation, shared with the segment chain's
    finalize jit (elementwise, hence bitwise at any boundary set)."""
    mtl, ntl, nb, _ = t_loc.shape
    _, _, i_log, j_log = local_indices(p, q, mtl, ntl)
    diag_tiles = (i_log[:, None] == j_log[None, :])[:, :, None]
    gd = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :]
    padd = diag_tiles & (gd >= n_true)  # (mtl, ntl, nb)
    ondiag = jnp.arange(nb)[:, None] == jnp.arange(nb)[None, :]
    dmask = padd[:, :, :, None] & ondiag[None, None]
    return jnp.where(dmask, jnp.ones((), dtype), t_loc)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _geqrf_jit(at, mesh, p, q, nt, m_true, n_true, bi, nm):
    spec = P(ROW_AXIS, COL_AXIS)
    nmerge = max(1, p)

    def kernel(t_loc):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype

        tls0 = jnp.zeros((nt, nb, nb), dtype)
        tvs0 = jnp.zeros((nt, nmerge, 2 * nb, nb), dtype)
        tts0 = jnp.zeros((nt, nmerge, nb, nb), dtype)
        if not nm:
            def panel_step(k, carry):
                return _qr_panel_step(k, carry, p, q, m_true)

            with audit_scope(nt):
                t_loc, tls, tvs, tts = lax.fori_loop(
                    0, nt, panel_step, (t_loc, tls0, tvs0, tts0)
                )
            t_loc = _qr_pad_identity(t_loc, p, q, n_true, at.dtype)
            return t_loc, tls, tvs[None, None], tts[None, None]

        # monitored loop (ISSUE 15): the per-panel orthogonality-loss
        # proxy rides the carry as a running max — same step arithmetic,
        # one unaudited exit pmax (the _lu_info_dist class), so the
        # audited wire bytes are unchanged and the gauge is bitwise-
        # equal to the checkpointed chain's (max folds are exact)
        rdt = num_gauge_dtype(dtype)

        def panel_step_nm(k, carry):
            *st4, gg = carry
            out4, loss = _qr_panel_step(k, tuple(st4), p, q, m_true,
                                        nm=True)
            return out4 + (jnp.maximum(gg, loss),)

        with audit_scope(nt):
            t_loc, tls, tvs, tts, gg = lax.fori_loop(
                0, nt, panel_step_nm,
                (t_loc, tls0, tvs0, tts0, jnp.zeros((), rdt))
            )
        t_loc = _qr_pad_identity(t_loc, p, q, n_true, at.dtype)
        gg = lax.pmax(lax.pmax(gg, ROW_AXIS), COL_AXIS)
        return (t_loc, tls, tvs[None, None], tts[None, None],
                gg[None, None])

    out_specs = (spec, P(ROW_AXIS), P(ROW_AXIS, COL_AXIS),
                 P(ROW_AXIS, COL_AXIS))
    if nm:
        out_specs = out_specs + (P(ROW_AXIS, COL_AXIS),)
    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec,),
            out_specs=out_specs,
            check_vma=False,
        )(at)


@instrument("unmqr_dist")
def unmqr_dist(
    f: DistQR, b: DistMatrix, op: Op = Op.ConjTrans, bcast_impl=None
) -> DistMatrix:
    """B <- Q^H B (op=ConjTrans) or Q B (op=NoTrans) from CAQR factors.
    ``bcast_impl`` as in :func:`geqrf_dist`."""
    a = f.fact
    p, q = mesh_shape(a.mesh)
    if b.mt != a.mt or b.nb != a.nb or b.grid != a.grid:
        raise ValueError("unmqr_dist operand mismatch")
    bt = _unmqr_jit(
        a.tiles, f.tloc, f.treev, f.treet, b.tiles, a.mesh, p, q, a.nt,
        a.m, op == Op.ConjTrans, resolve_bcast_impl(bcast_impl),
    )
    return DistMatrix(tiles=bt, m=b.m, n=b.n, nb=b.nb, mesh=b.mesh)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11))
def _unmqr_jit(at, tloc, treev, treet, bt, mesh, p, q, nt, m_true, adjoint, bi):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(a_loc, tls, tvs, tts, b_loc):
        mtl, nbt, nb, _ = a_loc.shape
        ntl_b = b_loc.shape[1]
        dtype = b_loc.dtype
        r, c, i_log, _ = local_indices(p, q, mtl, ntl_b)
        mfl = mtl * nb
        flat_gids = (i_log[:, None] * nb + jnp.arange(nb)[None, :]).reshape(-1)

        def apply_panel(k, b_loc):
            kc = k // q
            mine_c = c == k % q
            row0, has_rows = _local_panel_geometry(k, r, p, mtl, nb)
            pcol = lax.dynamic_slice_in_dim(a_loc, kc, 1, axis=1)[:, 0]
            flat = pcol.reshape(mfl, nb)
            valid = (flat_gids >= k * nb) & (flat_gids < m_true)
            flat = jnp.where((valid & mine_c)[:, None], flat, 0)
            flat = bcast_from_col(flat, k % q)
            v = _v_replay(flat, row0, nb)
            v = jnp.where(valid[:, None], v, 0)
            tl = tls[k]
            tv, tt = tvs[k], tts[k]
            bflat = jnp.transpose(b_loc, (0, 2, 1, 3)).reshape(mfl, ntl_b * nb)

            def local_apply(bflat):
                t_eff = jnp.conj(tl).T if adjoint else tl
                w1 = jnp.einsum("ri,rw->iw", jnp.conj(v), bflat, precision=PRECISE)
                upd = jnp.einsum(
                    "ri,ij,jw->rw", v, t_eff, w1, precision=PRECISE
                ).astype(dtype)
                return bflat - upd

            def tree_apply(bflat):
                myrow = lax.dynamic_slice(bflat, (row0, jnp.zeros_like(row0)), (nb, ntl_b * nb))
                # gather a ZEROED copy for rowless devices, but fall back to
                # the untouched rows on write-back — clobbering with the
                # zeroed copy wipes whatever tile row0 clamped onto
                myrow0 = jnp.where(has_rows, myrow, 0)
                tops = all_gather_a(myrow0, ROW_AXIS, axis=0)
                tops = _apply_tree_tops(tops, tv, tt, k, p, nb, adjoint=adjoint)
                newrow = jnp.where(has_rows, tops[r], myrow)
                return lax.dynamic_update_slice(bflat, newrow, (row0, jnp.zeros_like(row0)))

            if adjoint:  # Q^H = Q_tree^H Q_loc^H
                bflat = tree_apply(local_apply(bflat))
            else:  # Q = Q_loc Q_tree
                bflat = local_apply(tree_apply(bflat))
            return jnp.transpose(bflat.reshape(mtl, nb, ntl_b, nb), (0, 2, 1, 3))

        def step(s, b_loc):
            k = s if adjoint else nt - 1 - s
            return apply_panel(k, b_loc)

        with audit_scope(nt):
            return lax.fori_loop(0, nt, step, b_loc)

    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel,
            mesh=mesh,
            in_specs=(spec, P(ROW_AXIS), P(), P(), spec),
            out_specs=spec,
            check_vma=False,
        )(at, tloc, treev, treet, bt)
