"""Distributed triangular solve over the block-cyclic mesh.

TPU-native analogue of ``src/trsm.cc`` / ``src/internal/internal_trsm.cc``
run on a distributed B: block forward/backward substitution where per tile
row k — diag-tile solve on the owning mesh row, broadcast of the solved RHS
row along axis 'p', broadcast of the A panel along axis 'q' (or the
transpose-gather for op != NoTrans, cf. dist_chol.py), one masked batched
einsum update.  All four (uplo, op) combinations share one kernel body with
trace-time flags.  ``trsm_dist`` is the left-side solve;
``trsm_dist_right`` mirrors it over B's tile columns for X op(A) = B
(internal_trsmA's right-side variants) — no transposing redistribution
needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..obs import instrument
from ..types import Diag, MethodTrsm, Op, Side, Uplo, select_trsm_method
from .dist import DistMatrix
from .mesh import COL_AXIS, ROW_AXIS, mesh_shape
from .comm import (
    PRECISE,
    all_gather_a,
    bcast_diag_tile,
    bcast_from_col,
    bcast_from_row,
    bcast_impl_scope,
    la_depth,
    local_indices,
    prefetch_bcast,
    psum_scatter_a,
    resolve_bcast_impl,
    route_to_block_cyclic_rows,
    shard_map_compat,
)

from typing import Optional


@instrument("trsm_dist")
def trsm_dist(
    a: DistMatrix,
    b: DistMatrix,
    uplo: Uplo = Uplo.Lower,
    op: Op = Op.NoTrans,
    diag: Diag = Diag.NonUnit,
    method: Optional[MethodTrsm] = None,
    lookahead: Optional[int] = None,
    bcast_impl: Optional[str] = None,
) -> DistMatrix:
    """Solve op(A) X = B; A triangular-distributed, B distributed. X
    overwrites B's layout (left side; alpha folded by callers).

    ``method`` picks the communication schedule (slate::trsm's MethodTrsm,
    method.hh:88-99): TrsmB broadcasts the A panel to B's owners each
    step; TrsmA keeps A's tiles stationary — the solved X row is
    replicated, A's owners compute the update partials in place, and
    psum-scatters deliver each owner exactly its own tiles (for the
    transposed ops, routed per target row by
    comm.route_to_block_cyclic_rows) — the win when B is far thinner
    than A.  All (uplo, op) combinations run the stationary schedule
    (src/trsmA.cc covers every op).  None = auto-select.

    ``lookahead`` (Option.Lookahead; None = the option default, 1): A is
    read-only here, so its per-step panels (diag tile + column/row panel)
    are prefetched ``lookahead`` steps ahead through
    ``comm.prefetch_bcast`` — the broadcast for step k + d overlaps the
    serial solve/update chain of step k.  Bitwise-identical at any
    depth."""
    p, q = mesh_shape(a.mesh)
    if b.grid != a.grid or b.nb != a.nb or b.mt != a.nt or b.m != a.n:
        raise ValueError(
            f"trsm_dist operands mismatch: A {a.m}x{a.n} nb={a.nb} grid={a.grid}, "
            f"B {b.m}x{b.n} nb={b.nb} grid={b.grid}"
        )
    a.require_diag_pad("trsm_dist")
    if method is None:
        method = select_trsm_method(Side.Left, b.mt, b.nt)
    la = la_depth(lookahead, a.nt)
    bi = resolve_bcast_impl(bcast_impl)
    from ..obs import flight as _flight

    if method == MethodTrsm.TrsmA:
        # stationary-A's psum-scatter delivery has no per-step broadcast
        # phase to fence — flight step dispatch covers TrsmB only
        xt = _trsm_a_jit(
            a.tiles, b.tiles, a.mesh, p, q, a.nt, uplo, op, diag, la, bi
        )
    elif _flight.step_dispatch_active():
        xt = _flight.trsm_steps(
            a.tiles, b.tiles, a.mesh, p, q, a.nt, uplo, op, diag, la, bi
        )
    else:
        xt = _trsm_jit(
            a.tiles, b.tiles, a.mesh, p, q, a.nt, uplo, op, diag, la, bi
        )
    return DistMatrix(tiles=xt, m=b.m, n=b.n, nb=b.nb, mesh=b.mesh)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _trsm_a_jit(at, bt, mesh, p, q, nt, uplo, op, diag, la=0, bi="psum"):
    """Stationary-A left solve, all ops (slate::trsmA, src/trsmA.cc
    semantics): per step the solved X row is all-gathered and multiplied
    against A's stationary tiles where they live — column k of A for
    op = NoTrans, row k (transposed per tile) otherwise — then the
    partials are routed to B's block-cyclic owners: a psum-scatter over
    the column axis for NoTrans, and the shared slot-scatter +
    double-psum-scatter delivery (comm.route_to_block_cyclic_rows) for
    the transposed ops, whose source row k % p differs from the
    destination rows i % p.  A never moves."""
    spec = P(ROW_AXIS, COL_AXIS)
    trans = op != Op.NoTrans
    conj = op == Op.ConjTrans
    eff_lower = (uplo == Uplo.Lower) != trans
    forward = eff_lower
    unit = diag == Diag.Unit

    def kernel(a_loc, b_loc):
        mtl, ntl, nb, _ = a_loc.shape
        mtl_b, ntl_b = b_loc.shape[0], b_loc.shape[1]
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)

        def opt(t):  # apply op to one tile (or a stack of tiles)
            t = jnp.swapaxes(t, -1, -2)
            return jnp.conj(t) if conj else t

        def fetch(s):
            # the stationary-A schedule's only read-only broadcast is the
            # diag tile; the solved-row replication is a serial chain
            k = s if forward else nt - 1 - s
            dtile = bcast_diag_tile(a_loc, k, p, q, nb)
            return opt(dtile) if trans else dtile

        def consume(s, dtile, b_loc):
            k = s if forward else nt - 1 - s
            kr, kc = k // p, k // q

            # solve X[k,:] on the owning mesh row, write back
            brow = lax.dynamic_slice_in_dim(b_loc, kr, 1, axis=0)[0]
            xrow = lax.linalg.triangular_solve(
                jnp.broadcast_to(dtile, brow.shape), brow,
                left_side=True, lower=eff_lower, transpose_a=False,
                unit_diagonal=unit,
            )
            mine_r = (r == k % p)
            b_loc = lax.dynamic_update_slice_in_dim(
                b_loc, jnp.where(mine_r, xrow, brow)[None], kr, axis=0
            )
            # replicate the solved row: every device needs it to multiply
            # against A's stationary tiles
            xrow = bcast_from_row(jnp.where(mine_r, xrow, 0), k % p)
            xfull = all_gather_a(xrow, COL_AXIS, axis=0)  # (q, ntl_b, nb, nb)

            if not trans:
                # owner-computes: only mesh column k % q holds A[:, k]
                remaining = (i_log > k) if forward else (i_log < k)
                acol = lax.dynamic_slice_in_dim(a_loc, kc, 1, axis=1)[:, 0]
                mine_c = (c == k % q)
                acol = jnp.where(remaining[:, None, None] & mine_c, acol, 0)
                part = jnp.einsum(
                    "iab,Jjbc->iJjac", acol, xfull, precision=PRECISE
                )  # (mtl, q, ntl_b, nb, nb)
                # reduce the partials over columns, scattering slice J to
                # mesh column J (each device receives only its own tiles)
                upd = psum_scatter_a(
                    part, COL_AXIS, scatter_dimension=1, tiled=False
                )
                return b_loc - upd.astype(b_loc.dtype)

            # op != NoTrans: op(A)[i, k] = op(A[k, i]) — the stationary
            # tiles are A's ROW k, held by mesh row k % p spread over the
            # columns i % q; the partial for output row i must reach mesh
            # row i % p (generally != k % p), so partials are scattered
            # into per-target-row slots and psum-scattered on both axes
            remaining = (j_log > k) if forward else (j_log < k)
            arow = lax.dynamic_slice_in_dim(a_loc, kr, 1, axis=0)[0]  # (ntl,nb,nb)
            pan = opt(arow)
            pan = jnp.where(remaining[:, None, None] & mine_r, pan, 0)
            part = jnp.einsum(
                "tab,Jjbc->tJjac", pan, xfull, precision=PRECISE
            )  # (ntl, q, ntl_b, nb, nb); slot t targets output row j_log[t]
            upd = route_to_block_cyclic_rows(part, j_log, p, mtl_b)
            return b_loc - upd.astype(b_loc.dtype)

        return prefetch_bcast(nt, la, fetch, consume, b_loc)

    with bcast_impl_scope(bi), jax.named_scope("trsm"):
        return shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            check_vma=False,
        )(at, bt)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _trsm_jit(at, bt, mesh, p, q, nt, uplo, op, diag, la=0, bi="psum"):
    spec = P(ROW_AXIS, COL_AXIS)
    trans = op != Op.NoTrans
    conj = op == Op.ConjTrans
    # effective triangle of op(A)
    eff_lower = (uplo == Uplo.Lower) != trans
    forward = eff_lower  # forward substitution iff op(A) is lower
    unit = diag == Diag.Unit

    def kernel(a_loc, b_loc):
        mtl, ntl, nb, _ = a_loc.shape
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)

        def opt(t):  # apply op to one tile (or a stack of tiles)
            t = jnp.swapaxes(t, -1, -2)
            return jnp.conj(t) if conj else t

        def fetch(s):
            # A is stationary: the diag tile and the op(A) panel of step
            # s are pure functions of a_loc, prefetchable at any depth
            k = s if forward else nt - 1 - s
            kr, kc = k // p, k // q

            dtile = bcast_diag_tile(a_loc, k, p, q, nb)
            if trans:
                dtile = opt(dtile)

            # panel of op(A)[:, k] by my local row indices, remaining side only
            remaining = (i_log > k) if forward else (i_log < k)
            if not trans:
                acol = lax.dynamic_slice_in_dim(a_loc, kc, 1, axis=1)[:, 0]
                mine_c = (c == k % q)
                pan = bcast_from_col(
                    jnp.where(remaining[:, None, None] & mine_c, acol, 0), k % q
                )
            else:
                # op(A)[i,k] = op(A[k,i]): transpose-gather of A row k
                arow = lax.dynamic_slice_in_dim(a_loc, kr, 1, axis=0)[0]
                mine_r2 = (r == k % p)
                arow = bcast_from_row(jnp.where(mine_r2, arow, 0), k % p)
                allrow = all_gather_a(arow, COL_AXIS, axis=0)  # (q,ntl,nb,nb)
                pan = opt(allrow[i_log % q, i_log // q])
                pan = jnp.where(remaining[:, None, None], pan, 0)
            return dtile, pan

        def consume(s, panels, b_loc):
            k = s if forward else nt - 1 - s
            kr = k // p
            dtile, pan = panels

            # solve X[k,:] on the owning mesh row, write back, bcast down 'p'
            brow = lax.dynamic_slice_in_dim(b_loc, kr, 1, axis=0)[0]  # (nbt,nb,nb)
            xrow = lax.linalg.triangular_solve(
                jnp.broadcast_to(dtile, brow.shape), brow,
                left_side=True, lower=eff_lower, transpose_a=False,
                unit_diagonal=unit,
            )
            mine_r = (r == k % p)
            b_loc = lax.dynamic_update_slice_in_dim(
                b_loc, jnp.where(mine_r, xrow, brow)[None], kr, axis=0
            )
            xrow = bcast_from_row(jnp.where(mine_r, xrow, 0), k % p)

            upd = jnp.einsum("iab,jbc->ijac", pan, xrow, precision=PRECISE)
            return b_loc - upd.astype(b_loc.dtype)

        return prefetch_bcast(nt, la, fetch, consume, b_loc)

    with bcast_impl_scope(bi), jax.named_scope("trsm"):
        return shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            check_vma=False,
        )(at, bt)


@instrument("trsm_dist_right")
def trsm_dist_right(
    a: DistMatrix,
    b: DistMatrix,
    uplo: Uplo = Uplo.Lower,
    op: Op = Op.NoTrans,
    diag: Diag = Diag.NonUnit,
    lookahead: Optional[int] = None,
    bcast_impl: Optional[str] = None,
) -> DistMatrix:
    """Solve X op(A) = B; A triangular-distributed (n, n), B (m, n).
    X overwrites B's layout.  ``lookahead`` prefetches A's read-only
    per-step panels, as in trsm_dist."""
    p, q = mesh_shape(a.mesh)
    if b.grid != a.grid or b.nb != a.nb or b.nt != a.nt or b.n != a.m:
        raise ValueError(
            f"trsm_dist_right operands mismatch: A {a.m}x{a.n} nb={a.nb}, "
            f"B {b.m}x{b.n} nb={b.nb}"
        )
    a.require_diag_pad("trsm_dist_right")
    xt = _trsm_right_jit(
        a.tiles, b.tiles, a.mesh, p, q, a.nt, uplo, op, diag,
        la_depth(lookahead, a.nt), resolve_bcast_impl(bcast_impl),
    )
    return DistMatrix(tiles=xt, m=b.m, n=b.n, nb=b.nb, mesh=b.mesh)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _trsm_right_jit(at, bt, mesh, p, q, nt, uplo, op, diag, la=0, bi="psum"):
    spec = P(ROW_AXIS, COL_AXIS)
    trans = op != Op.NoTrans
    conj = op == Op.ConjTrans
    eff_lower = (uplo == Uplo.Lower) != trans
    # X A = B with op(A) upper: X's leading columns close first -> forward
    forward = not eff_lower
    unit = diag == Diag.Unit

    def kernel(a_loc, b_loc):
        mtl_a, ntl_a, nb, _ = a_loc.shape
        r, c, _, j_log_b = local_indices(p, q, mtl_a, ntl_a)

        def opt(t):
            t = jnp.swapaxes(t, -1, -2)
            return jnp.conj(t) if conj else t

        def fetch(s):
            # A is stationary: diag tile + row panel of op(A) prefetch
            k = s if forward else nt - 1 - s
            kr, kc = k // p, k // q

            dtile = bcast_diag_tile(a_loc, k, p, q, nb)
            if trans:
                dtile = opt(dtile)

            # row k of op(A) restricted to the remaining columns
            remaining = (j_log_b > k) if forward else (j_log_b < k)
            if not trans:
                arow = lax.dynamic_slice_in_dim(a_loc, kr, 1, axis=0)[0]
                mine_r = (r == k % p)
                arow = bcast_from_row(jnp.where(mine_r, arow, 0), k % p)
                arow = jnp.where(remaining[:, None, None], arow, 0)
            else:
                # op(A)[k, j] = op(A[j, k]): transpose-gather of A column k
                acol = lax.dynamic_slice_in_dim(a_loc, kc, 1, axis=1)[:, 0]
                mine_c2 = (c == k % q)
                acol = bcast_from_col(jnp.where(mine_c2, acol, 0), k % q)
                allcol = all_gather_a(acol, ROW_AXIS, axis=0)  # (p,mtl,nb,nb)
                arow = opt(allcol[j_log_b % p, j_log_b // p])
                arow = jnp.where(remaining[:, None, None], arow, 0)
            return dtile, arow

        def consume(s, panels, b_loc):
            k = s if forward else nt - 1 - s
            kc = k // q
            dtile, arow = panels

            # solve X[:, k] on the owning mesh column, write back, bcast 'q'
            bcol = lax.dynamic_slice_in_dim(b_loc, kc, 1, axis=1)[:, 0]
            xcol = lax.linalg.triangular_solve(
                jnp.broadcast_to(dtile, bcol.shape), bcol,
                left_side=False, lower=eff_lower, transpose_a=False,
                unit_diagonal=unit,
            )
            mine_c = (c == k % q)
            b_loc = lax.dynamic_update_slice_in_dim(
                b_loc, jnp.where(mine_c, xcol, bcol)[:, None], kc, axis=1
            )
            xcol = bcast_from_col(jnp.where(mine_c, xcol, 0), k % q)

            upd = jnp.einsum("iab,jbc->ijac", xcol, arow, precision=PRECISE)
            return b_loc - upd.astype(b_loc.dtype)

        return prefetch_bcast(nt, la, fetch, consume, b_loc)

    with bcast_impl_scope(bi), jax.named_scope("trsm"):
        return shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
            check_vma=False,
        )(at, bt)
