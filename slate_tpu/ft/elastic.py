"""Elastic resume: continue a checkpointed factorization on a (possibly
reshaped) mesh (ISSUE 12 — the ambitious half of checkpoint/restart).

Preemption at pod scale usually hands back a DIFFERENT mesh: ``resume``
rebuilds the snapshot's carry on whatever grid the scheduler granted and
runs the remaining k-loop segments.  Three carry-rebuild tiers:

- same grid: the snapshot bytes are device_put back verbatim (bitwise
  trivially);
- reshaped grid over the same device count: the checkpoint's original
  grid is reconstructed over the new mesh's devices and the carry moves
  through the shard_map ppermute redistribution
  (``parallel.dist.redistribute(impl='shardmap')`` — per-device memory
  one source + one destination block, comm-audited, exact bytes), which
  doubles as the serving layer's multi-tenant rebalancing primitive;
- anything else (device count changed, original grid unreachable): the
  host relayout of the logical tile grid — still exact byte moves, just
  not memory-distributed.

Either way the resumed run is BITWISE equal to the uninterrupted one:
pad tiles carry identity diagonals and exact-zero updates, so the data
region is invariant under re-padding for a different mesh lcm, and the
pp row permutation re-bases onto the new padded row space by copying
its (fixed-point-beyond-data) prefix.  Recovery cost lands in the
``ft.ckpt_*`` counters.
"""

from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..parallel.dist import (
    DistMatrix,
    fresh_pad_diag_range,
    padded_tiles,
    redistribute,
    redistribute_wire_bytes,
)
from ..parallel.mesh import make_mesh, mesh_shape, tile_sharding
from ..types import SlateError
from . import ckpt as _ckpt
from .ckpt import Checkpoint
from .policy import count


def resumable(ck: Optional[Checkpoint]) -> bool:
    """True when ``ck`` is a snapshot this module can continue."""
    return ck is not None and ck.op in _ckpt.CKPT_OPS


def _regrow(logi: np.ndarray, mt2: int, nt2: int, nb: int,
            diag_pad: bool) -> np.ndarray:
    """Crop/grow a LOGICAL-order tile grid to the target padded extent;
    grown pad tiles get the identity diagonal (the factorization padding
    contract).  Pure byte moves + fresh identity tiles — exact."""
    mt1, nt1 = logi.shape[:2]
    if (mt1, nt1) == (mt2, nt2):
        return logi
    out = np.zeros((mt2, nt2, nb, nb), logi.dtype)
    out[: min(mt1, mt2), : min(nt1, nt2)] = \
        logi[: min(mt1, mt2), : min(nt1, nt2)]
    if diag_pad:
        for t in range(*fresh_pad_diag_range(mt1, nt1, mt2, nt2)):
            out[t, t] = np.eye(nb, dtype=logi.dtype)
    return out


def _carry_to_mesh(ck: Checkpoint, mesh: Mesh, mt2: int, nt2: int
                   ) -> DistMatrix:
    p1, q1 = ck.grid
    p2, q2 = mesh_shape(mesh)
    nb = ck.nb
    if (p1, q1) == (p2, q2):
        cyc = _ckpt._logical_to_cyclic(ck.tiles, p1, q1)
        t = jax.device_put(jnp.asarray(cyc), tile_sharding(mesh))
        return DistMatrix(tiles=t, m=ck.m, n=ck.n, nb=nb, mesh=mesh,
                          diag_pad=True)
    devs = list(mesh.devices.flatten())
    if p1 * q1 == len(devs):
        # reshaped grid, same device count: land the snapshot in its
        # ORIGINAL layout and move it with the distributed shard_map
        # exchange — the per-device-memory-respecting path
        mesh1 = make_mesh(p1, q1, devices=devs)
        cyc1 = _ckpt._logical_to_cyclic(ck.tiles, p1, q1)
        d1 = DistMatrix(
            tiles=jax.device_put(jnp.asarray(cyc1), tile_sharding(mesh1)),
            m=ck.m, n=ck.n, nb=nb, mesh=mesh1, diag_pad=True,
        )
        d2 = redistribute(d1, mesh, impl="shardmap")
        count("ft.ckpt_redistribute_bytes", ck.op, float(
            redistribute_wire_bytes(d1.tiles.shape, p1, q1,
                                    d1.dtype.itemsize)))
        return d2
    # original grid not reconstructible over these devices: host relayout
    logi = _regrow(ck.tiles, mt2, nt2, nb, True)
    cyc = _ckpt._logical_to_cyclic(logi, p2, q2)
    t = jax.device_put(jnp.asarray(cyc), tile_sharding(mesh))
    return DistMatrix(tiles=t, m=ck.m, n=ck.n, nb=nb, mesh=mesh,
                      diag_pad=True)


def _rowperm_to_rows(ck: Checkpoint, mglob2: int) -> Optional[np.ndarray]:
    """Re-base the pp row permutation onto the new padded row space: all
    swap activity lives below the true extent (pivots are drawn from
    rows < m), so the old perm's prefix transplants exactly and the new
    pad rows are fixed points."""
    if ck.rowperm is None:
        return None
    out = np.arange(mglob2, dtype=ck.rowperm.dtype)
    ncopy = min(len(ck.rowperm), mglob2)
    out[:ncopy] = ck.rowperm[:ncopy]
    return out


def reshard(d: DistMatrix, mesh: Mesh) -> DistMatrix:
    """Move a live DistMatrix onto a different mesh via the shard_map
    block-cyclic exchange — the serving layer's multi-tenant rebalancing
    verb (counts as a ckpt reshard so rebalance traffic is observable)."""
    p1, q1 = mesh_shape(d.mesh)
    out = redistribute(d, mesh, impl="shardmap")
    if out is not d:  # identical-layout early return moves zero bytes
        count("ft.ckpt_reshards", "reshard")
        count("ft.ckpt_redistribute_bytes", "reshard", float(
            redistribute_wire_bytes(d.tiles.shape, p1, q1,
                                    d.dtype.itemsize)))
    return out


def resume(ck: Checkpoint, mesh: Mesh, bcast_impl: Optional[str] = None):
    """Continue a checkpointed factorization from its snapshot on
    ``mesh`` and return exactly what the checkpointed driver would have
    ((L|LU, info), (LU, perm, info) for pp, DistQR for geqrf,
    DistTwoStage for he2hb).  BITWISE-identical to the uninterrupted run
    on the same grid AND — for the tile-stack-only ops — on a reshaped
    grid (the redistribution moves exact bytes; the remaining segments
    compute the same per-element arithmetic).  The MULTI-ARRAY ops
    (geqrf/he2hb) carry grid-locked auxiliary state: a mesh row's local
    panel QR factors exactly the rows that row owns, so a reshaped-grid
    resume could not be bitwise (nor even consistent with the stored
    T factors) and raises a structured error instead; a same-shape grid
    over DIFFERENT devices resumes fine (the carry lands by device_put).
    Raises ``Preempted`` again if a persistent kill fault is still
    armed."""
    if not resumable(ck):
        raise SlateError(
            "elastic.resume: checkpoint is missing or names an unknown op"
        )
    t0 = time.perf_counter()
    p2, q2 = mesh_shape(mesh)
    multi = ck.op in _ckpt._MULTI_KEYS
    if multi and (p2, q2) != tuple(ck.grid):
        raise SlateError(
            f"elastic.resume: {ck.op} carries grid-locked auxiliary "
            f"arrays (per-mesh-row panel factors); its {ck.grid[0]}x"
            f"{ck.grid[1]} snapshot cannot resume on a {p2}x{q2} grid — "
            "restart from scratch or grant a same-shape grid"
        )
    mt2 = padded_tiles(ck.m, ck.nb, mesh)
    nt2 = padded_tiles(ck.n, ck.nb, mesh)
    if (p2, q2) != tuple(ck.grid):
        count("ft.ckpt_reshards", ck.op)
    d = _carry_to_mesh(ck, mesh, mt2, nt2)
    rowperm = _rowperm_to_rows(ck, mt2 * ck.nb)
    count("ft.ckpt_resumes", ck.op)
    bi = bcast_impl if bcast_impl is not None else ck.bcast_impl
    out = _ckpt._run(
        ck.op, d, ck.step, ck.every, bi, ck.num_monitor,
        rowperm=rowperm, gauges=(ck.gauges or None), ckpt0=ck,
        arrays=(ck.arrays or None),
        # keep the interrupted run's async preference (persisted in the
        # snapshot) unless the environment re-arms it explicitly
        async_snap=(ck.async_snapshots or _ckpt.resolve_ckpt_async(None)),
        # keep policing the growth gauge: a preemption must not smuggle
        # a garbage no-pivot factor past the abort the uninterrupted
        # run would have raised
        growth_abort=ck.growth_abort,
    )
    count("ft.ckpt_resume_runtime_s", ck.op, time.perf_counter() - t0)
    return out
