"""Checkpointed mesh k-loops: segment dispatch + carry snapshots (ISSUE 12).

The fused factorization kernels (dist_chol / dist_lu) run their whole
k-loop inside one XLA dispatch: a preemption mid-factorization loses
everything.  This module re-expresses the three long-running factor
loops — potrf, LU-nopiv, and partial-pivot LU — as a CHAIN OF SEGMENT
DISPATCHES over the same module-level step helpers the flight recorder
already exercises per step (``_chol_panel_compute``/``_nopiv_panel``/
``_pp_strict_steps``): each segment jit runs steps [k0, k1) of the
strict (depth-0, unbucketed) schedule on the full tile view, and the
loop carry — factored panels + trailing block in one cyclic tile stack,
the replicated pivot permutation (pp), and the Option.NumMonitor gauge
scalars — crosses segment boundaries as ordinary operands.

Because every schedule of these loops is bitwise-identical (lookahead
depth and trailing-view bucketing reorder only independent work — the
invariant tests/test_lookahead.py and the flight recorder already pin),
the chained segments produce EXACTLY the fused kernels' bytes, and a
run resumed from any snapshot is bitwise-equal to the uninterrupted
run (tests/test_ckpt.py asserts this per op).

``Option.Checkpoint`` (int K; explicit > ``SLATE_TPU_CKPT`` env > off)
snapshots the carry to host at every K-step boundary; ``off`` routes to
the plain fused kernels untouched — trace-identical, zero overhead.
Snapshots store the tile grid in LOGICAL order, so a checkpoint taken
on a p x q mesh can resume on a p' x q' mesh (``ft/elastic.py``): the
block-cyclic redistribution moves exact bytes, so the reshaped resume
is bitwise too.

The deterministic injector grows a *kill* class (``inject.KillFault``,
``inject.seeded_kill``): the driver consults the active plan between
segment dispatches and raises ``Preempted`` (carrying the last
snapshot) before executing the segment containing the kill step —
losing exactly the unsnapshotted steps a real preemption would.
``KillFault(in_segment=True)`` sharpens the granularity to the STEP
level: the driver dispatches a partial segment running the strict-
schedule step helpers up to the kill step (real work, then lost) before
raising, so the injected timeline matches a machine dying mid-segment.
Recovery cost lands in the ``ft.ckpt_*`` counters (policy.py), gated in
CI via ``python -m slate_tpu.ft.ckpt_smoke`` + ``obs.report --check``.

ISSUE 13 extends the carry model from single-tile-stack to MULTI-ARRAY:
``geqrf`` (tile stack + per-(mesh-row, panel) T_loc stack + replicated
tree-merge V/T stacks) and the two-stage eig reduction ``he2hb`` (tile
stack evolving toward the band + sharded reflector stack + replicated
compact-WY accumulators) checkpoint as segment chains over the same
module-level step helpers their fused kernels run
(``dist_qr._qr_panel_step`` / ``dist_twostage._he2hb_step``), so
kill→resume is BITWISE on the same mesh.  The auxiliary carries are
GRID-LOCKED (a mesh row's local panel QR depends on the row partition),
so a reshaped-grid resume raises a structured error instead of
producing silently different reflectors — the tile-stack-only ops keep
their reshard-on-resume path untouched.

Snapshots have an ASYNC form (``SLATE_TPU_CKPT_ASYNC=1`` or the
drivers' ``async_snapshots=True``): the device→host carry copy is
issued non-blocking (``jax.Array.copy_to_host_async``) and fenced only
at the NEXT snapshot point (or kill/finish), overlapping the DMA with
the next segment's dispatch — the segment jits do not donate their
operands, so the copied buffers stay immutable and async snapshots are
bitwise-equal to sync ones (tier-1-asserted).  The overlap lands as the
``ft.ckpt_async_overlap_s`` counter.
"""

from __future__ import annotations

import functools
import json
import os
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.tiling import cyclic_perm, inv_perm
from ..obs import instrument
from ..obs.numerics import resolve_num_monitor
from ..parallel.comm import (
    audit_scope,
    bcast_impl_scope,
    local_indices,
    num_gauge_dtype,
    phase_scope,
    pipelined_factor_loop,
    resolve_bcast_impl,
    shard_map_compat,
)
from ..parallel.dist import DistMatrix
from ..parallel.dist_chol import (
    _chol_bulk,
    _chol_info_dist,
    _chol_narrow,
    _chol_panel_bcast,
    _chol_panel_compute,
    potrf_dist,
)
from ..parallel.dist_lu import (
    _lu_info_dist,
    _nopiv_bulk,
    _nopiv_narrow,
    _nopiv_panel,
    _pp_strict_steps,
    _wabs_max,
    getrf_nopiv_dist,
    getrf_pp_dist,
)
from ..linalg.eig import _he2hb_panel_count
from ..obs.numerics import GROWTH_THRESHOLD, GrowthAbort, record_growth_abort
from ..parallel.dist_qr import DistQR, _qr_pad_identity, _qr_panel_step, geqrf_dist
from ..parallel.dist_twostage import DistTwoStage, _he2hb_step, he2hb_dist
from ..parallel.mesh import COL_AXIS, ROW_AXIS, mesh_shape
from ..types import SlateError
from . import inject
from .policy import count

CKPT_ENV = "SLATE_TPU_CKPT"
CKPT_ASYNC_ENV = "SLATE_TPU_CKPT_ASYNC"
CKPT_OPS = ("potrf", "getrf_nopiv", "getrf_pp", "geqrf", "he2hb")
# auxiliary carry arrays per multi-array op, in snapshot order.  These
# carries are GRID-LOCKED: their per-device layout (and the arithmetic
# that produced them — a mesh row's local panel QR factors exactly the
# rows that row owns) depends on the (p, q) grid shape, so a reshaped
# resume cannot be bitwise and elastic.resume refuses it loudly.
_MULTI_KEYS: Dict[str, Tuple[str, ...]] = {
    "geqrf": ("tls", "tvs", "tts"),
    "he2hb": ("vqs", "tqs"),
}


def resolve_checkpoint(every=None) -> Optional[int]:
    """Resolve an Option.Checkpoint value at driver level: explicit
    argument > ``SLATE_TPU_CKPT`` environment > off.  Returns the
    snapshot interval (int >= 1) or None (off — the plain kernels)."""
    if every is None:
        env = os.environ.get(CKPT_ENV, "").strip()
        if env in ("", "0", "off"):
            return None
        every = env
    if every in (None, 0, False) or str(every) in ("0", "off"):
        return None
    k = int(every)
    if k < 1:
        raise ValueError(
            f"Option.Checkpoint must be a positive step interval or off, "
            f"got {every!r}"
        )
    return k


def resolve_ckpt_async(flag=None) -> bool:
    """Async-snapshot switch: explicit argument > ``SLATE_TPU_CKPT_ASYNC``
    environment > off (sync).  Sync and async snapshots are bitwise-
    equal; async overlaps the device→host copy with the next segment."""
    if flag is None:
        return os.environ.get(CKPT_ASYNC_ENV, "").strip().lower() in (
            "1", "on", "true", "async")
    return bool(flag)


# ---------------------------------------------------------------------------
# Snapshot + preemption types
# ---------------------------------------------------------------------------


@dataclass
class Checkpoint:
    """One host-resident snapshot of a mesh factorization's k-loop carry.

    ``tiles`` is the PADDED tile grid in LOGICAL order (mt, nt, nb, nb)
    — layout-independent, so the snapshot resumes on any grid shape:
    pad tiles carry the identity diagonal and receive exact-zero
    trailing updates, hence the data region is bitwise-invariant under
    re-padding for a different mesh lcm.  ``rowperm`` (pp only) covers
    the padded row space; all swap activity lives below the true extent,
    so re-basing onto a different padded length copies a prefix of
    fixed points + data swaps exactly.  ``gauges`` are the NumMonitor
    carry scalars, already globally reduced (min/max are exact, so
    re-seeding every device with the global partial is bitwise).

    ``arrays`` (ISSUE 13) holds the MULTI-ARRAY ops' auxiliary carries
    (``_MULTI_KEYS``): the geqrf T_loc/tree stacks, the he2hb reflector
    and compact-WY stacks — stored in their GLOBAL device layout, which
    is grid-locked (see the module docstring), so a resume requires the
    snapshot's own (p, q) grid shape for these ops."""

    op: str
    step: int  # next logical k-step to execute on resume
    every: int  # snapshot interval the run was using
    m: int
    n: int
    nb: int
    grid: Tuple[int, int]  # (p, q) the snapshot was taken on
    bcast_impl: str
    num_monitor: bool
    tiles: np.ndarray  # LOGICAL-order padded tile grid
    rowperm: Optional[np.ndarray] = None
    gauges: Dict[str, np.ndarray] = field(default_factory=dict)
    arrays: Dict[str, np.ndarray] = field(default_factory=dict)
    # whether the interrupted run had the mid-loop growth-abort gate
    # armed (monitored no-pivot LU): resume must keep policing the
    # gauge, or a preemption would smuggle a garbage factor past the
    # abort the uninterrupted run would have raised
    growth_abort: bool = False
    # whether the interrupted run snapshotted asynchronously: resume
    # keeps the caller's overlap preference (results are bitwise either
    # way; this is the one resilience knob that would otherwise be
    # silently dropped across the resume boundary)
    async_snapshots: bool = False

    @property
    def nbytes(self) -> int:
        n = int(self.tiles.nbytes)
        if self.rowperm is not None:
            n += int(self.rowperm.nbytes)
        for v in self.arrays.values():
            n += int(v.nbytes)
        return n

    def save(self, path: str) -> str:
        """Persist to disk (``np.savez``): the preemption-survival form —
        ``Checkpoint.load(path)`` round-trips bitwise."""
        meta = dict(
            op=self.op, step=self.step, every=self.every, m=self.m,
            n=self.n, nb=self.nb, grid=list(self.grid),
            bcast_impl=self.bcast_impl,
            num_monitor=self.num_monitor, growth_abort=self.growth_abort,
            async_snapshots=self.async_snapshots,
        )
        arrays = {
            "tiles": self.tiles,
            "meta": np.frombuffer(json.dumps(meta).encode(), np.uint8),
        }
        if self.rowperm is not None:
            arrays["rowperm"] = self.rowperm
        for k, v in self.gauges.items():
            arrays[f"gauge_{k}"] = np.asarray(v)
        for k, v in self.arrays.items():
            arrays[f"arr_{k}"] = np.asarray(v)
        with open(path, "wb") as f:  # np.savez(str) would append .npz
            np.savez(f, **arrays)
        return path

    @classmethod
    def load(cls, path: str) -> "Checkpoint":
        with np.load(path) as z:
            meta = json.loads(bytes(z["meta"]).decode())
            gauges = {
                k[len("gauge_"):]: z[k] for k in z.files
                if k.startswith("gauge_")
            }
            arrs = {
                k[len("arr_"):]: z[k] for k in z.files
                if k.startswith("arr_")
            }
            return cls(
                op=meta["op"], step=int(meta["step"]),
                every=int(meta["every"]), m=int(meta["m"]), n=int(meta["n"]),
                nb=int(meta["nb"]), grid=tuple(meta["grid"]),
                bcast_impl=meta["bcast_impl"],
                num_monitor=bool(meta["num_monitor"]), tiles=z["tiles"],
                rowperm=(z["rowperm"] if "rowperm" in z.files else None),
                gauges=gauges, arrays=arrs,
                growth_abort=bool(meta.get("growth_abort", False)),
                async_snapshots=bool(meta.get("async_snapshots", False)),
            )


class Preempted(SlateError):
    """A (possibly injected) preemption interrupted a checkpointed
    k-loop.  ``checkpoint`` is the last snapshot — resume it with
    ``ft.elastic.resume`` — or None when the kill landed before the
    first snapshot boundary (nothing to resume from: the caller decides
    between a from-scratch restart and rejection)."""

    def __init__(self, op: str, killed_at: int, checkpoint: Optional[Checkpoint]):
        self.op = op
        self.killed_at = int(killed_at)
        self.checkpoint = checkpoint
        state = (
            f"resumable from step {checkpoint.step}"
            if checkpoint is not None
            else "no snapshot taken — unresumable"
        )
        super().__init__(f"ckpt[{op}]: preempted at step {killed_at} ({state})")


def _cyclic_to_logical(t: np.ndarray, p: int, q: int) -> np.ndarray:
    """Host-side ``tiling.from_cyclic`` (a pure index permutation — moves
    exact bytes, never touches values)."""
    rp = inv_perm(cyclic_perm(t.shape[0], p))
    cp = inv_perm(cyclic_perm(t.shape[1], q))
    return np.ascontiguousarray(t[rp][:, cp])


def _logical_to_cyclic(t: np.ndarray, p: int, q: int) -> np.ndarray:
    rp = cyclic_perm(t.shape[0], p)
    cp = cyclic_perm(t.shape[1], q)
    return np.ascontiguousarray(t[rp][:, cp])


# ---------------------------------------------------------------------------
# Segment kernels: steps [k0, k1) of the strict schedule on the full view.
# The step bodies are the module-level dist_chol/_lu helpers — the same
# arithmetic in the same per-element order as the fused kernels, so the
# chained segments reproduce their results bitwise at any boundary set.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _potrf_seg_jit(at, g, mesh, p, q, nt, n_true, k0, k1, bi, nm):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, g_in):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype
        cplx = jnp.issubdtype(dtype, jnp.complexfloating)
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
        lower = (i_log[:, None] >= j_log[None, :])[:, :, None, None]
        rdt = num_gauge_dtype(dtype)

        def panel(k, view):
            view, pan_own = _chol_panel_compute(view, k, p, q, i_log, c, cplx)
            with phase_scope("bcast", k):
                return view, _chol_panel_bcast(pan_own, k, p, q, j_log)

        def narrow(k, view, pl):
            return _chol_narrow(view, pl, k, q, lower, cplx)

        def bulk(k, view, pl):
            if k is None:
                return _chol_bulk(view, pl, lower, cplx)
            return _chol_bulk(view, pl, lower, cplx, k // q)

        zero_pl = (
            jnp.zeros((mtl, nb, nb), dtype),
            jnp.zeros((ntl, nb, nb), dtype),
        )
        if not nm:
            t_loc = pipelined_factor_loop(
                k0, k1, 0, panel, narrow, bulk, t_loc, zero_pl
            )
            return t_loc, jnp.zeros((1, 1), jnp.float32)

        def diag_probe(k, view):
            # dist_chol._potrf_jit's near-breakdown margin probe at panel
            # entry (the strict-schedule Schur diagonal, true extent only)
            dvals = jnp.einsum("ijaa->ija", jnp.real(view)).astype(rdt)
            gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :]
            m = ((i_log[:, None] == j_log[None, :])[:, :, None]
                 & (i_log >= k)[:, None, None] & (gidx < n_true))
            return jnp.min(jnp.where(m, dvals, jnp.inf))

        def panel_nm(k, st):
            view, gg = st
            gg = jnp.minimum(gg, diag_probe(k, view))
            view, pl = panel(k, view)
            return (view, gg), pl

        def narrow_nm(k, st, pl):
            return (narrow(k, st[0], pl), st[1])

        def bulk_nm(k, st, pl):
            return (bulk(k, st[0], pl), st[1])

        t_loc, gg = pipelined_factor_loop(
            k0, k1, 0, panel_nm, narrow_nm, bulk_nm,
            (t_loc, g_in.astype(rdt)), zero_pl,
        )
        # carry the margin out globally reduced (min is exact, so seeding
        # the next segment with the global partial is bitwise — the
        # _lu_info_dist unaudited reduction class)
        gg = lax.pmin(lax.pmin(gg, ROW_AXIS), COL_AXIS)
        return t_loc, gg[None, None]

    with bcast_impl_scope(bi):
        lt, g_out = shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, P()),
            out_specs=(spec, P(ROW_AXIS, COL_AXIS)), check_vma=False,
        )(at, g)
    return lt, jnp.min(g_out)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _potrf_fin_jit(at, g, mesh, p, q, nt, n_true, nm):
    """info + (margin, lmin, lmax) gauges of the completed factor — the
    exit computation of dist_chol._potrf_jit, split off so the segment
    chain runs it exactly once."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, g_in):
        mtl, ntl, nb, _ = t_loc.shape
        _, _, i_log, j_log = local_indices(p, q, mtl, ntl)
        info = _chol_info_dist(t_loc, i_log, j_log, nt, nb)
        if not nm:
            return info[None, None], jnp.zeros((1, 1, 3), jnp.float32)
        rdt = num_gauge_dtype(t_loc.dtype)
        dvals = jnp.einsum("ijaa->ija", jnp.real(t_loc)).astype(rdt)
        gidx = i_log[:, None, None] * nb + jnp.arange(nb)[None, None, :]
        dm = (i_log[:, None] == j_log[None, :])[:, :, None] & (gidx < n_true)
        lmin = jnp.min(jnp.where(dm, dvals, jnp.inf))
        lmax = jnp.max(jnp.where(dm, dvals, -jnp.inf))

        def allr(x, op):
            return op(op(x, ROW_AXIS), COL_AXIS)

        gz = jnp.stack([
            g_in.astype(rdt), allr(lmin, lax.pmin), allr(lmax, lax.pmax),
        ])
        return info[None, None], gz[None, None]

    info, gz = shard_map_compat(
        kernel, mesh=mesh, in_specs=(spec, P()),
        out_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS)),
        check_vma=False,
    )(at, g)
    return jnp.max(info), gz[0, 0]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
def _lu_seg_jit(at, g, mesh, p, q, nt, m_true, k0, k1, bi, nm):
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, g_in):
        mtl, ntl, nb, _ = t_loc.shape
        dtype = t_loc.dtype
        r, c, i_log, j_log = local_indices(p, q, mtl, ntl)
        rdt = num_gauge_dtype(dtype)

        def panel(k, view):
            return _nopiv_panel(view, k, p, q, i_log, j_log, r, c)

        def narrow(k, view, pl):
            return _nopiv_narrow(view, pl, k, p, q)

        def bulk(k, view, pl):
            if k is None:
                return _nopiv_bulk(view, pl)
            return _nopiv_bulk(view, pl, k // p, k // q)

        zero_pl = (
            jnp.zeros((mtl, nb, nb), dtype),
            jnp.zeros((ntl, nb, nb), dtype),
        )
        if not nm:
            t_loc = pipelined_factor_loop(
                k0, k1, 0, panel, narrow, bulk, t_loc, zero_pl
            )
            return t_loc, jnp.zeros((1, 1), jnp.float32)

        def panel_nm(k, st):
            view, gg = st
            gg = jnp.maximum(gg, _wabs_max(view, i_log, j_log, nb, m_true, rdt))
            view, pl = panel(k, view)
            return (view, gg), pl

        def narrow_nm(k, st, pl):
            return (narrow(k, st[0], pl), st[1])

        def bulk_nm(k, st, pl):
            return (bulk(k, st[0], pl), st[1])

        t_loc, gg = pipelined_factor_loop(
            k0, k1, 0, panel_nm, narrow_nm, bulk_nm,
            (t_loc, g_in.astype(rdt)), zero_pl,
        )
        gg = lax.pmax(lax.pmax(gg, ROW_AXIS), COL_AXIS)
        return t_loc, gg[None, None]

    with bcast_impl_scope(bi):
        lt, g_out = shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, P()),
            out_specs=(spec, P(ROW_AXIS, COL_AXIS)), check_vma=False,
        )(at, g)
    return lt, jnp.max(g_out)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def _lu_fin_jit(at, amax0, g, mesh, p, q, nt, m_true, nm):
    """info + (amax0, growth-max) gauges for the LU ops (shared by the
    nopiv and pp segment chains — the _lu_growth_out exit computation on
    already-reduced carried scalars)."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, a0, g_in):
        mtl, ntl, nb, _ = t_loc.shape
        _, _, i_log, j_log = local_indices(p, q, mtl, ntl)
        info = _lu_info_dist(t_loc, i_log, j_log, nt, nb)
        if not nm:
            return info[None, None], jnp.zeros((1, 1, 2), jnp.float32)
        rdt = num_gauge_dtype(t_loc.dtype)
        gfin = _wabs_max(t_loc, i_log, j_log, nb, m_true, rdt)
        gfin = lax.pmax(lax.pmax(gfin, ROW_AXIS), COL_AXIS)
        gz = jnp.stack([a0.astype(rdt), jnp.maximum(g_in.astype(rdt), gfin)])
        return info[None, None], gz[None, None]

    info, gz = shard_map_compat(
        kernel, mesh=mesh, in_specs=(spec, P(), P()),
        out_specs=(P(ROW_AXIS, COL_AXIS), P(ROW_AXIS, COL_AXIS)),
        check_vma=False,
    )(at, amax0, g)
    return jnp.max(info), gz[0, 0]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _wabs_init_jit(at, mesh, p, q, m_true):
    """Globally-reduced max|A| over the true extent — the growth-gauge
    denominator the fused LU kernels compute at loop entry."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc):
        mtl, ntl, nb, _ = t_loc.shape
        _, _, i_log, j_log = local_indices(p, q, mtl, ntl)
        rdt = num_gauge_dtype(t_loc.dtype)
        a0 = _wabs_max(t_loc, i_log, j_log, nb, m_true, rdt)
        a0 = lax.pmax(lax.pmax(a0, ROW_AXIS), COL_AXIS)
        return a0[None, None]

    out = shard_map_compat(
        kernel, mesh=mesh, in_specs=(spec,),
        out_specs=P(ROW_AXIS, COL_AXIS), check_vma=False,
    )(at)
    return jnp.max(out)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _pp_seg_jit(at, rowperm, g, mesh, p, q, nt, m_true, k0, k1, bi, nm):
    """Steps [k0, k1) of getrf_pp_dist's strict schedule
    (``_pp_strict_steps``)."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, rowperm, g_in):
        r, c, i_log, j_log = local_indices(p, q, *t_loc.shape[:2])
        g = g_in.astype(num_gauge_dtype(t_loc.dtype)) if nm else None
        t_loc, rowperm, g = _pp_strict_steps(
            t_loc, rowperm, g, k0, k1, p, q, r, c, i_log, j_log, nt, m_true)
        if nm:
            g = lax.pmax(lax.pmax(g, ROW_AXIS), COL_AXIS)
        else:
            g = jnp.zeros((), jnp.float32)
        return t_loc, rowperm[None], g[None, None]

    with bcast_impl_scope(bi):
        lt, perm, g_out = shard_map_compat(
            kernel, mesh=mesh, in_specs=(spec, P(), P()),
            out_specs=(spec, P(ROW_AXIS), P(ROW_AXIS, COL_AXIS)),
            check_vma=False,
        )(at, rowperm, g)
    return lt, perm[0], jnp.max(g_out)


# ---------------------------------------------------------------------------
# Multi-array segment kernels (ISSUE 13): steps [k0, k1) of the CAQR and
# he2hb strict schedules, the whole multi-array carry crossing segment
# boundaries as ordinary operands.  The step bodies are the same
# module-level helpers the fused kernels loop over, so the chains are
# bitwise at any boundary set.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _qr_seg_jit(at, tls, tvs, tts, mesh, p, q, m_true, k0, k1, bi):
    """Steps [k0, k1) of the CAQR panel loop (dist_qr._qr_panel_step)
    over the carry (tile stack, T_loc stack sharded over 'p', replicated
    tree V/T stacks)."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, tl_loc, tv, tt):
        def step(k, carry):
            return _qr_panel_step(k, carry, p, q, m_true)

        with audit_scope(k1 - k0):
            return lax.fori_loop(k0, k1, step, (t_loc, tl_loc, tv, tt))

    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel, mesh=mesh,
            in_specs=(spec, P(ROW_AXIS), P(), P()),
            out_specs=(spec, P(ROW_AXIS), P(), P()), check_vma=False,
        )(at, tls, tvs, tts)


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10, 11))
def _qr_seg_nm_jit(at, tls, tvs, tts, g, mesh, p, q, m_true, k0, k1, bi):
    """The MONITORED twin of ``_qr_seg_jit`` (ISSUE 14 satellite; the
    ROADMAP "NumMonitor gauges through the QR/eig segment chains" item):
    the same ``dist_qr._qr_panel_step`` arithmetic — tile/T/tree results
    stay bitwise-identical to the plain chain — with the per-panel
    reflector/τ consistency margin (``dist_qr._qr_orth_loss``) carried
    as a running max.  The gauge is LOCAL per mesh row (T was built from
    this row's V), so the only reduction is the unaudited exit pmax —
    the ``_lu_info_dist`` class: comm-audit wire bytes are unchanged.
    The off mode never calls this jit, so the unmonitored chain's jaxpr
    is untouched by construction."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, tl_loc, tv, tt, g_in):
        rdt = num_gauge_dtype(t_loc.dtype)

        def step(k, carry):
            *st4, gg = carry
            out4, loss = _qr_panel_step(k, tuple(st4), p, q, m_true,
                                        nm=True)
            return out4 + (jnp.maximum(gg, loss),)

        with audit_scope(k1 - k0):
            t_loc, tl_loc, tv, tt, gg = lax.fori_loop(
                k0, k1, step, (t_loc, tl_loc, tv, tt, g_in.astype(rdt)))
        # exact max fold: seeding the next segment with the reduced
        # partial is bitwise (the potrf/LU segment-gauge contract)
        gg = lax.pmax(lax.pmax(gg, ROW_AXIS), COL_AXIS)
        return t_loc, tl_loc, tv, tt, gg[None, None]

    with bcast_impl_scope(bi):
        t, tls, tvs, tts, g_out = shard_map_compat(
            kernel, mesh=mesh,
            in_specs=(spec, P(ROW_AXIS), P(), P(), P()),
            out_specs=(spec, P(ROW_AXIS), P(), P(),
                       P(ROW_AXIS, COL_AXIS)), check_vma=False,
        )(at, tls, tvs, tts, g)
    return t, tls, tvs, tts, jnp.max(g_out)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _qr_fin_jit(at, mesh, p, q, n_true):
    """The fused CAQR kernel's exit computation (identity on the padded
    diagonal), split off so the segment chain runs it exactly once."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc):
        return _qr_pad_identity(t_loc, p, q, n_true, t_loc.dtype)

    return shard_map_compat(
        kernel, mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False,
    )(at)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _he2hb_seg_jit(at, vqs, tqs, mesh, p, q, n_true, nb, k0, k1, bi):
    """Steps [k0, k1) of the he2hb panel + two-sided trailing loop
    (dist_twostage._he2hb_step) over the carry (tile stack, reflector
    stack sharded over 'p', replicated compact-WY accumulators).  The
    tile<->flat transposes at the segment boundary are exact byte moves,
    so the chain stays bitwise with the fused kernel."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, vq_loc, tq):
        mtl, ntl, _, _ = t_loc.shape
        a = jnp.transpose(t_loc, (0, 2, 1, 3)).reshape(mtl * nb, ntl * nb)

        def step(k, carry):
            return _he2hb_step(k, carry, p, q, n_true, nb)

        with audit_scope(k1 - k0):
            a, vq_loc, tq = lax.fori_loop(k0, k1, step, (a, vq_loc, tq))
        t_out = jnp.transpose(a.reshape(mtl, nb, ntl, nb), (0, 2, 1, 3))
        return t_out, vq_loc, tq

    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel, mesh=mesh,
            in_specs=(spec, P(None, ROW_AXIS), P()),
            out_specs=(spec, P(None, ROW_AXIS), P()), check_vma=False,
        )(at, vqs, tqs)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _he2hb_seg_nm_jit(at, vqs, tqs, g, mesh, p, q, n_true, nb, k0, k1, bi):
    """The MONITORED twin of ``_he2hb_seg_jit`` (ISSUE 15): the same
    ``dist_twostage._he2hb_step`` arithmetic — band/reflector/WY results
    stay bitwise-identical to the plain chain — with the per-panel
    reflector/τ consistency margin carried as a running max.  The panel
    factors are REPLICATED, so the gauge needs no reduction at all:
    collective-free, audited wire bytes unchanged.  The off mode never
    calls this jit, so the unmonitored chain's jaxpr is untouched."""
    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(t_loc, vq_loc, tq, g_in):
        mtl, ntl, _, _ = t_loc.shape
        a = jnp.transpose(t_loc, (0, 2, 1, 3)).reshape(mtl * nb, ntl * nb)
        rdt = num_gauge_dtype(t_loc.dtype)

        def step(k, carry):
            *st3, gg = carry
            out3, loss = _he2hb_step(k, tuple(st3), p, q, n_true, nb,
                                     nm=True)
            return out3 + (jnp.maximum(gg, loss),)

        with audit_scope(k1 - k0):
            a, vq_loc, tq, gg = lax.fori_loop(
                k0, k1, step, (a, vq_loc, tq, g_in.astype(rdt)))
        t_out = jnp.transpose(a.reshape(mtl, nb, ntl, nb), (0, 2, 1, 3))
        return t_out, vq_loc, tq, gg

    with bcast_impl_scope(bi):
        return shard_map_compat(
            kernel, mesh=mesh,
            in_specs=(spec, P(None, ROW_AXIS), P(), P()),
            out_specs=(spec, P(None, ROW_AXIS), P(), P()), check_vma=False,
        )(at, vqs, tqs, g)


# ---------------------------------------------------------------------------
# Host engine: segment chain + snapshot + kill consultation
# ---------------------------------------------------------------------------


def _seg_dispatch(op, st, mesh, p, q, nt, m_true, k0, k1, bi, nm):
    if op == "potrf":
        st["tiles"], g = _potrf_seg_jit(
            st["tiles"], st["g"], mesh, p, q, nt, m_true, k0, k1, bi, nm)
    elif op == "getrf_nopiv":
        st["tiles"], g = _lu_seg_jit(
            st["tiles"], st["g"], mesh, p, q, nt, m_true, k0, k1, bi, nm)
    elif op == "getrf_pp":
        st["tiles"], st["rowperm"], g = _pp_seg_jit(
            st["tiles"], st["rowperm"], st["g"], mesh, p, q, nt, m_true,
            k0, k1, bi, nm)
    elif op == "geqrf":
        if nm:
            st["tiles"], st["tls"], st["tvs"], st["tts"], g = \
                _qr_seg_nm_jit(
                    st["tiles"], st["tls"], st["tvs"], st["tts"], st["g"],
                    mesh, p, q, m_true, k0, k1, bi)
        else:
            st["tiles"], st["tls"], st["tvs"], st["tts"] = _qr_seg_jit(
                st["tiles"], st["tls"], st["tvs"], st["tts"], mesh, p, q,
                m_true, k0, k1, bi)
            g = None
    elif op == "he2hb":
        nb = st["tiles"].shape[-1]
        if nm:
            st["tiles"], st["vqs"], st["tqs"], g = _he2hb_seg_nm_jit(
                st["tiles"], st["vqs"], st["tqs"], st["g"], mesh, p, q,
                m_true, nb, k0, k1, bi)
        else:
            st["tiles"], st["vqs"], st["tqs"] = _he2hb_seg_jit(
                st["tiles"], st["vqs"], st["tqs"], mesh, p, q, m_true, nb,
                k0, k1, bi)
            g = None
    else:
        raise ValueError(f"no checkpointed driver for op {op!r}; "
                         f"expected one of {CKPT_OPS}")
    if nm and g is not None:
        st["g"] = g


def _snapshot(op, d: DistMatrix, st, k, every, bi, nm,
              ga: bool = False, asnap: bool = False) -> Checkpoint:
    p, q = mesh_shape(d.mesh)
    gauges: Dict[str, np.ndarray] = {}
    if nm:
        gauges["g"] = np.asarray(st["g"])
        if "amax0" in st:
            gauges["amax0"] = np.asarray(st["amax0"])
    arrays = {kk: np.asarray(st[kk]) for kk in _MULTI_KEYS.get(op, ())}
    ck = Checkpoint(
        op=op, step=int(k), every=int(every), m=d.m, n=d.n, nb=d.nb,
        grid=(p, q), bcast_impl=bi, num_monitor=nm,
        tiles=_cyclic_to_logical(np.asarray(st["tiles"]), p, q),
        rowperm=(np.asarray(st["rowperm"]) if "rowperm" in st else None),
        gauges=gauges, arrays=arrays, growth_abort=ga,
        async_snapshots=asnap,
    )
    count("ft.ckpt_snapshots", op)
    count("ft.ckpt_snapshot_bytes", op, float(ck.nbytes))
    return ck


class _PendingSnapshot:
    """An in-flight ASYNC snapshot: non-blocking device→host copies of
    the whole carry (``jax.Array.copy_to_host_async``), issued at the
    segment boundary so the DMA overlaps the NEXT segment's dispatch,
    fenced only at the next snapshot point (or at a kill / loop exit).
    The segment jits do not donate their operands, so the copied buffers
    stay immutable while the next segment computes — the materialized
    Checkpoint is bitwise-equal to the sync path's."""

    def __init__(self, op, d, st, k, every, bi, nm, ga=False):
        # shallow copy: _seg_dispatch REBINDS st entries (functional
        # updates), so the captured references keep the boundary values
        self._args = (op, d, dict(st), k, every, bi, nm, ga, True)
        for v in self._args[2].values():
            start = getattr(v, "copy_to_host_async", None)
            if start is not None:
                start()
        self.issued = time.perf_counter()

    def materialize(self) -> Checkpoint:
        op = self._args[0]
        count("ft.ckpt_async_overlap_s", op,
              max(0.0, time.perf_counter() - self.issued))
        return _snapshot(*self._args)


def _finish(op, d: DistMatrix, st, nm):
    from ..obs import numerics as _num

    mesh = d.mesh
    p, q = mesh_shape(mesh)
    nt = d.nt
    m_true = d.n if op == "potrf" else d.m
    if op == "geqrf":
        t = _qr_fin_jit(st["tiles"], mesh, p, q, d.n)
        fd = DistMatrix(tiles=t, m=d.m, n=d.n, nb=d.nb, mesh=mesh,
                        diag_pad=True)
        if nm:
            _num.record_qr_orth("geqrf", st["g"])
        return DistQR(fd, st["tls"], st["tvs"], st["tts"])
    if op == "he2hb":
        band = DistMatrix(tiles=st["tiles"], m=d.m, n=d.n, nb=d.nb, mesh=mesh)
        if nm:
            _num.record_he2hb_orth("he2hb", st["g"])
        return DistTwoStage(band, st["vqs"], st["tqs"],
                            st["vqs"][:0], st["tqs"][:0])
    out = DistMatrix(
        tiles=st["tiles"], m=d.m, n=d.n, nb=d.nb, mesh=mesh, diag_pad=True
    )
    if op == "potrf":
        info, gz = _potrf_fin_jit(st["tiles"], st["g"], mesh, p, q, nt,
                                  m_true, nm)
        if nm:
            _num.record_chol_gauges("potrf", gz[0], gz[1], gz[2])
        return out, info
    amax0 = st.get("amax0", jnp.zeros((), jnp.float32))
    info, gz = _lu_fin_jit(st["tiles"], amax0, st["g"], mesh, p, q, nt,
                           m_true, nm)
    if nm:
        _num.record_lu_growth(op, gz[0], gz[1])
    if op == "getrf_pp":
        return out, st["rowperm"], info
    return out, info


def _multi_init(op: str, d: DistMatrix, st: dict, nsteps: int) -> None:
    """Zero-initialize the multi-array ops' auxiliary carries in their
    GLOBAL layout (the fused kernels' in-kernel zeros, hoisted to
    operands — identical values, so the chain stays bitwise)."""
    nb = d.nb
    p, _q = mesh_shape(d.mesh)
    dtype = d.dtype
    if op == "geqrf":
        nmerge = max(1, p)
        st["tls"] = jnp.zeros((p * d.nt, nb, nb), dtype)
        st["tvs"] = jnp.zeros((d.nt, nmerge, 2 * nb, nb), dtype)
        st["tts"] = jnp.zeros((d.nt, nmerge, nb, nb), dtype)
    elif op == "he2hb":
        st["vqs"] = jnp.zeros((max(nsteps, 1), d.mt * nb, nb), dtype)
        st["tqs"] = jnp.zeros((max(nsteps, 1), nb, nb), dtype)


def _run(op: str, d: DistMatrix, k_from: int, every: int, bi: str,
         nm: bool, rowperm=None, gauges=None,
         ckpt0: Optional[Checkpoint] = None, arrays=None,
         async_snap: bool = False, growth_abort: bool = False):
    """Segment-dispatch the k-loop of ``op`` over [k_from, nsteps):
    snapshot the carry at every ``every``-step boundary (async when
    ``async_snap`` — the copy overlaps the next dispatch and fences at
    the next boundary); raise ``Preempted`` when an armed ``KillFault``
    lands inside the segment about to run (a step-level ``in_segment``
    kill first dispatches the partial segment up to the kill step — real
    work, then lost).  Either way the work since the last snapshot is
    exactly what the resume re-executes — ``ft.ckpt_lost_steps``.  With
    ``growth_abort`` (monitored no-pivot LU), a running-growth gauge
    crossing GROWTH_THRESHOLD at a segment boundary raises
    ``GrowthAbort`` instead of completing a garbage factor."""
    mesh = d.mesh
    p, q = mesh_shape(mesh)
    nt = _he2hb_panel_count(d.n, d.nb) if op == "he2hb" else d.nt
    m_true = d.n if op in ("potrf", "he2hb") else d.m
    st: dict = {"tiles": d.tiles}
    if op == "getrf_pp":
        st["rowperm"] = (
            jnp.asarray(rowperm) if rowperm is not None
            else jnp.arange(nt * d.nb)
        )
    if op in _MULTI_KEYS:
        if arrays:
            for kk in _MULTI_KEYS[op]:
                st[kk] = jnp.asarray(arrays[kk])
        else:
            _multi_init(op, d, st, nt)
    if nm:
        if op == "potrf":
            st["g"] = (jnp.asarray(gauges["g"]) if gauges
                       else jnp.asarray(jnp.inf, num_gauge_dtype(d.dtype)))
        elif op in ("geqrf", "he2hb"):
            # running max of the per-panel orthogonality-loss proxy
            # (dist_qr._qr_orth_loss); 0 = nothing observed yet
            st["g"] = (jnp.asarray(gauges["g"]) if gauges
                       else jnp.zeros((), num_gauge_dtype(d.dtype)))
        elif gauges:
            st["amax0"] = jnp.asarray(gauges["amax0"])
            st["g"] = jnp.asarray(gauges["g"])
        else:
            a0 = _wabs_init_jit(d.tiles, mesh, p, q, m_true)
            st["amax0"] = a0
            st["g"] = a0
    elif op not in _MULTI_KEYS:
        st["g"] = jnp.zeros((), jnp.float32)

    last = ckpt0
    pending: Optional[_PendingSnapshot] = None

    def fence():
        nonlocal last, pending
        if pending is not None:
            last = pending.materialize()
            pending = None

    k = int(k_from)
    while k < nt:
        k2 = min(k + every, nt)
        kills = [f for f in inject.armed_kills(op) if k <= f.k < k2]
        if kills:
            kill = min(kills, key=lambda f: f.k)
            plan = inject.current_plan()
            if plan is not None:
                plan.consume_fault(kill)
            if getattr(kill, "in_segment", False) and kill.k > k:
                # step-level arm: the machine really runs [k, kill.k) —
                # the strict-schedule step helpers stop early — and dies
                # there; the partial carry is discarded with it
                _seg_dispatch(op, dict(st), mesh, p, q, nt, m_true,
                              k, kill.k, bi, nm)
                count("ft.ckpt_inseg_kills", op)
            count("ft.ckpt_kills", op)
            count("ft.ckpt_lost_steps", op, float(kill.k - k))
            fence()  # an in-flight host copy survives the preemption
            raise Preempted(op, kill.k, last)
        _seg_dispatch(op, st, mesh, p, q, nt, m_true, k, k2, bi, nm)
        if growth_abort and nm and "amax0" in st:
            a0 = float(st["amax0"])
            growth = float(st["g"]) / a0 if a0 > 0 else 0.0
            if growth > GROWTH_THRESHOLD:
                record_growth_abort(op, growth)
                fence()
                raise GrowthAbort(op, growth, k2, GROWTH_THRESHOLD)
        k = k2
        if k < nt:
            if async_snap:
                fence()  # previous copy fences only now, one interval late
                pending = _PendingSnapshot(op, d, st, k, every, bi, nm,
                                           growth_abort)
                count("ft.ckpt_async_snapshots", op)
            else:
                last = _snapshot(op, d, st, k, every, bi, nm,
                                 growth_abort)
    fence()  # account the final interior snapshot's overlap + bytes
    return _finish(op, d, st, nm)


# ---------------------------------------------------------------------------
# Public drivers (Option.Checkpoint off routes to the fused kernels:
# trace-identical — the NumMonitor off-mode contract)
# ---------------------------------------------------------------------------


def _check_square(a: DistMatrix, who: str) -> None:
    if a.mt != a.nt:
        raise ValueError(f"{who} needs a square tile grid")
    a.require_diag_pad(who)


@instrument("potrf_ckpt")
def potrf_ckpt(a: DistMatrix, every=None, bcast_impl: Optional[str] = None,
               num_monitor: Optional[str] = None, async_snapshots=None):
    """Checkpointed mesh Cholesky: ``potrf_dist`` results (bitwise) with
    the carry snapshotted every ``every`` steps (Option.Checkpoint; None
    resolves the env chain — off delegates to the fused kernel
    untouched).  Returns (L DistMatrix, info); raises ``Preempted``
    under an armed kill fault.  ``async_snapshots`` resolves the
    SLATE_TPU_CKPT_ASYNC chain: overlap the snapshot copy with the next
    segment (bitwise-equal either way)."""
    ev = resolve_checkpoint(every)
    if ev is None:
        return potrf_dist(a, bcast_impl=bcast_impl, num_monitor=num_monitor)
    _check_square(a, "potrf_ckpt")
    return _run("potrf", a, 0, ev, resolve_bcast_impl(bcast_impl),
                resolve_num_monitor(num_monitor) == "on",
                async_snap=resolve_ckpt_async(async_snapshots))


@instrument("getrf_nopiv_ckpt")
def getrf_nopiv_ckpt(a: DistMatrix, every=None,
                     bcast_impl: Optional[str] = None,
                     num_monitor: Optional[str] = None,
                     async_snapshots=None, growth_abort: bool = True):
    """Checkpointed mesh LU-nopiv (getrf_nopiv_dist, bitwise).  Returns
    (LU DistMatrix, info).  When monitored (Option.NumMonitor=on) the
    in-carry running-growth gauge is checked at every segment boundary:
    crossing GROWTH_THRESHOLD raises ``obs.numerics.GrowthAbort``
    mid-k-loop instead of completing a garbage factor (the ROADMAP
    "close the control loop" escalation — callers retry with tntpiv/pp;
    ``growth_abort=False`` opts out)."""
    ev = resolve_checkpoint(every)
    if ev is None:
        return getrf_nopiv_dist(a, bcast_impl=bcast_impl,
                                num_monitor=num_monitor)
    _check_square(a, "getrf_nopiv_ckpt")
    return _run("getrf_nopiv", a, 0, ev, resolve_bcast_impl(bcast_impl),
                resolve_num_monitor(num_monitor) == "on",
                async_snap=resolve_ckpt_async(async_snapshots),
                growth_abort=growth_abort)


@instrument("getrf_pp_ckpt")
def getrf_pp_ckpt(a: DistMatrix, every=None,
                  bcast_impl: Optional[str] = None,
                  num_monitor: Optional[str] = None, async_snapshots=None):
    """Checkpointed partial-pivot mesh LU (getrf_pp_dist, bitwise): the
    carry additionally snapshots the replicated row permutation.
    Returns (LU DistMatrix, perm, info)."""
    ev = resolve_checkpoint(every)
    if ev is None:
        return getrf_pp_dist(a, bcast_impl=bcast_impl,
                             num_monitor=num_monitor)
    _check_square(a, "getrf_pp_ckpt")
    return _run("getrf_pp", a, 0, ev, resolve_bcast_impl(bcast_impl),
                resolve_num_monitor(num_monitor) == "on",
                async_snap=resolve_ckpt_async(async_snapshots))


@instrument("geqrf_ckpt")
def geqrf_ckpt(a: DistMatrix, every=None, bcast_impl: Optional[str] = None,
               async_snapshots=None, num_monitor: Optional[str] = None):
    """Checkpointed distributed CAQR (ISSUE 13): ``geqrf_dist`` results
    (bitwise) with the MULTI-ARRAY carry — tile stack, per-(mesh-row,
    panel) T_loc stack, replicated tree V/T stacks — snapshotted every
    ``every`` panel steps.  Returns DistQR; raises ``Preempted`` under
    an armed kill fault.  The auxiliary carries are grid-locked: resume
    requires the snapshot's own (p, q) grid shape.

    ``num_monitor`` (Option.NumMonitor, ISSUE 14 satellite): ``on``
    carries the per-panel reflector/τ orthogonality-loss proxy
    (``dist_qr._qr_orth_loss``) as a running max through the segment
    chain — results stay bitwise, zero extra audited collectives —
    surfaced as the ``num.qr_orth_margin`` gauge / ``qr_orth_loss_max``
    num-section total; off keeps the plain (unchanged) segment jits."""
    ev = resolve_checkpoint(every)
    if ev is None:
        return geqrf_dist(a, bcast_impl=bcast_impl, num_monitor=num_monitor)
    if a.m < a.n:
        raise ValueError(f"geqrf_ckpt requires m >= n, got {a.m}x{a.n}")
    return _run("geqrf", a, 0, ev, resolve_bcast_impl(bcast_impl),
                resolve_num_monitor(num_monitor) == "on",
                async_snap=resolve_ckpt_async(async_snapshots))


@instrument("he2hb_ckpt")
def he2hb_ckpt(a: DistMatrix, every=None, bcast_impl: Optional[str] = None,
               async_snapshots=None, num_monitor: Optional[str] = None):
    """Checkpointed two-stage eig stage-1 reduction (ISSUE 13):
    ``he2hb_dist`` results (bitwise) with the multi-array carry — tile
    stack evolving toward the band, sharded reflector stack, replicated
    compact-WY accumulators — snapshotted every ``every`` panel steps.
    Returns DistTwoStage; raises ``Preempted`` under an armed kill
    fault.  Grid-locked carry, as geqrf_ckpt.

    ``num_monitor`` (Option.NumMonitor, ISSUE 15): ``on`` carries the
    per-panel reflector/τ orthogonality-loss proxy — the first eig-chain
    gauge — as a running max through the segment chain (results bitwise,
    collective-free: the panel factors are replicated), surfaced as the
    ``num.he2hb_orth_margin`` gauge / ``he2hb_orth_loss_max`` total;
    off keeps the plain (unchanged) segment jits."""
    ev = resolve_checkpoint(every)
    if a.m != a.n:
        raise ValueError("he2hb_ckpt needs a square matrix")
    if ev is None or _he2hb_panel_count(a.n, a.nb) == 0:
        return he2hb_dist(a, bcast_impl=bcast_impl, num_monitor=num_monitor)
    return _run("he2hb", a, 0, ev, resolve_bcast_impl(bcast_impl),
                resolve_num_monitor(num_monitor) == "on",
                async_snap=resolve_ckpt_async(async_snapshots))
