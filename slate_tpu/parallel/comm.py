"""Shared shard_map communication/indexing helpers for the distributed
kernels (summa / dist_chol / dist_lu / dist_trsm).

These are the TPU-native forms of the reference's tile-communication verbs
(BaseMatrix.hh).  ``tileBcast`` along a process row/column has two
lowerings, selected by ``Option.BcastImpl`` (see ``resolve_bcast_impl``):

- ``psum`` (the legacy path): a masked ``lax.psum`` over one mesh axis —
  the owner contributes its tiles, everyone else zeros — which XLA lowers
  to an ICI all-reduce.  An all-reduce of B bytes moves ~2(s-1)/s * B per
  link (reduce-scatter + all-gather, Thakur et al., IJHPCA 2005) and burns
  s-1 pointless tile additions per hop.
- ``ring`` / ``doubling`` (the broadcast engine): ``lax.ppermute`` point-
  to-point hops rooted at the owner — a store-and-forward ring pipeline
  (s-1 single-pair hops) or a recursive-doubling tree (log2 s hops,
  power-of-two axes) — moving exactly (s-1)/s * B per link, HALF the
  all-reduce bytes, with no additions at all (the owner's exact bytes
  arrive, bitwise).  The owner index is usually a traced loop residue
  (k % q), so the rooted schedules dispatch through one ``lax.switch``
  over the s static roots; every device evaluates the same replicated
  branch, and only the links that carry useful data send.

``auto`` (the default) picks doubling on power-of-two axes, ring
otherwise.  SLATE routes broadcast over point-to-point links for the
same reason (Gates et al., SC'19).
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map

from .mesh import COL_AXIS, ROW_AXIS

PRECISE = lax.Precision.HIGHEST


def shard_map_compat(f, mesh, in_specs, out_specs, **kw):
    """``jax.shard_map`` with the (f, mesh, in_specs, out_specs) order
    every kernel uses.  The one place kernels reach shard_map through
    (slate_lint's ast pass flags raw shard_map imports), so the installed
    signature is validated in one spot: unknown keywords raise
    TypeError."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw)

# default trailing-update segmentation for the bucketed factorization
# kernels (4 measured best on the CPU mesh; artifacts/README.md)
BUCKETS = 4


# ---------------------------------------------------------------------------
# Communication-volume audit (VERDICT r4 item 7).  Trace-time hooks: every
# audited collective records its per-device payload bytes while a
# ``comm_audit()`` context is active.  Shapes are static under jit, so the
# traced operand size IS the per-execution payload; a ``lax.fori_loop`` body
# traces exactly once, so the kernels wrap their loops in ``audit_scope``
# with the trip count to recover totals.  The analogue of instrumenting the
# reference's tileBcast/listReduce with byte counters (BaseMatrix.hh).
# ---------------------------------------------------------------------------

_AUDIT: Optional[list] = None
_AUDIT_MULT = [1]

# Schedule-capture channel (obs.flight / obs.schedule): a SECOND audit
# stream whose records additionally carry the issuing loop phase, the
# issue step (a Python int for unrolled prologue/drain code, None inside
# a fori_loop body where k is a tracer), and — for ppermute hops — the
# (src, dst) pair list of the hop.  Kept separate from ``_AUDIT`` so the
# (op, nbytes, mult) tuple every existing consumer parses never changes
# shape.  Like the primary audit it records at TRACE time only.
_SCHED: Optional[list] = None
_PHASE_CTX = [(None, None)]  # (phase, step) during kernel tracing


@contextlib.contextmanager
def sched_audit(propagate: bool = False):
    """Yield a list that fills with (op, payload_bytes, multiplicity,
    phase, step, pairs) records for every audited collective traced while
    active — the phase/step tags come from the ``phase_scope`` markers
    the pipelined loop helpers place around their fetch/panel/update
    callbacks, so one trace of a mesh kernel yields a per-phase
    communication schedule (the obs.schedule.ScheduleModel substrate).
    Same re-trace contract as ``comm_audit``: a jit cache hit records
    nothing.  ``propagate=True`` re-appends the captured records to the
    enclosing schedule audit on exit (obs.driver_span's hop absorption
    observes without stealing)."""
    global _SCHED
    old, _SCHED = _SCHED, []
    try:
        yield _SCHED
    finally:
        records, _SCHED = _SCHED, old
        if propagate and old is not None:
            old.extend(records)


@contextlib.contextmanager
def phase_scope(phase: str, step=None):
    """Tag work traced inside as loop phase ``phase`` of step ``step``.

    Two marks, both metadata only — no op, numeric or collective changes,
    and the jaxpr stays identical:

    - collectives traced inside carry the phase in ``sched_audit``
      records (the obs.schedule capture);
    - every op traced inside sits under ``jax.named_scope(phase)``, so
      the compiled program's op metadata (the ``op_name`` a profiler
      trace reports per device op) names the phase.

    The factor loops' vocabulary: ``panel`` (diagonal-block factor, panel
    solves, the pivot search), ``swap`` (row interchanges), ``bcast``
    (panel broadcasts), ``bulk`` (the trailing update, ``narrow`` pieces
    included) and ``regroup`` (bucket-boundary slices and writes of the
    scanned single-chip factors)."""
    _PHASE_CTX.append((phase, _step_id(step)))
    try:
        with jax.named_scope(phase):
            yield
    finally:
        _PHASE_CTX.pop()


def _step_id(k):
    """``k`` as a Python int when concrete (prologue/drain unrolled
    steps), None when it is a loop tracer."""
    if k is None:
        return None
    try:
        return int(k)
    except (TypeError, jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        return None


@contextlib.contextmanager
def comm_audit(propagate: bool = False):
    """Yield a list that fills with (op, payload_bytes, multiplicity)
    records for every audited collective traced while active.  Callers
    must ensure the target kernel actually re-traces (jax.clear_caches()
    or a fresh shape) — a jit cache hit records nothing.

    ``propagate=True`` re-appends the captured records to the enclosing
    audit (if any) on exit, so a nested capture observes without stealing
    — obs.driver_span uses this to absorb bytes per span while an outer
    audit (slate_lint's trace pass, the comm-volume tool) still sees
    every record."""
    global _AUDIT
    old, _AUDIT = _AUDIT, []
    try:
        yield _AUDIT
    finally:
        records, _AUDIT = _AUDIT, old
        if propagate and old is not None:
            old.extend(records)


@contextlib.contextmanager
def audit_scope(mult):
    """Multiply records inside by ``mult`` (enclosing loop trip count)."""
    _AUDIT_MULT.append(_AUDIT_MULT[-1] * int(mult))
    try:
        yield
    finally:
        _AUDIT_MULT.pop()


def _rec(op: str, x: jax.Array) -> None:
    if _AUDIT is not None:
        _AUDIT.append((op, int(x.size) * x.dtype.itemsize, _AUDIT_MULT[-1]))
    if _SCHED is not None:
        ph, st = _PHASE_CTX[-1]
        _SCHED.append(
            (op, int(x.size) * x.dtype.itemsize, _AUDIT_MULT[-1], ph, st, None)
        )


def psum_a(x: jax.Array, axis: str) -> jax.Array:
    """Audited lax.psum."""
    _rec(f"psum[{axis}]", x)
    return lax.psum(x, axis)


def all_gather_a(x: jax.Array, axis_name: str, **kw) -> jax.Array:
    """Audited lax.all_gather (kw passes through, e.g. tensor ``axis=``)."""
    _rec(f"all_gather[{axis_name}]", x)
    return lax.all_gather(x, axis_name, **kw)


def psum_scatter_a(x: jax.Array, axis_name: str, **kw) -> jax.Array:
    """Audited lax.psum_scatter."""
    _rec(f"psum_scatter[{axis_name}]", x)
    return lax.psum_scatter(x, axis_name, **kw)


def ppermute_a(x: jax.Array, axis_name: str, perm) -> jax.Array:
    """Audited lax.ppermute.  The recorded ``nbytes`` is the total bytes
    crossing links in this hop — operand bytes x len(perm) source→target
    pairs — NOT the per-device operand size: a collective-permute only
    sends from the listed sources, so per-hop link bytes (not payload
    shape) is the honest wire unit.  ``obs.comm_audit.summarize`` divides
    by the axis size to recover per-device received bytes."""
    _rec_hop(f"ppermute[{axis_name}]", x, len(perm), perm)
    return lax.ppermute(x, axis_name, perm)


def _rec_hop(op: str, x: jax.Array, npairs: int, perm=None) -> None:
    if npairs <= 0:
        return
    if _AUDIT is not None:
        _AUDIT.append(
            (op, int(x.size) * x.dtype.itemsize * npairs, _AUDIT_MULT[-1])
        )
    if _SCHED is not None:
        ph, st = _PHASE_CTX[-1]
        _SCHED.append(
            (op, int(x.size) * x.dtype.itemsize * npairs, _AUDIT_MULT[-1],
             ph, st, list(perm) if perm is not None else None)
        )


# ---------------------------------------------------------------------------
# Broadcast engine (Option.BcastImpl): rooted broadcast/reduce lowerings.
#
# Selection is a TRACE-TIME property: every kernel that consumes the
# wrappers below threads the resolved impl through its jit as a static
# argument and wraps kernel tracing in ``bcast_impl_scope`` — a cache hit
# on a different impl is impossible by construction.  Kernels that do NOT
# thread the option (dist_qr / dist_twostage / dist_aux / dist_stedc's
# static-owner broadcasts) trace with the scope at its default, ``psum``,
# keeping their schedules byte-for-byte what they were.
# ---------------------------------------------------------------------------

BCAST_IMPLS = ("psum", "ring", "doubling", "auto")
BCAST_IMPL_ENV = "SLATE_TPU_BCAST_IMPL"

_IMPL_DEFAULT = [None]  # session default (use_bcast_impl), outside jit
_IMPL_ACTIVE = ["psum"]  # trace-time lowering (bcast_impl_scope)


def _check_impl(impl: str) -> str:
    if impl not in BCAST_IMPLS:
        raise ValueError(
            f"unknown bcast impl {impl!r}; expected one of {BCAST_IMPLS}"
        )
    return impl


def resolve_bcast_impl(impl: Optional[str] = None) -> str:
    """Resolve an Option.BcastImpl value at driver level (OUTSIDE jit):
    explicit argument > ``use_bcast_impl`` context default >
    ``SLATE_TPU_BCAST_IMPL`` environment > ``auto``.  The returned string
    is what drivers pass into their jitted kernels as a static argument
    (``auto`` stays ``auto``: the per-axis choice depends on each axis'
    size and is made inside the kernel)."""
    if impl is None:
        impl = _IMPL_DEFAULT[-1]
    if impl is None:
        impl = os.environ.get(BCAST_IMPL_ENV) or "auto"
    return _check_impl(impl)


@contextlib.contextmanager
def use_bcast_impl(impl: str):
    """Set the session-default broadcast lowering for drivers called
    inside (tests / CI sweeps); an explicit ``bcast_impl=`` argument still
    wins.  Safe across jit caches: the resolved value is a static kernel
    argument, so switching impls recompiles rather than reusing."""
    _IMPL_DEFAULT.append(_check_impl(impl))
    try:
        yield
    finally:
        _IMPL_DEFAULT.pop()


@contextlib.contextmanager
def bcast_impl_scope(impl: str):
    """Activate a lowering for the broadcast wrappers traced inside —
    used by the kernels around their shard_map call, with ``impl`` a
    static jit argument of the enclosing kernel."""
    _IMPL_ACTIVE.append(_check_impl(impl))
    try:
        yield
    finally:
        _IMPL_ACTIVE.pop()


def _axis_size(axis: str) -> int:
    """Static mesh-axis size inside shard_map: psum of a unit literal is
    evaluated at trace time to the axis size (no runtime collective)."""
    return int(lax.psum(1, axis))


def _impl_for(size: int) -> str:
    """Concrete per-axis lowering from the active scope: auto prefers the
    log2-hop doubling tree on power-of-two axes, the ring pipeline
    otherwise; explicit doubling on a non-power-of-two axis degrades to
    ring (same bytes, s-1 hops) rather than erroring."""
    impl = _IMPL_ACTIVE[-1]
    if impl == "auto":
        return "doubling" if size & (size - 1) == 0 else "ring"
    if impl == "doubling" and size & (size - 1):
        return "ring"
    return impl


def _bcast_hops(impl: str, size: int, root: int):
    """Static hop schedule for a rooted broadcast: a list of ppermute
    perms.  ring: s-1 store-and-forward single-pair hops around the ring;
    doubling: log2(s) hops, hop h multicasting from the 2^h devices that
    already hold the payload.  Both move exactly (s-1) pair-payloads."""
    if impl == "ring":
        return [
            [((root + h - 1) % size, (root + h) % size)]
            for h in range(1, size)
        ]
    hops, h = [], 1
    while h < size:  # doubling (size is a power of two here)
        hops.append(
            [((root + i) % size, (root + i + h) % size) for i in range(h)]
        )
        h *= 2
    return hops


def bcast_hop_schedule(impl: str, size: int, root: int = 0):
    """The rooted-broadcast hop schedule as plain data: the exact list of
    ppermute perms ``_rooted_bcast`` traces for ``impl`` on an axis of
    ``size`` rooted at ``root`` — including the auto/degradation rules
    (doubling on a non-power-of-two axis degrades to ring).  Exposed for
    ``slate_tpu.analysis.spmd``, which proves every schedule is a valid
    store-and-forward relay: pairwise-bijective hops, every source already
    holding the payload, the union of destinations covering the axis.
    ``psum`` is not a hop lowering (it has no schedule to prove)."""
    _check_impl(impl)
    if impl == "psum":
        raise ValueError("psum is not a hop lowering; no schedule exists")
    if size <= 1:
        return []
    if impl == "auto":
        impl = "doubling" if size & (size - 1) == 0 else "ring"
    elif impl == "doubling" and size & (size - 1):
        impl = "ring"
    return _bcast_hops(impl, size, root % size)


def _concrete_root(owner, size: int):
    """``owner`` as a Python int when it is trace-time concrete (prologue
    prefetches index with Python ints; some callers pass static owners),
    else None.  A concrete root skips the lax.switch dispatch entirely —
    only the owner's hop schedule is traced."""
    try:
        return int(owner) % size
    except (TypeError, jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        return None


def _rooted_dispatch(x, owner, axis, size, impl, branch):
    """Shared tail of the rooted verbs: audit one hop-set for the whole
    schedule (recording inside every switch branch would overcount by the
    branch count), then dispatch — directly for a concrete owner, through
    one lax.switch over the static roots for a traced one.  The audited
    hop pairs are the concrete owner's schedule when known, the root-0
    schedule otherwise (the hop structure is root-independent; a traced
    owner rotates the same pairs)."""
    root = _concrete_root(owner, size)
    for perm in _bcast_hops(impl, size, root if root is not None else 0):
        _rec_hop(f"ppermute[{axis}]", x, len(perm), perm)
    if root is not None:
        return branch(root)(x)
    return lax.switch(owner, [branch(o) for o in range(size)], x)


def _rooted_bcast(x: jax.Array, owner, axis: str) -> jax.Array:
    """Deliver the owner's ``x`` to every device on ``axis`` (tileBcast).

    ``owner`` may be a traced loop residue; the static hop schedules are
    dispatched through one ``lax.switch`` over the axis' roots (the owner
    index is replicated, so every device takes the same branch).  Results
    are the owner's exact bytes — bitwise identical to the masked-psum
    path, which only ever adds exact zeros to them."""
    size = _axis_size(axis)
    impl = _impl_for(size)
    if impl == "psum":
        me = lax.axis_index(axis)
        return psum_a(jnp.where(me == owner, x, jnp.zeros_like(x)), axis)
    if size == 1:
        return x
    me = lax.axis_index(axis)

    def branch(root):
        hops = _bcast_hops(impl, size, root)

        def br(v):
            d = (me - root) % size
            out = v
            covered = 1  # devices at ring distance < covered hold the payload
            for perm in hops:
                r = lax.ppermute(out, axis, perm)
                out = jnp.where(
                    (d >= covered) & (d < covered + len(perm)), r, out
                )
                covered += len(perm)
            return out

        return br

    return _rooted_dispatch(x, owner, axis, size, impl, branch)


def _rooted_reduce(x: jax.Array, owner, axis: str) -> jax.Array:
    """Owner-rooted reduction (the tileReduce counterpart): the sum of
    ``x`` over ``axis`` lands on mesh index ``owner``; every other device
    returns zeros.  ring: a deterministic s-1-hop accumulation chain
    toward the root; doubling: the reversed multicast tree (log2 s hops,
    pairwise folds).  Half the all-reduce bytes for the same delivered
    sum — the schedule for owner-consumed reductions (stationary-operand
    partial sums) where psum wastes the replicated result."""
    size = _axis_size(axis)
    me = lax.axis_index(axis)
    impl = _impl_for(size)
    if impl == "psum":
        full = psum_a(x, axis)
        return jnp.where(me == owner, full, jnp.zeros_like(x))
    if size == 1:
        return x

    def branch(root):
        # the broadcast hop schedule run BACKWARDS with reversed pairs:
        # partial sums fold toward the root in a fixed order, so the
        # delivered sum is deterministic (unlike psum's backend order)
        hops = list(reversed(_bcast_hops(impl, size, root)))

        def br(v):
            d = (me - root) % size
            out = v
            for perm in hops:
                rev = [(dst, src) for src, dst in perm]
                r = lax.ppermute(out, axis, rev)
                recv = False
                for _, dst in rev:
                    recv = recv | (d == (dst - root) % size)
                out = jnp.where(recv, out + r, out)
            return jnp.where(me == root, out, jnp.zeros_like(out))

        return br

    return _rooted_dispatch(x, owner, axis, size, impl, branch)


def bcast_from_col(x: jax.Array, owner_col) -> jax.Array:
    """Broadcast ``x`` from mesh column ``owner_col`` to all columns
    (tileBcast along a process row, BaseMatrix.hh:1917), lowered per the
    active ``bcast_impl_scope``."""
    return _rooted_bcast(x, owner_col, COL_AXIS)


def bcast_from_row(x: jax.Array, owner_row) -> jax.Array:
    return _rooted_bcast(x, owner_row, ROW_AXIS)


def reduce_to_col(x: jax.Array, owner_col) -> jax.Array:
    """Sum ``x`` over the column axis INTO mesh column ``owner_col``
    (owner-rooted listReduce); other columns receive zeros."""
    return _rooted_reduce(x, owner_col, COL_AXIS)


def reduce_to_row(x: jax.Array, owner_row) -> jax.Array:
    return _rooted_reduce(x, owner_row, ROW_AXIS)


def num_gauge_dtype(dtype):
    """Gauge dtype for the Option.NumMonitor loop carries (obs/numerics):
    real, and at least f32 so bf16 runs do not saturate the running
    extrema.  Single source shared by the LU and Cholesky kernels so the
    gauge precision policy cannot drift between them."""
    rdt = jnp.real(jnp.zeros((), dtype)).dtype
    return jnp.float32 if rdt == jnp.bfloat16 else rdt


def local_indices(p: int, q: int, mtl: int, ntl: int):
    """(r, c, i_log, j_log): my mesh coordinates and the logical tile
    indices of my local tile stack under cyclic layout (the trace-time
    analogue of tileRank^-1, func.hh:154)."""
    r = lax.axis_index(ROW_AXIS)
    c = lax.axis_index(COL_AXIS)
    i_log = r + jnp.arange(mtl) * p
    j_log = c + jnp.arange(ntl) * q
    return r, c, i_log, j_log


def bcast_diag_tile(
    t_loc: jax.Array, k, p: int, q: int, nb: int, roff=0, coff=0
) -> jax.Array:
    """Deliver tile (k, k) to every device (the reference's tileBcast of
    the panel-head tile): a two-hop rooted broadcast — along the row axis
    from mesh row k % p, then along the column axis from mesh column
    k % q.  Under the legacy ``psum`` lowering this is the historical
    masked DOUBLE psum (~4x the ring-broadcast bytes: two all-reduces of
    one tile); the engine lowerings move (p-1)/p + (q-1)/q tile payloads
    total.  ``roff``/``coff`` shift local tile indexing when ``t_loc`` is
    a trailing view (bucketed kernels)."""
    dtile = lax.dynamic_slice(
        t_loc, (k // p - roff, k // q - coff, 0, 0), (1, 1, nb, nb)
    )[0, 0]
    return bcast_owner_tile(dtile, k, p, q)


def bcast_owner_tile(dtile: jax.Array, k, p: int, q: int) -> jax.Array:
    """The broadcast half of :func:`bcast_diag_tile`: every device
    passes its candidate ``dtile`` and receives the one held by the
    device at mesh (k % p, k % q)."""
    if _IMPL_ACTIVE[-1] == "psum":
        r = lax.axis_index(ROW_AXIS)
        c = lax.axis_index(COL_AXIS)
        own = (r == k % p) & (c == k % q)
        dtile = jnp.where(own, dtile, jnp.zeros_like(dtile))
        return psum_a(psum_a(dtile, ROW_AXIS), COL_AXIS)
    # hop 1 delivers mesh row (k % p)'s local slice down each column —
    # column k % q now holds tile (k, k) everywhere; hop 2 roots there.
    # No masking anywhere: the owner's exact bytes travel.
    d1 = _rooted_bcast(dtile, k % p, ROW_AXIS)
    return _rooted_bcast(d1, k % q, COL_AXIS)


def route_to_block_cyclic_rows(
    part: jax.Array, targets: jax.Array, p: int, mtl_out: int,
    extra: Optional[jax.Array] = None,
) -> jax.Array:
    """Deliver per-target-row partials to their block-cyclic owners.

    ``part`` is (t, q, ntl, nb, nb): slot t carries the contribution to
    logical output row ``targets[t]`` for all q column shards.  The
    partials are scattered into per-target-row slots (row ``g`` lives at
    mesh row ``g % p``, local slot ``g // p``), the column shards are
    psum-scattered to their mesh columns, and the per-row slots are
    psum-scattered to their mesh rows — the stationary-operand
    delivery pattern shared by trsmA's transposed path and hemmA
    (src/trsmA.cc / src/hemmA.cc).  ``extra``, when given, is a
    (mtl_out, q, ntl, nb, nb) contribution already belonging to the
    calling device's own mesh row (hemmA's stored part)."""
    q_, ntl = part.shape[1], part.shape[2]
    nb = part.shape[-1]
    r = lax.axis_index(ROW_AXIS)
    routed = jnp.zeros((p, mtl_out, q_, ntl, nb, nb), part.dtype)
    if extra is not None:
        routed = routed.at[r].add(extra)
    routed = routed.at[targets % p, targets // p].add(part, mode="drop")
    out = psum_scatter_a(routed, COL_AXIS, scatter_dimension=2, tiled=False)
    # scatter the per-row slots too (dim 0 size == p): each mesh row
    # receives only its own slot — p x less data than psum + slice
    return psum_scatter_a(out, ROW_AXIS, scatter_dimension=0, tiled=False)


# ---------------------------------------------------------------------------
# Lookahead pipelining (Option.Lookahead; SURVEY §2.5 P3).  The reference
# overlaps each step's panel broadcast with the previous step's trailing
# update via lookahead task queues (gemmC.cc:147-176, potrf.cc:129-133).
# Inside one lax.fori_loop the carry serializes iterations, so XLA cannot
# overlap step k+1's collective with step k's einsum on its own: the
# kernels below restructure the loop so the independent work lives in the
# SAME iteration body, where the latency-hiding scheduler can interleave
# it.  Two carry patterns cover every mesh k-loop:
#
# * ``prefetch_bcast`` — read-only operands (SUMMA-class accumulation
#   loops, trsm's A panels): broadcast step k+d's panel while step k's
#   buffered panel feeds the MXU.  Arbitrary depth d (a d-deep FIFO).
# * ``pipelined_factor_loop`` — factorizations (potrf/LU), where panel
#   k+1 depends on update k: defer each step's trailing update into the
#   next iteration, refresh only the row/column the next panel reads
#   (``narrow``), issue the panel broadcasts, then apply the bulk of the
#   deferred update (``bulk``) — the broadcast and the big einsum are
#   independent.  Effective depth caps at 1: panel k+2 reads column k+1,
#   which needs update k applied first, so deeper prefetch has no legal
#   reorder.
#
# Both patterns reorder ONLY independent work: every element receives
# exactly the same arithmetic in the same per-element order, so results
# are bitwise-identical to the strict schedule at any depth (enforced by
# tests/test_lookahead.py), and total audited comm bytes are unchanged —
# lookahead moves WHEN bytes move, never how many.
# ---------------------------------------------------------------------------


def la_depth(lookahead, nt: int) -> int:
    """Resolve an Option.Lookahead value to a usable pipeline depth:
    ``None`` means the option default (1, the reference's default
    lookahead), clamped to [0, nt]."""
    if lookahead is None:
        from ..types import Option, get_option

        lookahead = get_option(None, Option.Lookahead)
    return max(0, min(int(lookahead), int(nt)))


def la_live_buffers(depth: int, factor_loop: bool = False) -> int:
    """Panel-broadcast payloads the lookahead schedule pins LIVE at once
    — the per-device residency the pipelining buys overlap with, and the
    depth term of ``obs.memmodel.MemoryModel`` (single source: changing
    a loop's carry structure here moves the memory model with it).

    ``prefetch_bcast`` keeps the d-deep FIFO plus the in-flight head:
    1 + d payloads.  ``pipelined_factor_loop`` carries the deferred
    step-(k-1) payload next to the freshly-broadcast step-k payload and
    its effective depth caps at 1: 1 + 2·min(d, 1) payload pairs."""
    d = max(0, int(depth))
    if factor_loop:
        return 1 + 2 * min(d, 1)
    return 1 + d


def prefetch_bcast(nt: int, depth: int, fetch, consume, state):
    """Software-pipelined k-loop over READ-ONLY panel broadcasts.

    ``fetch(k)`` builds step k's panel pytree purely from loop-invariant
    operands (rooted panel broadcasts / gathers of stationary tiles);
    ``consume(k, panel, state)`` performs step k's update (and any
    serial-chain collectives of its own).  Depth 0 reproduces the strict
    broadcast→update schedule exactly.  Depth d >= 1 double-buffers:
    a d-deep FIFO of prefetched panels is filled before the loop, each
    iteration issues fetch(k + d) BEFORE consume(k, fifo head) so the
    broadcast for a future step is independent of — and overlappable
    with — the current trailing update, and the last d panels drain
    after the loop.  Total broadcast count (and audited bytes) is
    unchanged: d prologue + (nt - d) in-loop fetches = nt.
    """
    d = max(0, min(int(depth), int(nt)))
    if d == 0:
        def body(k, st):
            with phase_scope("bcast", k):
                panel = fetch(k)
            with phase_scope("bulk", k):
                return consume(k, panel, st)

        with audit_scope(nt):
            return lax.fori_loop(0, nt, body, state)

    # prologue: fill the FIFO with panels 0..d-1 (each audited once)
    def _pro(k):
        with phase_scope("bcast", k):
            return fetch(k)

    buf = jax.tree.map(lambda *xs: jnp.stack(xs), *[_pro(k) for k in range(d)])

    def body(k, carry):
        st, fifo = carry
        head = jax.tree.map(lambda b: b[0], fifo)
        with phase_scope("bcast", k):
            nxt = fetch(k + d)  # issued before the update consumes the head
        fifo = jax.tree.map(
            lambda b, nx: jnp.concatenate([b[1:], nx[None]]), fifo, nxt
        )
        with phase_scope("bulk", k):
            st = consume(k, head, st)
        return st, fifo

    with audit_scope(nt - d):
        state, buf = lax.fori_loop(0, nt - d, body, (state, buf))
    for i in range(d):  # epilogue: drain the FIFO (no fetches left)
        state = consume(nt - d + i, jax.tree.map(lambda b: b[i], buf), state)
    return state


def pipelined_factor_loop(k0, k1, depth, panel, narrow, bulk, state, zero_payload):
    """Deferred-trailing-update pipelining for factorization k-loops.

    ``panel(k, state) -> (state, payload)``: diag-tile factor + panel
    solves + panel broadcasts of step k; must read only the local tile
    slots ``narrow`` has refreshed (the logical row/column k slots).
    ``narrow(k, state, payload)``: apply the carried step-(k-1) trailing
    update to exactly those slots.
    ``bulk(k, state, payload)``: apply the carried update everywhere
    ``narrow`` did not (``k=None``: everywhere — the strict form and the
    post-loop drain).

    Depth 0 is the strict schedule (panel, then full update, per step).
    Depth >= 1 carries each step's update payload into the next
    iteration: the body runs narrow → panel → bulk, so step k's panel
    broadcasts are issued between two halves of step k-1's update and
    are data-independent of the bulk einsum — the overlap window.  The
    first iteration consumes ``zero_payload`` (subtracting exact zeros,
    bitwise identity) and the last payload drains after the loop.
    """
    n = int(k1) - int(k0)
    if n <= 0:
        return state
    if int(depth) <= 0:
        def body(k, st):
            with phase_scope("panel", k):
                st, pl = panel(k, st)
            with phase_scope("bulk", k):
                return bulk(None, st, pl)

        with audit_scope(n):
            return lax.fori_loop(k0, k1, body, state)

    def body(k, carry):
        st, pl = carry
        with phase_scope("bulk", k):
            st = narrow(k, st, pl)
        with phase_scope("panel", k):
            st, pl_new = panel(k, st)
        with phase_scope("bulk", k):
            st = bulk(k, st, pl)
        return st, pl_new

    with audit_scope(n):
        state, pl_last = lax.fori_loop(k0, k1, body, (state, zero_payload))
    with phase_scope("bulk", k1 - 1):
        return bulk(None, state, pl_last)


def bucket_plan(nt: int, p: int, q: int, nbuckets: int = BUCKETS):
    """Static trailing-update segmentation shared by the bucketed
    factorization kernels: yields (k0, k1, s0r, s0c) per bucket, where
    s0r/s0c are uniform safe row/col tile cuts (every device keeps tiles
    any rank may still touch — over-keeps at most one tile row/col)."""
    nbkts = min(nbuckets, nt)
    bounds = [nt * g // nbkts for g in range(nbkts)] + [nt]
    for g in range(nbkts):
        k0, k1 = bounds[g], bounds[g + 1]
        yield k0, k1, max(0, (k0 - p + 1) // p), max(0, (k0 - q + 1) // q)
