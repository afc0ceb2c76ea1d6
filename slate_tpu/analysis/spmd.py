"""SPMD safety passes: the distributed-deadlock bug classes.

Three jaxpr passes over every registered driver (lint.py wires them into
the trace loop beside the axis/precision/audit checks), plus a pure-data
proof over the broadcast engine's hop schedules:

``check_branch_collectives`` — every ``cond``/``switch`` branch must
issue the SAME ordered (collective, axes) sequence.  Under SPMD a
collective blocks until every device on the axis reaches the matching
call; if a replicated predicate ever diverges (or a branch is simply
written with a different collective order), devices park in different
collectives and the program deadlocks on real ICI.  Branch-uniform
sequences make the dispatch safe by construction, whatever the predicate
does.

``check_ppermute_bijection`` — every ``ppermute`` perm must use each
source at most once and each destination at most once, with indices in
range for the axis.  A duplicated destination silently drops one payload
(XLA keeps one, the other vanishes); a duplicated source double-sends; a
device absent from the destination list receives ZEROS, not its old
value — all of which trace fine and hang or corrupt only on hardware.

``check_donation_liveness`` — no value donated to a jitted call may be
read again afterwards (by a later eqn or as an output of the enclosing
jaxpr).  XLA may have reused the buffer; the read sees garbage.  PR 9's
memwatch catches *lost* donations at compile time; this catches the
inverse bug — a donation that succeeds while the caller still holds the
value — at trace time.

``check_hop_schedules`` — the broadcast engine's ring/doubling schedules
(parallel/comm.bcast_hop_schedule) proved as data for every impl x axis
size x root on the registry grid: pairwise-bijective hops, every hop
sourced from a device that already holds the payload, and the union of
destinations covering the whole axis.  ``SEEDED_SCHEDULES`` is the
self-test hook (lint --seed-violation ppermute-pair appends a broken
schedule the same way ast_checks.SEEDED_SOURCES carries seeded sources).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from jax.extend import core as jax_core

from .findings import Finding
from .jaxpr_checks import DATA_COLLECTIVES, _axes_of, _sub_jaxprs, iter_eqns

# Collectives that BLOCK until every device on the axis participates —
# divergent ordering across branches is a deadlock.  axis_index is local
# arithmetic under SPMD lowering and pbroadcast a replication annotation;
# neither synchronizes, so neither constrains branch ordering.
BLOCKING_COLLECTIVES = frozenset(DATA_COLLECTIVES | {"pmin", "pmax"})

# (label, size, root, hops) appended by lint --seed-violation
# ppermute-pair; cleared at the start of every run like SEEDED_SOURCES.
SEEDED_SCHEDULES: List[Tuple[str, int, int, list]] = []


def _collective_signature(jaxpr: jax_core.Jaxpr) -> Tuple:
    """Ordered (collective, axes) sequence a branch issues, flattened
    through sub-jaxprs.  Nested cond branches contribute their FIRST
    branch's sequence — the divergence check visits every cond eqn
    independently, so an inner mismatch is already its own finding and
    the outer comparison stays stable."""
    sig: List[Tuple] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in BLOCKING_COLLECTIVES:
            sig.append((name, _axes_of(eqn)))
            continue
        if name == "cond":
            subs = list(_sub_jaxprs(eqn))
            if subs:
                sig.extend(_collective_signature(subs[0]))
            continue
        for sub in _sub_jaxprs(eqn):
            sig.extend(_collective_signature(sub))
    return tuple(sig)


def _fmt_sig(sig: Tuple, limit: int = 6) -> str:
    parts = [f"{op}[{','.join(axes) or '-'}]" for op, axes in sig[:limit]]
    if len(sig) > limit:
        parts.append(f"...+{len(sig) - limit}")
    return " -> ".join(parts) if parts else "(none)"


def check_branch_collectives(
    closed: jax_core.ClosedJaxpr, where: str
) -> List[Finding]:
    """Invariant 4a: cond/switch branches issue identical ordered
    (collective, axes) sequences — the deadlock-free dispatch shape."""
    out: List[Finding] = []
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "cond":
            continue
        branches = list(_sub_jaxprs(eqn))
        sigs = [_collective_signature(b) for b in branches]
        if not sigs:
            continue
        bad = next((i for i, s in enumerate(sigs) if s != sigs[0]), None)
        if bad is None:
            continue
        out.append(
            Finding(
                "spmd-divergent-collectives",
                where,
                f"cond/switch branches issue divergent collective "
                f"sequences — branch 0: {_fmt_sig(sigs[0])}; branch "
                f"{bad}: {_fmt_sig(sigs[bad])} — devices disagreeing on "
                "the predicate would park in different collectives "
                "(distributed deadlock)",
            )
        )
        if len(out) >= 8:  # one deep driver can repeat one bad dispatch
            break
    return out


def _perm_findings(
    rule: str, where: str, perm: Sequence[Tuple[int, int]],
    size: Optional[int], what: str,
) -> List[Finding]:
    """Bijection + range findings for one src->dst pair list."""
    out: List[Finding] = []
    srcs = [s for s, _ in perm]
    dsts = [d for _, d in perm]
    dup_s = sorted({s for s in srcs if srcs.count(s) > 1})
    dup_d = sorted({d for d in dsts if dsts.count(d) > 1})
    if dup_s:
        out.append(Finding(rule, where, (
            f"{what} uses source device(s) {dup_s} more than once — a "
            "collective-permute source sends exactly one payload; the "
            "extra pair is silently dropped")))
    if dup_d:
        out.append(Finding(rule, where, (
            f"{what} targets destination device(s) {dup_d} more than "
            "once — XLA keeps one payload and drops the rest (silent "
            "data loss on real ICI)")))
    if size is not None:
        oob = sorted({v for v in srcs + dsts if not 0 <= v < size})
        if oob:
            out.append(Finding(rule, where, (
                f"{what} references device(s) {oob} outside the axis "
                f"(size {size})")))
    return out


def check_ppermute_bijection(
    closed: jax_core.ClosedJaxpr, axis_sizes: Dict[str, int], where: str
) -> List[Finding]:
    """Invariant 4b: every traced ppermute perm is a partial bijection
    (sources unique, destinations unique, indices in range).  JAX rejects
    out-of-range perms at trace time but duplicates trace silently."""
    out: List[Finding] = []
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "ppermute":
            continue
        perm = [tuple(p) for p in eqn.params.get("perm", ())]
        axes = _axes_of(eqn)
        size = axis_sizes.get(axes[0]) if axes else None
        out.extend(
            _perm_findings(
                "spmd-ppermute-bijection", where, perm, size,
                f"ppermute[{','.join(axes) or '?'}] perm",
            )
        )
        if len(out) >= 8:
            break
    return out


def _verify_schedule(
    label: str, size: int, root: int, hops: Sequence[Sequence[Tuple[int, int]]]
) -> List[Finding]:
    """One hop schedule proved as a store-and-forward relay."""
    out: List[Finding] = []
    covered = {root % size}
    for h, perm in enumerate(hops):
        what = f"hop {h}"
        out.extend(
            _perm_findings("spmd-ppermute-bijection", label, perm, size, what)
        )
        stray = sorted({s for s, _ in perm} - covered)
        if stray:
            out.append(Finding("spmd-ppermute-bijection", label, (
                f"hop {h} forwards from device(s) {stray} that have not "
                "received the payload yet — they would relay garbage")))
        covered |= {d for _, d in perm}
    missing = sorted(set(range(size)) - covered)
    if missing:
        out.append(Finding("spmd-ppermute-bijection", label, (
            f"schedule never delivers the payload to device(s) {missing} "
            "— a ppermute leaves non-destinations holding ZEROS, so the "
            "broadcast silently corrupts them")))
    return out


def check_hop_schedules(axis_sizes: Sequence[int] = (2, 4, 8)) -> List[Finding]:
    """Invariant 4b (engine half): every ring/doubling hop schedule the
    broadcast engine can emit on the registry grid's axis sizes, for
    every root, is a valid relay.  Seeded schedules ride the same
    verifier so the gate provably trips."""
    from ..parallel.comm import bcast_hop_schedule

    cases: List[Tuple[str, int, int, list]] = []
    for impl in ("ring", "doubling"):
        for size in axis_sizes:
            for root in range(size):
                cases.append((
                    f"comm:{impl}[size={size},root={root}]",
                    size, root, bcast_hop_schedule(impl, size, root),
                ))
    cases.extend(SEEDED_SCHEDULES)
    out: List[Finding] = []
    for label, size, root, hops in cases:
        out.extend(_verify_schedule(label, size, root, hops))
    return out


def check_donation_liveness(
    closed: jax_core.ClosedJaxpr, where: str
) -> List[Finding]:
    """Invariant 4c: a value donated to a jitted call (a pjit eqn with a
    True ``donated_invars`` slot) is dead afterwards — no later eqn may
    read it and the enclosing jaxpr may not return it."""
    out: List[Finding] = []

    def walk(jaxpr: jax_core.Jaxpr) -> None:
        donated: Dict[jax_core.Var, str] = {}
        for eqn in jaxpr.eqns:
            # reads checked BEFORE this eqn's own donations register: the
            # donating call itself legitimately reads its operand
            for v in eqn.invars:
                if isinstance(v, jax_core.Var) and v in donated:
                    out.append(Finding("spmd-donation-liveness", where, (
                        f"value donated to jit {donated[v]!r} is read "
                        f"again by a later {eqn.primitive.name} — the "
                        "buffer may already be reused by XLA "
                        "(use-after-donate)")))
                    del donated[v]  # one finding per donated value
            dv = eqn.params.get("donated_invars")
            if dv and any(dv):
                callee = str(eqn.params.get("name", eqn.primitive.name))
                for v, d in zip(eqn.invars, dv):
                    if d and isinstance(v, jax_core.Var):
                        donated[v] = callee
            for sub in _sub_jaxprs(eqn):
                walk(sub)
        for v in jaxpr.outvars:
            if isinstance(v, jax_core.Var) and v in donated:
                out.append(Finding("spmd-donation-liveness", where, (
                    f"value donated to jit {donated[v]!r} is returned "
                    "from the enclosing jaxpr — the caller would read a "
                    "buffer XLA may have reused (use-after-donate)")))
                del donated[v]

    walk(closed.jaxpr)
    return out
