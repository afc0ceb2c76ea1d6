"""Jaxpr-walking checks: collective axis names, dot_general precision,
payload upcasts, loop audit coverage, and donation aliasability.

Every check operates on the jaxpr produced by ``jax.make_jaxpr`` over a
registered driver (registry.py) traced on the synthetic CPU mesh — shapes
and dtypes are exact, nothing executes.
"""

from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
from jax.extend import core as jax_core

from .findings import Finding

# primitives that move tile data between devices (the audited verbs)
DATA_COLLECTIVES = frozenset(
    {"psum", "psum_scatter", "all_gather", "ppermute", "all_to_all"}
)
# scalar/control collectives: still need declared axis names, but are not
# payload-bearing for the audit/upcast rules
SCALAR_COLLECTIVES = frozenset({"pmin", "pmax", "axis_index", "pbroadcast"})
LOOP_PRIMS = frozenset({"while", "scan"})


def _sub_jaxprs(eqn) -> Iterator[jax_core.Jaxpr]:
    for val in eqn.params.values():
        if isinstance(val, jax_core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jax_core.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, jax_core.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, jax_core.Jaxpr):
                    yield item


def iter_eqns(jaxpr: jax_core.Jaxpr, loop_depth: int = 0):
    """Yield (eqn, loop_depth) over the jaxpr and every sub-jaxpr.

    ``loop_depth`` counts enclosing while/scan bodies — a collective at
    depth > 0 executes once per trip, which is what ``audit_scope`` has to
    account for."""
    for eqn in jaxpr.eqns:
        yield eqn, loop_depth
        inner = loop_depth + (1 if eqn.primitive.name in LOOP_PRIMS else 0)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, inner)


def _axes_of(eqn) -> Tuple:
    """Normalized tuple of axis names used by a collective eqn."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    flat = []
    for a in axes:
        if isinstance(a, (tuple, list)):
            flat.extend(a)
        else:
            flat.append(a)
    # positional axes (ints) arise from vmap-style reductions, not mesh
    # collectives — they are not names and are skipped by the axis check
    return tuple(a for a in flat if isinstance(a, str))


def check_collective_axes(
    closed: jax_core.ClosedJaxpr, allowed: Sequence[str], where: str
) -> List[Finding]:
    """Invariant 1a: every collective rides a declared mesh axis."""
    out = []
    seen = set()
    for eqn, _ in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in DATA_COLLECTIVES and name not in SCALAR_COLLECTIVES:
            continue
        for ax in _axes_of(eqn):
            if ax not in allowed and (name, ax) not in seen:
                seen.add((name, ax))
                out.append(
                    Finding(
                        "axis-name",
                        where,
                        f"{name} over axis {ax!r}, not a declared mesh axis "
                        f"{tuple(allowed)}",
                    )
                )
    return out


def check_dot_precision(closed: jax_core.ClosedJaxpr, where: str) -> List[Finding]:
    """Invariant 2a: floating dot_generals carry Precision.HIGHEST.

    Integer dots (the Ozaki int8 planes) have no precision semantics and
    are skipped.  A driver with an intentional lower-precision contraction
    takes a waiver naming it."""
    import jax.numpy as jnp
    from jax.lax import Precision

    out = []
    count = 0
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "dot_general":
            continue
        dtype = eqn.invars[0].aval.dtype
        if not jnp.issubdtype(dtype, jnp.floating) and not jnp.issubdtype(
            dtype, jnp.complexfloating
        ):
            continue
        prec = eqn.params.get("precision")
        if isinstance(prec, (tuple, list)):
            ok = all(p == Precision.HIGHEST for p in prec)
        else:
            ok = prec == Precision.HIGHEST
        if not ok:
            count += 1
            if count <= 8:  # cap repeats; one kernel often repeats one dot
                out.append(
                    Finding(
                        "precision",
                        where,
                        f"dot_general on {dtype} with precision={prec!r} "
                        "(want Precision.HIGHEST or a waiver)",
                    )
                )
    return out


def _widest_float_bits(avals) -> int:
    import jax.numpy as jnp

    bits = 0
    for a in avals:
        dt = getattr(a, "dtype", None)
        if dt is None:
            continue
        if jnp.issubdtype(dt, jnp.complexfloating) or jnp.issubdtype(
            dt, jnp.floating
        ):
            # finfo(complex).bits is already the per-COMPONENT width
            bits = max(bits, jnp.finfo(dt).bits)
    return bits


def check_comm_upcast(closed: jax_core.ClosedJaxpr, where: str) -> List[Finding]:
    """Invariant 2b: no collective payload is silently wider than the
    widest floating input — a f32 kernel psumming f64 doubles its ICI
    bytes without anyone asking for it."""
    import jax.numpy as jnp

    in_bits = _widest_float_bits(closed.in_avals)
    if in_bits == 0:
        return []
    out = []
    seen = set()
    for eqn, _ in iter_eqns(closed.jaxpr):
        if eqn.primitive.name not in DATA_COLLECTIVES:
            continue
        for v in eqn.invars:
            dt = getattr(v.aval, "dtype", None)
            if dt is None:
                continue
            if jnp.issubdtype(dt, jnp.complexfloating) or jnp.issubdtype(
                dt, jnp.floating
            ):
                bits = jnp.finfo(dt).bits  # per-component for complex too
            else:
                continue
            if bits > in_bits and (eqn.primitive.name, str(dt)) not in seen:
                seen.add((eqn.primitive.name, str(dt)))
                out.append(
                    Finding(
                        "comm-upcast",
                        where,
                        f"{eqn.primitive.name} payload is {dt} but the widest "
                        f"driver input float is {in_bits}-bit — payload "
                        "silently upcast",
                    )
                )
    return out


def _count_loop_collectives(jaxpr: jax_core.Jaxpr, in_loop: bool) -> int:
    """Data-collective eqns that EXECUTE per loop trip.  A cond/switch
    runs exactly one of its branches per trip — the broadcast engine's
    rooted ring/doubling schedules dispatch over the static owner roots
    this way — so branches contribute the max over branches, not the sum
    (the audit records one hop set per broadcast, not one per branch)."""
    n = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if in_loop and name in DATA_COLLECTIVES:
            n += 1
        if name == "cond":
            n += max(
                (_count_loop_collectives(sub, in_loop) for sub in _sub_jaxprs(eqn)),
                default=0,
            )
        else:
            inner = in_loop or name in LOOP_PRIMS
            for sub in _sub_jaxprs(eqn):
                n += _count_loop_collectives(sub, inner)
    return n


def count_loop_collectives(closed: jax_core.ClosedJaxpr) -> int:
    """Data collectives living inside while/scan bodies (cond branches
    counted as max-over-branches: one executes per trip)."""
    return _count_loop_collectives(closed.jaxpr, False)


def check_loop_audit(
    closed: jax_core.ClosedJaxpr,
    audit_records,
    where: str,
) -> List[Finding]:
    """Invariant 1b: collectives inside loop bodies are covered by an
    ``audit_scope`` multiplicity.

    The registry traces each driver under ``comm_audit()``; a kernel whose
    loop collectives went through the audited wrappers inside an
    ``audit_scope(trip_count)`` leaves records with multiplicity > 1
    (registry problem sizes keep every trip count > 1).  Loop collectives
    with no scoped record mean the comm-volume audit under-counts that
    driver.  One scoped loop must not mask another unscoped one, so the
    count of scoped records must cover the count of loop-body collective
    eqns — an unscoped loop's records carry multiplicity 1 and leave the
    scoped count short."""
    n_loop = count_loop_collectives(closed)
    if n_loop == 0:
        return []
    n_scoped = sum(1 for r in audit_records if r[2] > 1)
    if n_scoped >= n_loop:
        return []
    return [
        Finding(
            "loop-audit",
            where,
            f"{n_loop} collective(s) inside fori_loop/scan bodies but only "
            f"{n_scoped} audit record(s) carry an audit_scope multiplicity "
            "— comm_audit() would under-count this driver",
        )
    ]


def check_donation(
    fn, args, donate_argnums: Sequence[int], where: str, static_argnums=()
) -> List[Finding]:
    """Invariant 3: every donated argument must be aliasable — there must
    be a distinct output with identical shape+dtype for each donated
    input, else XLA keeps the buffer and emits the runtime
    'donated buffers were not usable' warning this check promotes to a
    failure."""
    import numpy as np

    flat_out = jax.eval_shape(fn, *args)
    out_avals = [
        (tuple(a.shape), np.dtype(a.dtype))
        for a in jax.tree_util.tree_leaves(flat_out)
    ]
    findings = []
    # ONE shared pool across all donated args: each output buffer can alias
    # at most one donation, so two same-aval donations need two outputs
    pool = list(out_avals)
    for i in donate_argnums:
        donated = [
            (tuple(a.shape), np.dtype(a.dtype))
            for a in jax.tree_util.tree_leaves(
                jax.eval_shape(lambda x: x, args[i])
            )
        ]
        for d in donated:
            if d in pool:
                pool.remove(d)
            else:
                findings.append(
                    Finding(
                        "donation",
                        where,
                        f"donated arg {i} aval {d[1]}{list(d[0])} has no "
                        "matching output to alias — XLA cannot use the "
                        "donation",
                    )
                )
    return findings
