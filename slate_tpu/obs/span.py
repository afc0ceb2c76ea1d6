"""Driver spans: the nesting instrumentation context every distributed
driver flows through.

``driver_span(name, **tags)`` is the TPU-native fusion of the reference's
``trace::Block`` RAII regions with xprof-style annotation: it times the
region, nests (thread-local stack), bridges the name into real TPU
profiles via ``jax.profiler.TraceAnnotation``, and absorbs the comm-byte
audit (parallel/comm.py) so every collective traced inside the span lands
in the metrics registry tagged with the span's name.

The profiler annotation is always on: with observability off a span (and
an ``instrument``-ed driver) opens only the ``TraceAnnotation`` named
``PROFILER_PREFIX + name`` and yields a shared null object — no registry,
no comm audit, no memory sample, no record.  An annotation costs well
under 2 us with no profiler running (0.49 us bare, 1.9 us through this
generator context manager, measured on a v5e host), so every driver
keeps it; a ``jax.profiler`` trace then holds the program's spans on the
device ops' clock without ``enable()``.  Everything else is gated on
``enable()`` / the ``SLATE_TPU_OBS`` env var.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

from .metrics import REGISTRY

# profiler-side span names carry this prefix, so a trace reader can tell
# the program's spans from JAX's own host events; registry tags keep the
# bare name
PROFILER_PREFIX = "slate_tpu/"

# finished-span records for the Perfetto exporter; bounded so a long
# sweep cannot grow without limit
_EVENT_CAP = 100_000

_enabled = os.environ.get("SLATE_TPU_OBS", "") not in ("", "0")
_tls = threading.local()

# finished spans as plain dicts (name, tags, t0, t1, depth, parent, metrics)
FINISHED: List[dict] = []
_finished_lock = threading.Lock()


def enable() -> None:
    """Light up the whole stack: every instrumented driver starts
    recording spans + metrics (the ``SLATE_TPU_OBS=1`` switch)."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


@contextlib.contextmanager
def force_enabled(value: bool = True):
    """Temporarily flip observability (tests, lint's obs-instrumented
    registry entries)."""
    global _enabled
    old, _enabled = _enabled, value
    try:
        yield
    finally:
        _enabled = old


def reset() -> None:
    """Drop finished spans + metrics + memory samples + numerics gauges
    (fresh run boundary)."""
    with _finished_lock:
        FINISHED.clear()
    REGISTRY.reset()
    import sys as _sys

    mem = _sys.modules.get(__package__ + ".memory")
    if mem is not None:  # only if the memory layer was ever consulted
        mem.reset()
    num = _sys.modules.get(__package__ + ".numerics")
    if num is not None:  # only if the numerics layer was ever consulted
        num.reset()
    srv = _sys.modules.get(
        __package__.rsplit(".", 1)[0] + ".serve.metrics")
    if srv is not None:  # only if the serving layer was ever consulted
        srv.reset()


def _stack() -> List["Span"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def current_span() -> Optional["Span"]:
    st = _stack()
    return st[-1] if st else None


class Span:
    """One timed region.  ``set()`` attaches scalar metrics to the span
    (they also land in the registry as gauges tagged span=name);
    ``phase()`` opens a nested child span and copies its duration up as
    ``<phase>_seconds``."""

    __slots__ = ("name", "tags", "t0", "t1", "depth", "parent", "metrics")

    def __init__(self, name: str, tags: Dict[str, Any], depth: int,
                 parent: Optional[str]):
        self.name = name
        self.tags = tags
        self.depth = depth
        self.parent = parent
        self.t0 = 0.0
        self.t1 = 0.0
        self.metrics: Dict[str, float] = {}

    def set(self, key: str, value: float) -> None:
        self.metrics[key] = float(value)
        REGISTRY.gauge_set(key, float(value), span=self.name)

    @contextlib.contextmanager
    def phase(self, pname: str):
        with driver_span(f"{self.name}:{pname}", phase=pname) as sp:
            yield sp
        if sp is not _NULL:
            self.metrics[f"{pname}_seconds"] = sp.t1 - sp.t0


class _NullSpan:
    """Shared no-op span handed out while observability is off."""

    __slots__ = ()
    name = ""
    tags: Dict[str, Any] = {}
    metrics: Dict[str, float] = {}
    t0 = t1 = 0.0

    def set(self, key: str, value: float) -> None:
        pass

    @contextlib.contextmanager
    def phase(self, pname: str):
        yield self


_NULL = _NullSpan()


def _comm_bytes(records) -> Dict[str, float]:
    """(op, payload_bytes, mult) records -> {op_base: total_bytes}."""
    by_op: Dict[str, float] = {}
    for op, nbytes, mult in records:
        base = op.split("[")[0]
        by_op[base] = by_op.get(base, 0.0) + float(nbytes) * mult
    return by_op


@contextlib.contextmanager
def driver_span(name: str, **tags):
    """Open an observability span.  Nests; absorbs comm-audit bytes; maps
    the name into xprof via jax.profiler.TraceAnnotation.  Yields the
    Span (or a shared null object when observability is off).

    Concurrency contract: the span STACK is thread-local, but the
    comm-byte audit it absorbs rides the pre-existing process-global
    ``parallel.comm._AUDIT`` — per-span comm_bytes are only attributed
    correctly when jit tracing happens on one thread at a time (true for
    every driver in this repo; lint and the audit tools are
    single-threaded by construction)."""
    if not _enabled:
        with TraceAnnotation(PROFILER_PREFIX + name):
            yield _NULL
        return

    from ..parallel import comm  # lazy: obs must not import parallel at module load
    from . import context as _context

    st = _stack()
    parent = st[-1] if st else None
    # request/tenant attribution (ISSUE 17): a span opened while a
    # TraceContext is ambient carries the request's trace_id (and tenant)
    # in its tags — the join key the unified Perfetto export correlates
    # tracks by.  setdefault: an explicit caller-provided id wins.
    ctx = _context.current()
    if ctx is not None:
        tags.setdefault("trace_id", ctx.trace_id)
        if ctx.tenant:
            tags.setdefault("tenant", ctx.tenant)
    span = Span(name, tags, len(st), parent.name if parent else None)
    st.append(span)

    ann = TraceAnnotation(PROFILER_PREFIX + name)
    ann.__enter__()

    # capture audited collectives traced inside this span; propagate=True
    # re-appends the records outward on exit so enclosing audits
    # (slate_lint's, the comm-volume tool's, an outer span's) still see
    # every byte.  The schedule channel rides along for the per-hop
    # ppermute LINK records (src→dst pairs) the Perfetto exporter turns
    # into hop events instead of dropping.
    audit_cm = comm.comm_audit(propagate=True)
    records = audit_cm.__enter__()
    sched_cm = comm.sched_audit(propagate=True)
    sched_records = sched_cm.__enter__()

    span.t0 = time.perf_counter()
    try:
        yield span
    finally:
        span.t1 = time.perf_counter()
        sched_cm.__exit__(None, None, None)
        audit_cm.__exit__(None, None, None)
        ann.__exit__(None, None, None)
        st.pop()

        dur = span.t1 - span.t0
        span.metrics.setdefault("wall_seconds", dur)
        # tenant tag dimension (ISSUE 17): per-tenant span/comm series
        # when a tenant-carrying context is ambient.  Tenant-less runs
        # (bench, lint, the whole pre-serving surface) keep their exact
        # historical tag sets.
        tt = {"tenant": ctx.tenant} if ctx is not None and ctx.tenant else {}
        REGISTRY.counter_add("span_count", 1, span=name, **tt)
        REGISTRY.observe("span_seconds", dur, span=name, **tt)
        total_comm = 0.0
        for op, nbytes in _comm_bytes(records).items():
            REGISTRY.counter_add("comm_bytes", nbytes, span=name, op=op,
                                 **tt)
            total_comm += nbytes
        span.metrics["comm_bytes"] = total_comm
        # live schedule surface (ISSUE 17): the absorbed schedule-audit
        # records also land as sched.* counter series — per-hop ppermute
        # LINK bytes where the impl has hop pairs (ring/binomial),
        # collective payload bytes otherwise (psum) — so a scrape of the
        # LIVE registry carries the schedule family under either
        # lowering (the offline twin is the FlightReport's flat sched.*
        # values)
        for rec_op, rec_bytes, rec_mult, _ph, _st2, rec_pairs in sched_records:
            REGISTRY.counter_add(
                "sched.link_bytes" if rec_pairs else "sched.coll_bytes",
                float(rec_bytes) * rec_mult,
                span=name, op=rec_op.split("[")[0], **tt)
        # per-hop LINK records (ppermute pairs) for the Perfetto
        # exporter's hop events; bounded per span
        # step None marks an in-loop broadcast whose owner was a tracer:
        # its pairs are the root-0 hop schedule, not owner-resolved
        # devices (concrete prologue steps carry the true rotated pairs)
        hops = [
            {"op": op, "bytes": float(nbytes), "mult": mult, "step": st,
             "pairs": pairs}
            for op, nbytes, mult, _ph, st, pairs in sched_records
            if pairs
        ][:64]
        # memory sampling at driver_span boundaries: top-level
        # spans and the outermost span of each request, and only while
        # obs is on — the disabled path above never reaches here, so
        # disabled mode makes zero live_arrays calls (asserted by
        # tests/test_mem.py)
        rid = tags.get("trace_id")
        outermost = parent is None or (
            rid is not None and parent.tags.get("trace_id") != rid)
        try:
            from . import memory as _memory

            _memory.sample_span(span, outermost)
        except Exception:
            pass
        record = {
            "name": name,
            "tags": {k: str(v) for k, v in tags.items()},
            "t0": span.t0,
            "t1": span.t1,
            "depth": span.depth,
            "parent": span.parent,
            "metrics": dict(span.metrics),
            "hops": hops,
        }
        with _finished_lock:
            if len(FINISHED) < _EVENT_CAP:
                FINISHED.append(record)
        # live telemetry bus (ISSUE 17): only when obs.live was imported
        # by someone (an endpoint, a test) — a sys.modules probe keeps
        # the bus entirely out of runs that never asked for it
        import sys as _sys

        _live = _sys.modules.get(__package__ + ".live")
        if _live is not None:
            _live.publish("span", record)


def _default_tags(args) -> Dict[str, Any]:
    """Shape-ish tags from the first operand, without touching device data."""
    if not args:
        return {}
    a = args[0]
    if hasattr(a, "m") and hasattr(a, "n") and hasattr(a, "nb"):
        return {"m": a.m, "n": a.n, "nb": a.nb}
    shape = getattr(a, "shape", None)
    if shape is not None:
        return {"shape": "x".join(str(s) for s in shape)}
    return {}


def _oom_note(name: str, exc: BaseException) -> None:
    """OOM forensics at the drivers' dispatch layer (ISSUE 9): on a
    RESOURCE_EXHAUSTED class failure, emit the live-tensor / model-peak
    report before the exception propagates.  Only runs on the exception
    path (rare), so the lazy import + marker match live in one place —
    memory.is_oom is the single source of the marker list — and the
    whole hook is wrapped so forensics can never mask the original
    failure."""
    try:
        from . import memory as _memory

        _memory.handle_driver_exception(name, exc)
    except Exception:
        pass


def instrument(name: Optional[str] = None, **static_tags) -> Callable:
    """Decorator wiring a driver into the observability layer.  With
    observability disabled the call runs inside the span's profiler
    annotation only (plus an exception-path OOM forensics hook — no jaxpr
    change); enabled, it runs inside ``driver_span(name, **shape_tags)``."""

    def deco(fn: Callable) -> Callable:
        span_name = name or fn.__name__
        profiler_name = PROFILER_PREFIX + span_name

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                try:
                    with TraceAnnotation(profiler_name):
                        return fn(*args, **kwargs)
                except Exception as e:
                    _oom_note(span_name, e)
                    raise
            tags = dict(static_tags)
            tags.update(_default_tags(args))
            try:
                with driver_span(span_name, **tags):
                    return fn(*args, **kwargs)
            except Exception as e:
                _oom_note(span_name, e)
                raise

        wrapper.__wrapped__ = fn
        return wrapper

    return deco


# ---------------------------------------------------------------------------
# jit-aware measurement: wall/compile/execute phases + XLA cost estimates
# ---------------------------------------------------------------------------


def _cost_from_compiled(compiled) -> Dict[str, float]:
    """Normalize ``compiled.cost_analysis()`` (a per-device LIST of dicts
    on JAX 0.4.x, a bare dict on newer) into flop/byte estimates."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out: Dict[str, float] = {}
    for src, dst in (
        ("flops", "flops"),
        ("bytes accessed", "bytes_accessed"),
        ("transcendentals", "transcendentals"),
    ):
        v = ca.get(src)
        if v is not None:
            out[dst] = float(v)
    return out


def cost_analysis_of(fn: Callable, *args, **kwargs) -> Dict[str, float]:
    """flop + byte estimates from ``jitted.lower(...).compile()``'s
    cost_analysis (XLA's own model).  ``fn`` may already be jitted;
    anything without ``.lower`` is wrapped in jax.jit first.  Returns {}
    when the backend offers no analysis."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
    except Exception:
        return {}
    return _cost_from_compiled(compiled)


def _block(x) -> None:
    import jax

    jax.block_until_ready(x)


def measure(name: str, fn: Callable, *args, tags: Optional[Dict[str, Any]] = None,
            with_cost: bool = True):
    """Run ``fn(*args)`` instrumented: one AOT lower+compile, timed as the
    compile phase (tracing fires the comm-byte audit; the compiled object
    also yields XLA's flop/byte cost estimates with no second compile),
    then a timed execution.  Falls back to a cold-call + warm-call pair
    (compile time by difference) when ``fn`` cannot be AOT-lowered.

    Returns (result, span_metrics_dict).  Works with or without
    observability enabled (it force-enables for its own scope)."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    with force_enabled():
        with driver_span(name, **(tags or {})) as sp:
            compiled = None
            try:
                with sp.phase("compile"):
                    compiled = jitted.lower(*args).compile()
            except Exception:
                with sp.phase("cold"):
                    out = jitted(*args)
                    _block(out)
            with sp.phase("execute"):
                out = (compiled if compiled is not None else jitted)(*args)
                _block(out)
            execute = sp.metrics.get("execute_seconds", 0.0)
            if compiled is None:
                cold = sp.metrics.get("cold_seconds", 0.0)
                sp.set("compile_seconds", max(0.0, cold - execute))
            else:
                sp.set("compile_seconds", sp.metrics["compile_seconds"])
            sp.set("execute_seconds", execute)
            # comm bytes need no explicit copy: the compile/cold phase
            # audits with propagate=True, so driver_span's own exit sums
            # the same records into this span's comm_bytes
            if with_cost:
                cost = (_cost_from_compiled(compiled) if compiled is not None
                        else cost_analysis_of(jitted, *args))
                for k, v in cost.items():
                    sp.set(k, v)
        # wall_seconds is the span's true duration (compile + execute),
        # set by driver_span on exit — the phases carry the split
        metrics = dict(sp.metrics)
    return out, metrics
