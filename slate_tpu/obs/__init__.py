"""Unified runtime observability: metrics + span tracing + Perfetto export
+ machine-readable RunReports.

The TPU-native analogue of the reference's trace subsystem
(include/slate/internal/Trace.hh RAII blocks + the ``slate::timers`` phase
map) fused with xprof-style annotation:

- ``enable()`` / ``SLATE_TPU_OBS=1`` lights up the whole stack: every
  instrumented driver (parallel/ kernels, linalg facades, mesh drivers)
  records nested spans, wall/compile/execute phases, comm bytes (absorbed
  from the parallel.comm trace-time audit) and XLA flop/byte estimates.
- ``driver_span(name, **tags)`` is the instrumentation context; the
  ``instrument`` decorator wires a driver in permanently.  Both always
  open a ``jax.profiler.TraceAnnotation`` named ``PROFILER_PREFIX +
  name`` (a microsecond with no profiler running), so a ``jax.profiler``
  trace holds the program's spans with or without ``enable()``.
- ``perfetto.write_chrome_trace(path)`` exports the recorded spans as a
  Chrome trace-event JSON that loads in ui.perfetto.dev.
- ``report`` holds the versioned RunReport schema every perf artifact
  (bench.py, tester.py, tools/northstar_sweep.py, CI smoke) emits
  through, plus the ``python -m slate_tpu.obs.report`` CLI with
  ``--check`` regression gating against prior reports / BENCH_*.json.
- ``python -m slate_tpu.obs.smoke`` is the CI acceptance run.
- ``memory`` / ``memmodel`` / ``memwatch`` are the HBM observability
  layer (ISSUE 9): AOT compile-time memory analysis + donation-alias
  verification + live sampling at span boundaries + OOM forensics on
  the measured side, a closed-form per-device peak model
  (``MemoryModel``, ``predict_max_n``) on the analytic side, and
  ``python -m slate_tpu.obs.memwatch`` emitting the committed ``mem.*``
  regression artifacts.
- ``numerics`` / ``numwatch`` are the accuracy sibling (ISSUE 10):
  ``Option.NumMonitor`` in-carry element-growth / Schur-margin /
  IR-trajectory gauges in the mesh k-loops (off = jaxpr-identical, on =
  zero extra audited bytes), distributed Hager-Higham condition
  estimation over factored tiles, health-aware mixed-ladder routing,
  and ``python -m slate_tpu.obs.numwatch`` emitting the committed
  ``num.*`` regression artifacts.
"""

# NOTE: perfetto/report are deliberately NOT imported here so that
# ``python -m slate_tpu.obs.report`` runs without runpy's found-in-
# sys.modules warning; import them as submodules
# (``from slate_tpu.obs import perfetto, report``).
from .context import (  # noqa: F401
    TraceContext,
    current as current_context,
    new_trace_id,
    use_context,
)
from .metrics import REGISTRY, MetricsRegistry, flatten_snapshot  # noqa: F401
from .span import (  # noqa: F401
    FINISHED,
    PROFILER_PREFIX,
    Span,
    cost_analysis_of,
    current_span,
    disable,
    driver_span,
    enable,
    enabled,
    force_enabled,
    instrument,
    measure,
    reset,
)

__all__ = [
    "TraceContext",
    "current_context",
    "new_trace_id",
    "use_context",
    "REGISTRY",
    "MetricsRegistry",
    "flatten_snapshot",
    "FINISHED",
    "PROFILER_PREFIX",
    "Span",
    "cost_analysis_of",
    "current_span",
    "disable",
    "driver_span",
    "enable",
    "enabled",
    "force_enabled",
    "instrument",
    "measure",
    "reset",
    # lazily forwarded from obs.flight (see __getattr__)
    "flight_scope",
    "no_flight",
    "step_dispatch_active",
    "FlightRecorder",
]

_FLIGHT_NAMES = frozenset(
    {"flight_scope", "no_flight", "step_dispatch_active", "FlightRecorder"}
)


def __getattr__(name):
    # obs.flight_scope() et al. without an eager submodule import, so
    # ``python -m slate_tpu.obs.flight`` still runs without runpy's
    # found-in-sys.modules warning (same reason report/perfetto are not
    # imported here)
    if name in _FLIGHT_NAMES:
        from . import flight

        return getattr(flight, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
