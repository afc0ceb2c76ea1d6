"""Request-level serving traces: the end-to-end lifecycle record of one
request through the Router (ISSUE 14).

The obs stack already answers "is the schedule right" (``sched.*``),
"does it fit" (``mem.*``) and "is the answer right" (``num.*``); this
module answers "what happened to request 4711" — the Dapper-style span
record of one request's whole path: queue/admission → condest
classification → executable-cache lookup (hit/miss) → factor →
solve/refine → the PR 12/13 degradation ladder (FtError retry,
Preempted resume, GrowthAbort pivoted retry, structured reject).

Contracts:

- **Exactly one terminal outcome per request.**  ``finish`` is a
  single-shot: a second terminal is a programming error and raises.
  The outcome set (``TERMINALS``) attributes every exit to one
  cause — a request that retried AND resumed terminates under the LAST
  degradation that carried it home.
- **Disabled mode stays honest.**  ``new_trace`` returns ``None`` while
  the obs layer is off: ZERO trace allocations, and every Router call
  site guards with ``if tr is not None`` (the module-level ``phase`` /
  ``note`` / ``finish`` helpers do it once), so the dispatch path is
  byte-identical to the untraced router (asserted in tests/test_serve.py).
- **The metric surface is the shared registry.**  ``finish`` observes
  the request latency into the ``serve.latency_s`` histogram tagged by
  (op, request class, outcome) — obs/metrics.py histograms now carry
  first-class reservoir quantiles — and ``sla_values()`` reduces the
  live registry to the flat ``latency_{p50,p95,p99}_*`` +
  outcome-count/rate keys that land in the RunReport ``serve`` section
  (serve/metrics.py merges them), gated by ``obs.report --check`` with
  the wall-clock ``*latency*_s`` keys ``--ignore``d.

Export surfaces: ``obs.perfetto.request_trace_events`` renders finished
traces as one Perfetto track per accuracy class with flow arrows
retry→resume→final; ``python -m slate_tpu.serve.stats`` emits a
Prometheus-style text + JSON snapshot of the live registry.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..obs import REGISTRY, enabled
from ..obs import context as _obs_context

# terminal outcomes: every request ends in EXACTLY one of these
TERMINALS = (
    "served",                # clean dispatch, no degradation consumed
    "served_retry",          # transient FtError -> one Recompute retry
    "served_resume",         # Preempted -> resumed from its checkpoint
    "served_growth_retry",   # GrowthAbort -> one pivoted (pp) retry
    "reject_admission",      # over the HBM/bin admission bound
    "reject_budget",         # over the submitting TENANT's HBM budget
    #   (the batch-window queue's fair-share ledger, ISSUE 19 — the
    #   global admission bound above is the whole-device twin)
    "reject_unresumable",    # preempted with no (or a re-killed) snapshot
    "reject_residual",       # resilient-path residual gate refused it
    "reject_batch_abort",    # a sibling/other-group failure aborted the
    #   batch before this request's own dispatch concluded (the Router
    #   raises for the whole solve_batch call; the cause lives on the
    #   request that actually failed)
    "failed_info",           # factorization reported nonzero info
    "failed_error",          # the request's OWN dispatch raised past the
    #   degradation ladder (persistent SDC after the one retry, an abort
    #   inside a retry, an unexpected error)
)

# degradation notes -> the served-terminal they map to (the LAST note
# names the cause that actually carried the request home)
_NOTE_TERMINAL = {
    "ft_retry": "served_retry",
    "resume": "served_resume",
    "growth_retry": "served_growth_retry",
}

_IDS = itertools.count(1)
_lock = threading.Lock()
_FINISHED: List["RequestTrace"] = []
_FINISHED_CAP = 4096
# (op, klass, outcome) -> count; the exact outcome-attribution totals
# (histogram reservoirs estimate quantiles; these counts are exact)
_OUTCOME_COUNTS: Dict[Tuple[str, str, str], float] = {}


class RequestTrace:
    """One request's lifecycle: identity (rid/op/n/nb/dtype), the
    condest-keyed accuracy class, nesting phase spans, degradation
    notes, and the single terminal outcome."""

    __slots__ = ("rid", "op", "n", "nb", "dtype", "klass", "bin", "batch",
                 "t0", "t1", "phases", "notes", "outcome", "_stack",
                 "trace_id", "tenant")

    def __init__(self, op: str, n: int, nb: int, dtype: str,
                 tenant: Optional[str] = None) -> None:
        self.rid = next(_IDS)
        # the correlation id every surface below joins on (ISSUE 17):
        # assigned ONCE here, so degradation-ladder retries/resumes —
        # which re-dispatch under this same trace — keep one trace_id
        # across dispatches, while a batch-abort bystander (its own
        # RequestTrace) gets its own
        self.trace_id = _obs_context.new_trace_id()
        self.tenant = tenant
        self.op = op
        self.n = int(n)
        self.nb = int(nb)
        self.dtype = dtype
        self.klass: Optional[str] = None
        self.bin: Optional[int] = None
        self.batch: int = 1
        self.t0 = time.perf_counter()
        self.t1 = 0.0
        self.phases: List[dict] = []   # {name, t0, t1, depth, parent, meta}
        self.notes: List[str] = []     # degradation events, in order
        self.outcome: Optional[str] = None
        self._stack: List[str] = []

    @contextlib.contextmanager
    def phase(self, name: str, **meta):
        """Open one nesting phase span (records on exit, so children
        append before their parents — containment is by interval +
        ``parent`` name)."""
        rec = {"name": name, "t0": time.perf_counter(), "t1": 0.0,
               "depth": len(self._stack),
               "parent": self._stack[-1] if self._stack else None,
               "meta": dict(meta)}
        self._stack.append(name)
        # every surface beneath this phase (driver spans, flight
        # StepEvents, mem samples, num gauges) reads the ambient
        # TraceContext at its own record points — this phase boundary is
        # the ONE propagation choke point (ISSUE 17)
        ctx = _obs_context.TraceContext(
            self.trace_id, tenant=self.tenant, klass=self.klass,
            rid=self.rid, op=self.op)
        try:
            with _obs_context.use_context(ctx):
                yield rec
        finally:
            self._stack.pop()
            rec["t1"] = time.perf_counter()
            self.phases.append(rec)
            # unconditional: a trace only exists because obs was on at
            # admission, and flipping obs off mid-request must not
            # desynchronize the phase/latency surfaces from the exact
            # outcome counts.  The tenant tag joins only when a tenant
            # was declared, so tenant-less request streams keep their
            # exact historical tag sets (and the committed SLA artifact
            # its exact series).
            tt = {"tenant": self.tenant} if self.tenant else {}
            REGISTRY.observe("serve.phase_s", rec["t1"] - rec["t0"],
                             op=self.op, phase=name, **tt)

    def note(self, kind: str) -> None:
        """Record one degradation event (ft_retry / resume /
        growth_retry) — ``terminal()`` attributes the served outcome to
        the last one."""
        if kind not in _NOTE_TERMINAL:
            raise ValueError(f"unknown degradation note {kind!r}")
        self.notes.append(kind)

    def terminal(self) -> str:
        """The served-terminal this request's notes attribute it to."""
        return _NOTE_TERMINAL[self.notes[-1]] if self.notes else "served"

    def finish(self, outcome: str) -> None:
        """Set THE terminal outcome (single-shot), observe the request
        latency tagged (op, class, outcome), and retire the trace to the
        finished stream."""
        if self.outcome is not None:
            raise RuntimeError(
                f"request {self.rid} ({self.op}) already terminal "
                f"({self.outcome!r}); a second outcome {outcome!r} would "
                "double-attribute it")
        if outcome not in TERMINALS:
            raise ValueError(f"unknown terminal outcome {outcome!r}; "
                             f"expected one of {TERMINALS}")
        self.outcome = outcome
        self.t1 = time.perf_counter()
        klass = self.klass or "friendly"
        with _lock:
            key = (self.op, klass, outcome)
            _OUTCOME_COUNTS[key] = _OUTCOME_COUNTS.get(key, 0.0) + 1.0
            _FINISHED.append(self)
            if len(_FINISHED) > _FINISHED_CAP:
                del _FINISHED[0]
        # unconditional (not re-gated on enabled()): the trace exists
        # because obs was on when the request entered, and the latency
        # histogram MUST stay in lockstep with the exact outcome counts
        # above — an obs.disable() racing a request in flight must not
        # leave outcome totals exceeding latency counts
        tt = {"tenant": self.tenant} if self.tenant else {}
        REGISTRY.observe("serve.latency_s", self.t1 - self.t0,
                         op=self.op, klass=klass, outcome=outcome, **tt)
        REGISTRY.counter_add("serve.outcomes", 1.0, op=self.op,
                             klass=klass, outcome=outcome, **tt)
        # live telemetry bus (ISSUE 17): publish the terminated request
        # when the bus module is loaded (sys.modules probe — zero cost
        # for runs that never imported obs.live)
        import sys as _sys

        _live = _sys.modules.get(
            __package__.rsplit(".", 1)[0] + ".obs.live")
        if _live is not None:
            _live.publish("request", {
                "rid": self.rid, "trace_id": self.trace_id,
                "tenant": self.tenant, "op": self.op, "n": self.n,
                "klass": klass, "outcome": outcome,
                "latency_s": self.t1 - self.t0,
                "notes": list(self.notes),
            })


# ---------------------------------------------------------------------------
# None-safe call-site helpers: the Router threads Optional[RequestTrace]
# and these keep the disabled path one `is None` test per site
# ---------------------------------------------------------------------------


def new_trace(op: str, n: int, nb: int, dtype: str,
              tenant: Optional[str] = None) -> Optional[RequestTrace]:
    """A live trace while the obs layer is enabled, else None — the
    zero-allocation disabled contract (which also means NO TraceContext
    is ever entered with obs off: the context spine is invisible to the
    disabled dispatch path)."""
    if not enabled():
        return None
    return RequestTrace(op, n, nb, dtype, tenant=tenant)


def phase(tr: Optional[RequestTrace], name: str, **meta):
    return tr.phase(name, **meta) if tr is not None \
        else contextlib.nullcontext()


@contextlib.contextmanager
def phase_all(trs, name: str, **meta):
    """One phase span opened on every live trace of a stacked group (the
    group shares the compiled dispatch, so it shares the span times)."""
    with contextlib.ExitStack() as stack:
        for tr in trs:
            if tr is not None:
                stack.enter_context(tr.phase(name, **meta))
        yield


def note(tr: Optional[RequestTrace], kind: str) -> None:
    if tr is not None:
        tr.note(kind)


def finish(tr: Optional[RequestTrace], outcome: Optional[str] = None) -> None:
    """Terminate ``tr`` with ``outcome`` (default: the note-attributed
    served terminal)."""
    if tr is not None:
        tr.finish(outcome if outcome is not None else tr.terminal())


def finished_traces() -> List[RequestTrace]:
    with _lock:
        return list(_FINISHED)


def reset() -> None:
    with _lock:
        _FINISHED.clear()
        _OUTCOME_COUNTS.clear()


# ---------------------------------------------------------------------------
# SLA reduction: live registry -> flat RunReport serve-section keys
# ---------------------------------------------------------------------------


def sla_values() -> Dict[str, float]:
    """Reduce the request-latency histograms + exact outcome counts to
    the flat SLA surface of the RunReport ``serve`` section:

    - ``latency_{p50,p95,p99}_{op}_{klass}_s``: reservoir quantiles
      pooled over every outcome of one (op, accuracy class) — wall-clock
      keys, ``--ignore``d by the CI gate (``*latency*_s``);
    - ``latency_count_{op}_{klass}``: observation counts — machine-
      independent under a fixed request stream, gate tight;
    - ``outcome_{outcome}`` / ``outcome_rate_{outcome}``: exact
      attribution totals and their share of all terminated requests —
      the shape/rate keys the gate holds tight.

    Empty (no request terminated) -> {} so an idle run's serve section
    stays exactly the counter zeros."""
    from .metrics import _sanitize_key as _san

    with _lock:
        counts = dict(_OUTCOME_COUNTS)
    vals: Dict[str, float] = {}
    # exact outcome attribution totals + rates
    by_outcome: Dict[str, float] = {}
    for (_op, _kl, outc), c in counts.items():
        by_outcome[outc] = by_outcome.get(outc, 0.0) + c
    total = sum(by_outcome.values())
    for outc, c in sorted(by_outcome.items()):
        vals[f"outcome_{outc}"] = c
        vals[f"outcome_rate_{outc}"] = c / total
    # pooled per-(op, klass) latency quantiles over all outcomes
    from ..obs.metrics import quantile_of

    pools: Dict[Tuple[str, str], dict] = {}
    for series in REGISTRY.histogram_series("serve.latency_s"):
        tags = series["tags"]
        key = (tags.get("op", "?"), tags.get("klass", "?"))
        pool = pools.setdefault(
            key, {"count": 0, "samples": [],
                  "min": float("inf"), "max": float("-inf")})
        pool["count"] += series["count"]
        pool["samples"].extend(series["samples"])
        pool["min"] = min(pool["min"], series["min"])
        pool["max"] = max(pool["max"], series["max"])
    for (op, klass), pool in sorted(pools.items()):
        stem = _san(f"{op}_{klass}")
        vals[f"latency_count_{stem}"] = float(pool["count"])
        for label, q in (("p50", 0.5), ("p95", 0.95), ("p99", 0.99)):
            qv = quantile_of(pool["samples"], q, pool["min"], pool["max"])
            if qv is not None:
                vals[f"latency_{label}_{stem}_s"] = qv
    return vals
