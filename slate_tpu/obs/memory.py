"""Measured HBM memory observability: AOT compile-time memory analysis,
live-buffer / device-stats sampling at span boundaries, donation-alias
verification, and OOM forensics.

The measured sibling of ``obs.memmodel`` (ISSUE 9 tentpole).  Four
surfaces:

- **AOT analysis** — ``aot_memory_analysis(fn, *args)`` lowers+compiles
  and returns XLA's own per-device buffer-assignment numbers
  (argument / output / temp / alias bytes).  Machine-independent at a
  fixed shape, which makes it a *perfect* regression gate for the
  lost-donation / extra-copy bug class this repo has hit twice (PR 1's
  unusable-donation fix, PR 3's staged-potrf OOM) — the ``mem.*`` keys
  the memwatch CLI commits and CI gates.
- **Donation verification** — ``donation_alias_bytes`` asserts a donated
  operand actually ALIASES in the compiled executable
  (``alias_size_in_bytes``), not merely that it is aliasable (the static
  lint check): a silently-lost donation shows up as alias bytes
  collapsing to zero.
- **Live sampling** — when observability is on (``SLATE_TPU_OBS=1``),
  every top-level ``driver_span`` exit records ``jax.live_arrays()``
  totals and ``device.memory_stats()`` bytes_in_use / peak_bytes_in_use
  into the metrics registry and a bounded sample list the Perfetto
  exporter renders as per-device counter tracks.  With observability off
  this module is never consulted: zero ``live_arrays`` calls, asserted
  by tests/test_mem.py.
- **OOM forensics** — ``handle_driver_exception`` (wired into
  ``obs.instrument``, i.e. every driver's dispatch layer) recognizes
  RESOURCE_EXHAUSTED, and emits a report to stderr naming the largest
  live tensors, the device stats, the MemoryModel's predicted peaks for
  the op, and the escape routes (staged potrf, lookahead 0, smaller nb)
  before re-raising.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from .metrics import REGISTRY

# bounded sample stream for the Perfetto memory counter tracks
SAMPLES: List[dict] = []
_SAMPLE_CAP = 4096
_lock = threading.Lock()

# test hook: number of jax.live_arrays() walks this module performed
LIVE_CALLS = 0

# mem.* outcome totals for the RunReport "mem" section (ft/ir pattern)
_STATE = {
    "oom_events": 0.0,
    "samples": 0.0,
    "live_bytes_max": 0.0,
    "bytes_in_use_max": 0.0,
    "peak_bytes_in_use_max": 0.0,
}

SAMPLE_ENV = "SLATE_TPU_OBS_MEM_SAMPLE"
_FORCE: List[bool] = []


def reset() -> None:
    with _lock:
        SAMPLES.clear()
        for k in _STATE:
            _STATE[k] = 0.0


def mem_counter_values() -> Dict[str, float]:
    """mem.* outcome totals for the RunReport ``mem`` section.  All-zero
    (no sampling, no OOM this run) stays out of the report comparison
    surface, exactly like the ft/ir sections."""
    with _lock:
        return dict(_STATE)


def sampling_active() -> bool:
    """Live sampling runs when observability is enabled and the env has
    not opted out (SLATE_TPU_OBS_MEM_SAMPLE=0), or when a test/smoke has
    forced it on."""
    if _FORCE:
        return _FORCE[-1]
    from . import span as _span

    if not _span.enabled():
        return False
    return os.environ.get(SAMPLE_ENV, "") != "0"


class force_sampling:
    """Context manager pinning sampling on (tests, memwatch --smoke) or
    off, independent of the obs switch."""

    def __init__(self, on: bool = True):
        self.on = on

    def __enter__(self):
        _FORCE.append(self.on)
        return self

    def __exit__(self, *exc):
        _FORCE.pop()
        return False


# ---------------------------------------------------------------------------
# AOT compile-time analysis
# ---------------------------------------------------------------------------

_MA_FIELDS = (
    ("argument_size_in_bytes", "arg_bytes"),
    ("output_size_in_bytes", "out_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "code_bytes"),
)


def _ma_dict(ma) -> Dict[str, float]:
    out = {}
    for src, dst in _MA_FIELDS:
        try:
            out[dst] = float(getattr(ma, src))
        except (AttributeError, TypeError):
            out[dst] = 0.0
    out["peak_bytes"] = out["arg_bytes"] + out["out_bytes"] + out["temp_bytes"]
    return out


def aot_memory_analysis(fn, *args, donate_argnums=(), static_argnums=()
                        ) -> Optional[Dict[str, float]]:
    """Lower + compile ``fn(*args)`` and return XLA's buffer-assignment
    numbers (PER-DEVICE for partitioned programs): argument / output /
    temp / alias bytes plus their sum as ``peak_bytes``.  Returns None
    when the backend offers no analysis."""
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=donate_argnums, static_argnums=static_argnums)
    try:
        # measure a FRESH compile: an executable deserialized from the
        # persistent compilation cache reports empty buffer-assignment
        # stats (alias/temp bytes read 0), which would fake the exact
        # lost-donation signal this analysis exists to catch
        prev = getattr(jax.config, "jax_enable_compilation_cache", None)
        if prev:
            jax.config.update("jax_enable_compilation_cache", False)
        try:
            compiled = jitted.lower(*args).compile()
        finally:
            if prev:
                jax.config.update("jax_enable_compilation_cache", True)
        return _ma_dict(compiled.memory_analysis())
    except Exception:
        return None


def donation_alias_bytes(fn, args, donate_argnums,
                         static_argnums=()) -> Tuple[float, float]:
    """(donated_bytes, aliased_bytes) of the compiled executable: the
    donated operands' total size and how many bytes XLA actually aliased
    into outputs.  A donation that compiles with aliased < donated is
    the 'donated buffers were not usable' bug class — measured here, not
    assumed from the jaxpr (that static half is slate_lint's
    check_donation)."""
    import jax

    import numpy as _np

    donated = 0.0
    for i in donate_argnums:
        a = args[i]
        nbytes = float(a.size) * a.dtype.itemsize
        # memory_analysis reports PER-DEVICE sizes for partitioned
        # programs; compare against the donated operand's per-device
        # SHARD bytes (shard_shape handles replicated and partially-
        # replicated layouts, where bytes-per-device exceeds
        # nbytes / device_count)
        try:
            shard = a.sharding.shard_shape(a.shape)
            donated += float(_np.prod(shard)) * a.dtype.itemsize
        except Exception:
            donated += nbytes
    ma = aot_memory_analysis(
        jax.jit(fn, donate_argnums=tuple(donate_argnums),
                static_argnums=tuple(static_argnums)), *args)
    aliased = ma["alias_bytes"] if ma else 0.0
    return donated, aliased


# ---------------------------------------------------------------------------
# Live-buffer / device-stats sampling
# ---------------------------------------------------------------------------


def device_live_bytes() -> Tuple[float, Dict[str, float]]:
    """(total, per-device) RESIDENT bytes of every live jax.Array.
    Per-device attribution uses ``sharding.shard_shape`` — a replicated
    array occupies its full bytes on EVERY device it lives on (dividing
    nbytes by the device count would understate real HBM pressure by the
    replication factor) — and ``total`` is the sum of those per-device
    residencies, i.e. fleet-resident bytes, not logical array bytes.
    One ``jax.live_arrays()`` walk (counted in LIVE_CALLS for the
    zero-overhead-when-disabled test)."""
    global LIVE_CALLS
    import jax
    import numpy as _np

    LIVE_CALLS += 1
    total = 0.0
    per: Dict[str, float] = {}
    for x in jax.live_arrays():
        nb = float(getattr(x, "nbytes", 0) or 0)
        try:
            devs = list(x.sharding.device_set)
            shard_nb = (float(_np.prod(x.sharding.shard_shape(x.shape)))
                        * x.dtype.itemsize)
        except Exception:
            devs, shard_nb = [], nb
        if devs:
            for d in devs:
                key = str(d)
                per[key] = per.get(key, 0.0) + shard_nb
            total += shard_nb * len(devs)
        else:
            total += nb
    return total, per


def device_memory_stats() -> Dict[str, Dict[str, float]]:
    """Per-device allocator stats (bytes_in_use / peak_bytes_in_use /
    bytes_limit) where the backend reports them; empty on backends that
    do not (XLA CPU returns None)."""
    import jax

    out: Dict[str, Dict[str, float]] = {}
    try:
        devices = jax.devices()
    except Exception:
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            out[str(d)] = {
                k: float(stats[k])
                for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats
            }
    return out


def sample(tag: str, **extra) -> dict:
    """Record one memory sample: live-buffer totals + per-device
    allocator stats, into the bounded sample stream, the metrics
    registry (``mem.*`` gauges), and the running maxima the RunReport
    ``mem`` section carries."""
    from . import context as _context

    live, per_live = device_live_bytes()
    stats = device_memory_stats()
    s = {
        "t": time.perf_counter(),
        "tag": tag,
        "live_bytes": live,
        "live_per_device": per_live,
        "bytes_in_use": {d: v.get("bytes_in_use", 0.0)
                         for d, v in stats.items()},
        "peak_bytes_in_use": {d: v.get("peak_bytes_in_use", 0.0)
                              for d, v in stats.items()},
    }
    # request attribution (ISSUE 17): a sample taken under a request's
    # ambient TraceContext joins the unified Perfetto export by
    # trace_id; the tenant (bounded cardinality) also tags the gauges
    ctx = _context.current()
    if ctx is not None:
        s.setdefault("trace_id", ctx.trace_id)
        if ctx.tenant:
            s.setdefault("tenant", ctx.tenant)
    s.update(extra)
    tt = {"tenant": ctx.tenant} if ctx is not None and ctx.tenant else {}
    REGISTRY.gauge_set("mem.live_bytes", live, span=tag, **tt)
    in_use_max = max(s["bytes_in_use"].values(), default=0.0)
    peak_max = max(s["peak_bytes_in_use"].values(), default=0.0)
    if stats:
        REGISTRY.gauge_set("mem.bytes_in_use_max", in_use_max, span=tag,
                           **tt)
        REGISTRY.gauge_set("mem.peak_bytes_in_use_max", peak_max, span=tag,
                           **tt)
    with _lock:
        _STATE["samples"] += 1
        _STATE["live_bytes_max"] = max(_STATE["live_bytes_max"], live)
        _STATE["bytes_in_use_max"] = max(_STATE["bytes_in_use_max"],
                                         in_use_max)
        _STATE["peak_bytes_in_use_max"] = max(
            _STATE["peak_bytes_in_use_max"], peak_max)
        if len(SAMPLES) < _SAMPLE_CAP:
            SAMPLES.append(s)
    # live telemetry bus (ISSUE 17): sys.modules probe — free unless an
    # endpoint/test imported obs.live
    _live = sys.modules.get(__package__ + ".live")
    if _live is not None:
        _live.publish("mem", s)
    return s


def sample_span(span, outermost: bool) -> None:
    """driver_span exit hook: sample at OUTERMOST span boundaries only —
    top-level spans and the first span of each request, through which
    the request's trace_id reaches the sample (nested phase spans would
    walk live_arrays per phase for the same information).  Attaches the
    live-byte total to the span's metrics so it rides into RunReport span
    rows."""
    if not outermost or not sampling_active():
        return
    try:
        s = sample(span.name)
    except Exception:
        return
    span.metrics["mem.live_bytes"] = s["live_bytes"]
    peak = max(s["peak_bytes_in_use"].values(), default=0.0)
    if peak:
        span.metrics["mem.peak_bytes_in_use"] = peak


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM")


def is_oom(exc: BaseException) -> bool:
    msg = str(exc)
    return any(m in msg for m in _OOM_MARKERS)


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(b) < 1024 or unit == "GiB":
            return f"{b:.2f} {unit}" if unit != "B" else f"{b:.0f} B"
        b /= 1024
    return f"{b:.2f} GiB"


def oom_report_text(driver: str, exc: BaseException, top: int = 12) -> str:
    """The forensics report: live tensors by size, device stats, the
    MemoryModel's predicted peaks for the failing op class, and the
    escape routes."""
    import jax

    from . import memmodel

    lines = [f"== slate_tpu OOM forensics: {driver} ==",
             f"   {type(exc).__name__}: {str(exc)[:400]}"]
    stats = device_memory_stats()
    for d, v in sorted(stats.items())[:8]:
        lines.append(
            f"   {d}: in_use={_fmt_bytes(v.get('bytes_in_use', 0))} "
            f"peak={_fmt_bytes(v.get('peak_bytes_in_use', 0))} "
            f"limit={_fmt_bytes(v.get('bytes_limit', 0))}")
    try:
        arrays = sorted(jax.live_arrays(),
                        key=lambda x: -(getattr(x, "nbytes", 0) or 0))
        global LIVE_CALLS
        LIVE_CALLS += 1
        total = sum(float(getattr(x, "nbytes", 0) or 0) for x in arrays)
        lines.append(f"   live buffers: {len(arrays)} arrays, "
                     f"{_fmt_bytes(total)} total; largest:")
        for x in arrays[:top]:
            try:
                ndev = len(x.sharding.device_set)
            except Exception:
                ndev = 1
            lines.append(f"     {str(x.shape):>18} {str(x.dtype):<10} "
                         f"{_fmt_bytes(float(x.nbytes))} over {ndev} dev")
    except Exception:
        lines.append("   (live-buffer walk unavailable)")
    budget = memmodel.hbm_budget()
    lines.append(f"   model budget: {_fmt_bytes(budget)} per device "
                 f"(override via {memmodel.HBM_ENV})")
    if "potrf" in driver or "posv" in driver or "chol" in driver:
        for form, fn in (("fused_ll", memmodel.potrf_fused_ll_peak),
                         ("staged", memmodel.potrf_staged_peak),
                         ("ozaki_cache", memmodel.potrf_ozaki_cache_peak)):
            lines.append("   predicted f64 peaks at n=16384/32768 "
                         f"[{form}]: {_fmt_bytes(fn(16384))} / "
                         f"{_fmt_bytes(fn(32768))}")
    lines += [
        "   escape routes:",
        "     - big f64 potrf: the staged left-looking form "
        "(chol.potrf_left_looking_staged; potrf_array routes there "
        "eagerly above the fused-fit ceiling — memmodel.potrf_f64_form)",
        "     - Option.Lookahead=0: each depth unit pins extra panel "
        "broadcasts live (comm.la_live_buffers)",
        "     - smaller nb: panel payloads scale with nb^2 "
        "(memmodel.MemoryModel.payload_bytes)",
        "     - feasibility up front: memmodel.predict_max_n(budget)",
    ]
    return "\n".join(lines)


def handle_driver_exception(driver: str, exc: BaseException) -> None:
    """Dispatch-layer hook (obs.instrument): on RESOURCE_EXHAUSTED, count
    the event and print the forensics report to stderr.  One report per
    exception object — nested instrumented drivers (posv_mesh wrapping
    potrf_mesh) see the same exception unwind through each layer, and
    the innermost (most specific) driver gets the report.  Never raises
    — the original exception propagates from the caller."""
    if not is_oom(exc):
        return
    try:
        if getattr(exc, "_slate_oom_reported", False):
            return
        exc._slate_oom_reported = True  # type: ignore[attr-defined]
    except Exception:
        pass
    with _lock:
        _STATE["oom_events"] += 1
    REGISTRY.counter_add("mem.oom_events", 1, span=driver)
    try:
        print(oom_report_text(driver, exc), file=sys.stderr, flush=True)
    except Exception:
        pass
