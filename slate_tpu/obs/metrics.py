"""Tagged metrics registry: counters, gauges, histograms.

The single sink every instrumentation source feeds — driver spans
(obs.span) and the comm-byte audit (parallel/comm.py via span
absorption).  Deliberately
tiny: a metric is (name, frozen tag set) -> scalar state, snapshots are
plain JSON-able dicts, and nothing here imports jax so the registry can
be used from tooling that never builds a mesh.
"""

from __future__ import annotations

import math
import random
import threading
import zlib
from typing import Dict, List, Optional, Tuple

# histograms keep a bounded sample reservoir next to exact running stats
_HIST_SAMPLE_CAP = 512

_Key = Tuple[str, Tuple[Tuple[str, str], ...]]


def _key(name: str, tags: Dict[str, object]) -> _Key:
    return name, tuple(sorted((k, str(v)) for k, v in tags.items()))


def quantile_of(samples: List[float], q: float,
                vmin: Optional[float] = None,
                vmax: Optional[float] = None) -> Optional[float]:
    """Linear-interpolated quantile of a sample list, clamped to the
    EXACT running [vmin, vmax] when given (a reservoir can have dropped
    the true extremes; the running stats never do).  Returns None for an
    empty list."""
    if not samples:
        return None
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile q must be in [0, 1], got {q}")
    s = sorted(samples)
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    val = s[lo] + (s[hi] - s[lo]) * (pos - lo)
    if vmin is not None:
        val = max(val, vmin)
    if vmax is not None:
        val = min(val, vmax)
    return val


class _Hist:
    __slots__ = ("count", "total", "vmin", "vmax", "samples", "_rng")

    def __init__(self, seed: int = 0) -> None:
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.samples: List[float] = []
        # deterministic per-series reservoir (Vitter algorithm R): the
        # seed derives from the series key, not process salt, so a fixed
        # workload reproduces the same sample set run-to-run
        self._rng = random.Random(seed)

    def observe(self, v: float) -> None:
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        if len(self.samples) < _HIST_SAMPLE_CAP:
            self.samples.append(v)
        else:
            j = self._rng.randrange(self.count)
            if j < _HIST_SAMPLE_CAP:
                self.samples[j] = v

    def quantile(self, q: float) -> Optional[float]:
        """Quantile estimate: EXACT (sorted-sample interpolation over
        every observation) while count <= the reservoir cap — which
        covers the tiny-count case: 1 observation returns it, 2 return
        their interpolation — and a reservoir estimate clamped to the
        exact running min/max beyond it."""
        if self.count == 0:
            return None
        return quantile_of(self.samples, q, self.vmin, self.vmax)


class MetricsRegistry:
    """Counters accumulate, gauges overwrite, histograms observe.

    Tags are free-form key=value pairs; a distinct tag set is a distinct
    series (Prometheus-style).  All methods are cheap and thread-safe.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[_Key, float] = {}
        self._gauges: Dict[_Key, float] = {}
        self._hists: Dict[_Key, _Hist] = {}

    # -- write side ---------------------------------------------------
    def counter_add(self, name: str, value: float = 1.0, **tags) -> None:
        k = _key(name, tags)
        with self._lock:
            self._counters[k] = self._counters.get(k, 0.0) + float(value)

    def gauge_set(self, name: str, value: float, **tags) -> None:
        with self._lock:
            self._gauges[_key(name, tags)] = float(value)

    def observe(self, name: str, value: float, **tags) -> None:
        k = _key(name, tags)
        with self._lock:
            h = self._hists.get(k)
            if h is None:
                h = self._hists[k] = _Hist(zlib.crc32(repr(k).encode()))
            h.observe(float(value))

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()

    # -- read side ----------------------------------------------------
    def counter_value(self, name: str, **tags) -> float:
        return self._counters.get(_key(name, tags), 0.0)

    def quantile(self, name: str, q: float, **tags) -> Optional[float]:
        """Quantile of one histogram series (None when it never
        observed) — the first-class read the SLA reductions build on
        instead of ad-hoc sorting at report time."""
        with self._lock:
            h = self._hists.get(_key(name, tags))
            return h.quantile(q) if h is not None else None

    def histogram_series(self, name: str) -> List[dict]:
        """All series of one histogram name: [{tags, count, sum, min,
        max, samples}] — the pooling surface for reductions that merge
        series across a tag (e.g. per-(op, class) latency over all
        outcomes)."""
        with self._lock:
            return [
                {"tags": dict(tags), "count": h.count, "sum": h.total,
                 "min": h.vmin, "max": h.vmax, "samples": list(h.samples)}
                for (n, tags), h in sorted(self._hists.items()) if n == name
            ]

    def snapshot(self) -> Dict[str, List[dict]]:
        """JSON-able dump: the RunReport ``metrics`` section."""
        with self._lock:
            out: Dict[str, List[dict]] = {"counters": [], "gauges": [], "histograms": []}
            for (name, tags), v in sorted(self._counters.items()):
                out["counters"].append({"name": name, "tags": dict(tags), "value": v})
            for (name, tags), v in sorted(self._gauges.items()):
                out["gauges"].append({"name": name, "tags": dict(tags), "value": v})
            for (name, tags), h in sorted(self._hists.items()):
                out["histograms"].append(
                    {
                        "name": name,
                        "tags": dict(tags),
                        "count": h.count,
                        "sum": h.total,
                        "min": h.vmin if h.count else None,
                        "max": h.vmax if h.count else None,
                        "p50": h.quantile(0.5),
                        "p95": h.quantile(0.95),
                        "p99": h.quantile(0.99),
                    }
                )
            return out


REGISTRY = MetricsRegistry()


def flatten_snapshot(snap: Dict[str, List[dict]], sep: str = "|") -> Dict[str, float]:
    """Flatten a snapshot() into scalar {series_name: value} for report
    comparison: counters/gauges by value, histograms by their sum."""
    flat: Dict[str, float] = {}

    def series(entry: dict) -> str:
        tags = entry.get("tags") or {}
        if not tags:
            return entry["name"]
        tagstr = ",".join(f"{k}={v}" for k, v in sorted(tags.items()))
        return f"{entry['name']}{sep}{tagstr}"

    for entry in snap.get("counters", []) + snap.get("gauges", []):
        flat[series(entry)] = float(entry["value"])
    for entry in snap.get("histograms", []):
        flat[series(entry)] = float(entry["sum"])
    return flat
