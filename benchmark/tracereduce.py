"""Reduction of a profiler trace of the measured window to device numbers.

Input is what ``jax.profiler`` writes (an XSpace, read with
``jax.profiler.ProfileData``): one plane per chip (``/device:TPU:<i>``)
whose ``XLA Ops`` line holds the operations the chip ran (a loop's event
spans its body's events) and whose ``Async XLA Ops`` line holds the
asynchronous ones, and host planes holding the benchmark's own spans
(``jax.profiler.TraceAnnotation``).  The host span ``window`` bounds the
measured window; every device interval is clipped to it.

Per chip:

- busy: the union of operation intervals;
- operation time: each operation's self time (its interval less its
  nested operations'), by the operation's name in the HLO;
- idle gaps: the window minus busy, each named by the benchmark's host
  span open at the gap's midpoint (``make_inputs``, ``call``,
  ``to_host``; ``none`` where no span was open);
- exposed collective: time in which a collective runs and no other
  operation does (operations that hold others, as loops do, are left
  out of both).

Chip figures are averaged over the chips.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

WINDOW_SPAN = "window"
HOST_SPANS = ("make_inputs", "call", "to_host", "check")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
TOP = 10


def read_profile(profile):
    """(device ops per chip, host spans) from a ``ProfileData``: ops as
    {plane name: [(name, start_ns, end_ns, line name)]}, spans as [(name,
    start_ns, end_ns)]."""
    devices, spans = {}, []
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OPS_LINES:
                    ops.extend((op_name(e.name), e.start_ns, e.end_ns, line.name)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in wanted)
    return devices, spans


def layout(profile, devices, spans) -> dict:
    """What the trace held, for the run's info line: each chip's lines
    with their event counts, and the time range of the device operations
    and of the window span (one clock when they overlap)."""
    planes = {p.name: {line.name: sum(1 for _ in line.events) for line in p.lines}
              for p in profile.planes if p.name.startswith("/device:")}
    ops = [(s, e) for evs in devices.values() for _, s, e, _ in evs]
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    return {"planes": planes,
            "ops_ns": [min(s for s, _ in ops), max(e for _, e in ops)] if ops else None,
            "window_ns": list(windows[0]) if windows else None}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def nesting(events):
    """({name: self time}, leaf events) of one line's events, where an
    event that lies inside another is nested in it and a leaf holds no
    other."""
    times = defaultdict(float)
    stack, parents = [], set()  # open events: [end, name, index]
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    for i in order:
        name, s, e = events[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            times[stack[-1][1]] -= min(e, stack[-1][0]) - s
            parents.add(stack[-1][2])
        times[name] += e - s
        stack.append([e, name, i])
    return times, [ev for i, ev in enumerate(events) if i not in parents]


def union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def complement(merged, lo, hi):
    """Gaps of ``merged`` (sorted, disjoint, inside [lo, hi]) in [lo, hi]."""
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def minus(a, b) -> float:
    """Length of union ``a`` not covered by union ``b`` (both merged)."""
    covered, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return length(a) - covered


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


class _SpanIndex:
    """The benchmark's host spans, which run one after another on one
    thread, searchable by time."""

    def __init__(self, spans):
        self.spans = sorted((s, e, n) for n, s, e in spans if n in HOST_SPANS)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and self.spans[i][1] >= t:
            return self.spans[i][2]
        return "none"


def summarize(devices, spans):
    """Device numbers of the window, or None where the trace holds no
    window span or no chip ran an operation in it."""
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    index = _SpanIndex(spans)
    busy = coll = exposed = 0.0
    op_time = defaultdict(float)
    gap_time = defaultdict(float)
    for ops in devices.values():
        clipped = [(n, max(s, lo), min(e, hi), ln) for n, s, e, ln in ops if e > lo and s < hi]
        merged = union((s, e) for _, s, e, _ in clipped)
        busy += length(merged)
        leaves = []
        for line in OPS_LINES:
            times, leaf = nesting([(n, s, e) for n, s, e, ln in clipped if ln == line])
            leaves += leaf
            for name, t in times.items():
                op_time[name] += t
        for s, e in complement(merged, lo, hi):
            gap_time[index.at(0.5 * (s + e))] += e - s
        c = union((s, e) for n, s, e in leaves if is_collective(n))
        if c:
            coll += length(c)
            exposed += minus(c, union((s, e) for n, s, e in leaves if not is_collective(n)))
    chips = len(devices)
    if busy == 0.0:
        return None
    ns = 1e-9 / chips

    def top(table):
        return [[k, v * ns] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "chips": chips,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * ns,
        "collective_s": coll * ns,
        "exposed_collective_s": exposed * ns,
        "device_ops": top(op_time),
        "idle_gaps": top(gap_time),
    }
