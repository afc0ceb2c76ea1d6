"""The program's own marks in a profiler trace: named device scopes and
host spans.

The program names its work two ways (``slate_tpu.parallel.comm.phase_scope``
and ``slate_tpu.obs.driver_span``), both with the observability layer off:

- a named scope on every op it traces, which XLA keeps as the op's
  ``op_name`` metadata.  On a v5e the profiler keeps that string in the
  ``tf_op`` stat of the device plane's event metadata (one entry per
  distinct op), e.g. ``jit(solve)/potrf/while/body/bulk/dot_general:``.
  ``jax.profiler.ProfileData`` shows event stats only, so ``op_scopes``
  reads the metadata from the XSpace bytes.  Ops XLA adds itself (layout
  copies, for one) carry no ``tf_op``.
- a host span named ``slate_tpu/<name>`` per driver and Router phase
  (``PROFILER_PREFIX``).

``summarize`` attributes each chip's device self time to the innermost
phase of ``PHASES`` on the op's scope path and to the innermost stage of
``STAGES`` (``unscoped`` where it has none), names device ops
``<stage>/<phase>:<op>`` (``-`` for a missing part, ``-:<op>`` for an op
with no scope at all), and names each idle gap by the innermost span open
at its midpoint among the benchmark's spans and the program's.  Chip
figures are means over the chips, as in ``tracereduce``.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark import tracereduce

PROFILER_PREFIX = "slate_tpu/"
PHASES = ("panel", "swap", "bcast", "bulk", "regroup")
STAGES = ("potrf", "potrs", "getrf", "trsm", "redistribute")
UNSCOPED = "unscoped"
SERVE_SPAN = PROFILER_PREFIX + "serve."
TOP = tracereduce.TOP


# -- the XSpace's event metadata ------------------------------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto.
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_NAME = 2
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7
OP_NAME_STAT = "tf_op"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of the message in ``buf[lo:hi]``: an int for
    a varint, (start, end) of the payload for a length-delimited field;
    fixed-width fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """(key, value span) of one protobuf map entry."""
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == _MAP_KEY:
            key = v
        elif f == _MAP_VALUE:
            value = v
    return key, value


def op_scopes(raw: bytes) -> dict:
    """{device event name (the HLO text ``ProfileData`` reports): scope
    path} from the ``tf_op`` stats of the device planes' event metadata,
    with the trailing ``:<op type>`` cut.  Only the planes' metadata maps
    are decoded; their event lines are skipped by length."""
    buf = memoryview(raw)
    scopes = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == _PLANE_NAME:
                name = _text(buf, v)
                if not name.startswith("/device:"):
                    break
            elif pf == _PLANE_EVENT_MD:
                events.append(v)
            elif pf == _PLANE_STAT_MD:
                key, value = _map_values(buf, v)
                for sf, sv in _fields(buf, *value):
                    if sf == _STAT_MD_NAME:
                        stat_names[key] = _text(buf, sv)
        if not name.startswith("/device:"):
            continue
        wanted = {k for k, n in stat_names.items() if n == OP_NAME_STAT}
        for entry in events:
            _, md = _map_values(buf, entry)
            ev_name, path = None, None
            for ef, ev in _fields(buf, *md):
                if ef == _EVENT_MD_NAME:
                    ev_name = _text(buf, ev)
                elif ef == _EVENT_MD_STATS:
                    stat = dict(_fields(buf, *ev))
                    if stat.get(_STAT_MD_ID) in wanted:
                        if _STAT_STR in stat:
                            path = _text(buf, stat[_STAT_STR])
                        elif _STAT_REF in stat:
                            path = stat_names.get(stat[_STAT_REF])
            if ev_name is not None and path:
                scopes.setdefault(ev_name, path.rpartition(":")[0] if ":" in path else path)
    return scopes


# -- reading and reducing ---------------------------------------------------------


def read(profile, raw: bytes):
    """(device ops per chip, spans) of a trace: ops as {plane: [(op name,
    start_ns, end_ns, line, scope path)]}, spans as [(name, start_ns,
    end_ns)] holding the benchmark's own and the program's
    (``slate_tpu/``-prefixed) host spans."""
    scopes = op_scopes(raw)
    devices, spans = {}, []
    ours = set(tracereduce.HOST_SPANS) | {tracereduce.WINDOW_SPAN}
    for plane in profile.planes:
        if tracereduce._DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in tracereduce.OPS_LINES:
                    ops.extend((tracereduce.op_name(e.name), e.start_ns, e.end_ns,
                                line.name, scopes.get(e.name, ""))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns) for e in line.events
                             if e.name in ours or e.name.startswith(PROFILER_PREFIX))
    return devices, spans


def innermost(path: str, vocabulary) -> str:
    """The last component of a scope path that the vocabulary names, or
    ''."""
    for part in reversed(path.split("/")):
        if part in vocabulary:
            return part
    return ""


def op_label(path: str, op: str) -> str:
    stage, phase = innermost(path, STAGES), innermost(path, PHASES)
    if not stage and not phase:
        return f"-:{op}"
    return f"{stage or '-'}/{phase or '-'}:{op}"


def gap_names(gaps, spans):
    """The innermost span open at each gap's midpoint ('none' where none
    is), for spans that nest as one thread's do; ``gaps`` sorted."""
    order = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    names, stack, j = [], [], 0
    for s, e in gaps:
        t = 0.5 * (s + e)
        while j < len(order) and order[j][1] <= t:
            while stack and stack[-1][2] < order[j][1]:
                stack.pop()
            stack.append(order[j])
            j += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        names.append(stack[-1][0] if stack else "none")
    return names


def summarize(devices, spans):
    """Scope and span figures of the window, or None where the trace holds
    no window span or no chip ran an operation in it."""
    windows = [(s, e) for n, s, e in spans if n == tracereduce.WINDOW_SPAN]
    if not windows or not devices:
        return None
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
    inner = [sp for sp in spans if sp[0] != tracereduce.WINDOW_SPAN]
    phase_t, stage_t = defaultdict(float), defaultdict(float)
    op_t, unscoped_t, gap_t = defaultdict(float), defaultdict(float), defaultdict(float)
    busy = 0.0
    for ops in devices.values():
        clipped = [(n, max(s, lo), min(e, hi), ln, sc) for n, s, e, ln, sc in ops
                   if e > lo and s < hi]
        merged = tracereduce.union((s, e) for _, s, e, _, _ in clipped)
        busy += tracereduce.length(merged)
        for line in tracereduce.OPS_LINES:
            times, _ = tracereduce.nesting([((sc, n), s, e) for n, s, e, ln, sc in clipped
                                            if ln == line])
            for (path, op), t in times.items():
                phase_t[innermost(path, PHASES) or UNSCOPED] += t
                stage_t[innermost(path, STAGES) or UNSCOPED] += t
                op_t[op_label(path, op)] += t
                if not path:
                    unscoped_t[op] += t
        gaps = tracereduce.complement(merged, lo, hi)
        for (s, e), name in zip(gaps, gap_names(gaps, inner)):
            gap_t[name] += e - s
    if busy == 0.0:
        return None
    ns = 1e-9 / len(devices)

    def top(table):
        return [[k, v * ns] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "chips": len(devices),
        "window_s": (hi - lo) * 1e-9,
        "phase_s": {k: v * ns for k, v in sorted(phase_t.items())},
        "stage_s": {k: v * ns for k, v in sorted(stage_t.items())},
        "serve_idle_s": sum(v for k, v in gap_t.items() if k.startswith(SERVE_SPAN)) * ns,
        "device_ops": top(op_t),
        "unscoped_ops": top(unscoped_t),
        "idle_gaps": top(gap_t),
    }
