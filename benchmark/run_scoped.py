#!/usr/bin/env python3
"""Run one benchmark cell once with a traced window, as ``run.py --trace
1`` does, and read the program's named device scopes and host spans from
the same trace (``benchmark/scopes.py``):

    python3 benchmark/run_scoped.py --workload <cell> --seed <n> --seconds <s>

The result line is ``run.py``'s, with the scope metrics of the cell
(``SCOPE_METRICS``) among its per-layer metrics where the trace holds
what they read.  The ``{"info": "trace", ...}`` line gains ``scopes``:
device self time by phase and by stage, the device ops named
``<stage>/<phase>:<op>``, the ops with no scope, and the idle gaps named
by the innermost open span, program spans included.  ``harness.py`` and
``tracereduce.py`` do not read scopes; this script lays the scope
reduction over the harness's traced window.
"""

import time

T_START = time.perf_counter()

import contextlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmark import harness, scopes, tracereduce  # noqa: E402

# the cells in which each scope metric finds something to read
SCOPE_METRICS = {
    "posv-f32-n30720": ["panel_pct", "bulk_roofline"],
    "gesv-mesh2x2-f32-n32768": ["panel_pct", "swap_pct", "bulk_roofline"],
    "posv-batch8-f32-n1024": ["router_idle_pct.batch"],
}


@contextlib.contextmanager
def scoped_trace_window(on: bool):
    """``harness.trace_window`` whose summary also carries ``scopes``."""
    import jax
    from jax.profiler import ProfileData

    holder = {}
    if not on:
        yield holder
        return
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
                 if f.endswith(".xplane.pb")]
        if paths:
            t0 = time.perf_counter()
            profile = ProfileData.from_file(paths[0])
            devices, spans = tracereduce.read_profile(profile)
            holder["summary"] = tracereduce.summarize(devices, spans)
            holder["reduce_s"] = time.perf_counter() - t0
            holder["layout"] = tracereduce.layout(profile, devices, spans)
            t1 = time.perf_counter()
            with open(paths[0], "rb") as f:
                scoped = scopes.summarize(*scopes.read(profile, f.read()))
            holder["layout"]["scopes"] = scoped
            holder["layout"]["scopes_read_s"] = time.perf_counter() - t1
            if holder["summary"] is not None:
                holder["summary"]["scopes"] = scoped
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv) -> int:
    names, units = harness.metric_names, harness.metric_units

    def metric_names(spec, section, cell):
        extra = SCOPE_METRICS.get(cell, []) if section == "per_layer" else []
        return names(spec, section, cell) + extra

    def metric_units(spec):
        return {**units(spec), **{m: "%" for ms in SCOPE_METRICS.values() for m in ms}}

    harness.trace_window = scoped_trace_window
    harness.metric_names = metric_names
    harness.metric_units = metric_units
    return harness.main(list(argv) + ["--trace", "1"], T_START)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
