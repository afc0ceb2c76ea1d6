"""One run of one cell of ``BENCHMARK.json``.

The harness is driven by data.  A cell names a configuration
(``configs/<config>.json``: the operation, dtype and operand), a traffic
mix (``traffic/<traffic>.json``: the path and the sizes the operation
reads, such as n, nrhs, block size, grid and batch, and how often a call
repeats an operator) and its limits (``limits/<cell>.json``).  The
operation names its module (``operations/<op>.py``: the call's inputs,
flops, least bytes and the numbers its check compares) and its plain
reference (``reference/<op>.py``); the path and the operation name the
entry (``entries/<op>_<path>.py``); the operand kind names its generator
(``operands/<kind>.py``); every metric is a reader of its own
(``metrics/<name>.py``).

A run: refuse anything but a TPU with the chips the cell asks for, load
or compile the cell's programs and make one warm-up call (set-up), then
a closed loop with one caller: make the next call's inputs on the device
from (seed, call index), call the entry, take its answer to the host;
until ``--seconds`` have passed, finishing the call in flight.  Then read
the chips' peak memory, free the program's state and check the answers
on the host.  The last stdout line is the result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Callable, List, Optional

import numpy as np

from benchmark import tracereduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SPEC_FILE = os.path.join(ROOT, "BENCHMARK.json")
CACHE_DIR = os.path.join(ROOT, ".bench_jax_cache")
WARMUP_INDEX = 2**32 - 1  # call index of the warm-up inputs; window calls count from 0
CHECK_SAMPLE = 64  # calls checked, drawn from the seed, where a window holds more
# A traced run measures at most this long: a 2x2 mesh call alone leaves
# over a million operations per chip in the trace, and writing and reading
# a 20 s trace of it took over 500 s on a v5e host.
TRACE_WINDOW_S = 5.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot measure this cell here; no result is printed."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind} module {name!r} at {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    operation: Any
    entry: Any
    operand: Any
    reference: Any

    @property
    def problems(self) -> int:
        """Problems solved in one call."""
        return self.operation.problems(self.traffic)

    @property
    def dtype(self):
        import jax.numpy as jnp

        return jnp.dtype(self.config["dtype"])

    def call_flops(self) -> float:
        return self.operation.call_flops(self.traffic)

    def call_bytes(self) -> float:
        return self.operation.call_bytes(self.traffic, self.dtype.itemsize)


def load_spec() -> dict:
    if not os.path.isfile(SPEC_FILE):
        raise Refused(f"no {os.path.basename(SPEC_FILE)} at the checkout's root")
    return load_json(SPEC_FILE)


def make_cell(name: str, chips: int, config: dict, traffic: dict, limits: dict) -> Cell:
    return Cell(name=name, chips=chips, config=config, traffic=traffic, limits=limits,
                operation=load_module("operations", config["op"]),
                entry=load_module("entries", f"{config['op']}_{traffic['path']}"),
                operand=load_module("operands", config["matrix"]),
                reference=load_module("reference", config["op"]))


def load_cell(name: str, spec: dict) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]

    def data(kind, key):
        path = os.path.join(BENCH_DIR, kind, key + ".json")
        if not os.path.isfile(path):
            raise Refused(f"no {kind} file for {key!r}")
        return load_json(path)

    return make_cell(name, int(w["chips"]), data("configs", w["config"]),
                     data("traffic", w["traffic"]), data("limits", name))


def metric_names(spec: dict, section: str, cell: str) -> List[str]:
    return [m["name"] for m in spec[section] if cell in m.get("workloads", [cell])]


def metric_reader(name: str):
    """``metrics/<name>.py``; a split quantity (``<base>.<group>``, one per
    group of cells that report different end-to-end metrics) falls back
    to its base's reader."""
    base = name.split(".", 1)[0]
    own = os.path.isfile(os.path.join(BENCH_DIR, "metrics", name + ".py"))
    return load_module("metrics", name if own else base)


def metric_units(spec: dict) -> dict:
    return {m["name"]: m["unit"] for m in spec["end_to_end"] + spec["per_layer"]}


# -- the run's preconditions --------------------------------------------------


def guard_environment() -> None:
    """The benchmark measures the defaults: no ``SLATE_TPU_*`` variable may
    steer the program, and its observability layer stays off (it turns
    NumMonitor on and so compiles other programs)."""
    bad = sorted(k for k in os.environ if k.startswith("SLATE_TPU_"))
    if bad:
        raise Refused(f"SLATE_TPU_* variables are set ({', '.join(bad)}); the benchmark runs the defaults")


def guard_program() -> None:
    from slate_tpu import obs

    if obs.enabled():
        raise Refused("slate_tpu.obs is enabled; the benchmark runs with it off")


def chip_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"needs a TPU; JAX found {devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_compile_cache() -> None:
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction: every run finds every program


class CompileCounter:
    """Backend compiles (fresh or from the persistent cache) and their
    seconds, from JAX's monitoring events."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


# -- inputs -------------------------------------------------------------------


def base_key(seed: int):
    import jax.numpy as jnp

    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)  # a raw threefry key


def operator_index(traffic: dict, seed: int, index: int) -> int:
    """The call whose operator call ``index`` solves with.  Its own, unless
    the traffic sets ``repeat_share`` r: then each call after the first,
    with chance r drawn from (seed, call index), keeps the operator of
    the call before it, as a caller re-solving one system does."""
    share = float(traffic.get("repeat_share", 0.0))
    if index == WARMUP_INDEX:
        return index
    while index > 0 and share > 0.0 and \
            np.random.default_rng([seed % 2**64, index]).random() < share:
        index -= 1
    return index


def input_maker(cell: Cell, seed: int, shardings=None) -> Callable:
    """call index -> the call's inputs on the device, placed as the entry
    wants them (``shardings``, a prefix of the inputs' tree) or on the
    default device."""
    import jax
    import jax.numpy as jnp

    body = cell.operation.input_body(cell.traffic, cell.operand, cell.dtype)
    make = jax.jit(body) if shardings is None else jax.jit(body, out_shardings=shardings)
    key = base_key(seed)

    def inputs(index: int):
        return make(key, jnp.uint32(index),
                    jnp.uint32(operator_index(cell.traffic, seed, index)))

    return inputs


# -- the window ---------------------------------------------------------------


@dataclasses.dataclass
class CallRecord:
    index: int
    make_s: float
    latency_s: float
    flops: float
    nbytes: float
    ok: bool
    x: Optional[list]  # host answers, one per problem


def _span(on: bool, name: str):
    import jax

    return jax.profiler.TraceAnnotation(name) if on else contextlib.nullcontext()


def one_call(cell: Cell, call: Callable, inputs, spans: bool):
    """Call the entry and bring its answer to the host.  Returns (host
    answers, one per problem, ok, seconds); a call that raises is a
    failed call."""
    import jax

    t0 = time.perf_counter()
    try:
        with _span(spans, "call"):
            out = call(*inputs)
        with _span(spans, "to_host"):
            x, info = jax.device_get(out)
    except Exception:  # the run reports a failed call and goes on
        traceback.print_exc(file=sys.stderr)
        return None, False, time.perf_counter() - t0
    secs = time.perf_counter() - t0
    ok = info is None or int(np.max(np.abs(np.asarray(info)))) == 0
    return cell.operation.outputs(cell.traffic, x), ok, secs


def run_window(cell: Cell, call: Callable, make: Callable, seconds: float,
               spans: bool):
    import jax

    calls: List[CallRecord] = []
    with _span(spans, "window"):
        t0 = time.perf_counter()
        index = 0
        while True:
            t_make = time.perf_counter()
            with _span(spans, "make_inputs"):
                inputs = jax.block_until_ready(make(index))
            make_s = time.perf_counter() - t_make
            xs, ok, secs = one_call(cell, call, inputs, spans)
            del inputs  # the next call's operator must not sit next to this one
            calls.append(CallRecord(index, make_s, secs, cell.call_flops(),
                                    cell.call_bytes(), ok, xs))
            index += 1
            t1 = time.perf_counter()
            if t1 - t0 >= seconds:
                break
    return calls, t0, t1


# -- the check ----------------------------------------------------------------


def call_readings(cell: Cell, make: Callable, seed: int, index: int, xs) -> dict:
    """The numbers the operation compares for call ``index``, whose host
    answers are ``xs``, with its inputs made again from the seed.  A
    number that is not a number reads as infinitely bad."""
    got = cell.operation.readings(cell.traffic, make(index), xs, seed, index)
    return {k: float("inf") if np.isnan(v) else float(v) for k, v in got.items()}


def checked_calls(calls: List[CallRecord], seed: int) -> List[CallRecord]:
    good = [c for c in calls if c.ok]
    if len(good) <= CHECK_SAMPLE:
        return good
    pick = np.random.default_rng([seed % 2**64, len(calls)]).choice(
        len(good), CHECK_SAMPLE, replace=False)
    return [good[i] for i in sorted(pick)]


def check(cell: Cell, calls: List[CallRecord], make: Callable, seed: int) -> dict:
    """Every number compared, with its limit: for each limit of the cell
    the worst reading over the checked calls (none where no call was
    checked or the operation reads no such number), and the problems not
    solved."""
    worst: dict = {}
    for c in checked_calls(calls, seed):
        for name, v in call_readings(cell, make, seed, c.index, c.x).items():
            worst[name] = max(worst.get(name, v), v)
    checks = {name: {"value": worst.get(name), "limit": lim["limit"]}
              for name, lim in cell.limits.items()}
    checks["failed"] = {"value": sum(cell.problems for c in calls if not c.ok), "limit": 0}
    return checks


def passes(checks: dict) -> bool:
    return all(v["value"] is not None and np.isfinite(v["value"]) and v["value"] <= v["limit"]
               for v in checks.values())


# -- the run ------------------------------------------------------------------


@dataclasses.dataclass
class RunView:
    """What a metric reader sees."""
    cell: Cell
    chips: int
    peaks: dict
    calls: List[CallRecord]
    window_s: float
    setup_s: float
    peak_bytes: int
    window_compiles: int
    trace: Optional[dict]


def peak_bytes(memory: List[dict]) -> int:
    """The fullest chip's peak: its arrays' peak (``peak_bytes_in_use``)
    plus the peak it reserved for programs' temporaries
    (``peak_bytes_reserved``), which a TPU keeps apart from the arrays."""
    return max(m.get("peak_bytes_in_use", 0) + m.get("peak_bytes_reserved", 0) for m in memory)


def emit(obj: dict, stream=sys.stdout) -> None:
    print(json.dumps(obj), file=stream, flush=True)


@contextlib.contextmanager
def trace_window(on: bool):
    """Profile the window into a temporary directory; yields a dict that
    receives the reduced trace once the window has closed."""
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
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, peaks: dict,
        spec: dict, t_start: float) -> dict:
    import jax

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    t_setup = time.perf_counter()
    call = cell.entry.build(cell.traffic, devices)
    make = input_maker(cell, seed, cell.entry.shardings(cell.traffic, devices))
    jax.block_until_ready(make(WARMUP_INDEX))
    data_s = time.perf_counter() - t_setup
    inputs = make(WARMUP_INDEX)
    _, warm_ok, warm_s = one_call(cell, call, inputs, False)
    del inputs
    if not warm_ok:
        print("benchmark: the warm-up call failed", file=sys.stderr)
    setup_compile = (counter.count, counter.seconds)

    with trace_window(trace) as traced:
        calls, t0, t1 = run_window(cell, call, make,
                                   min(seconds, TRACE_WINDOW_S) if trace else seconds, trace)
    window_compiles = counter.count - setup_compile[0]
    memory = [d.memory_stats() or {} for d in devices]  # {} on the CPU of the tests
    peak = peak_bytes(memory)
    emit({"info": "setup", "import_s": t_setup - t_start, "compile_s": setup_compile[1],
          "compiles": setup_compile[0], "data_s": data_s, "warmup_call_s": warm_s,
          "setup_s": t0 - t_start})
    emit({"info": "memory", "chips": memory})
    window_s = t1 - t0
    emit({"info": "window", "calls": len(calls), "latency_samples": len(calls),
          "window_s": window_s, "make_inputs_s": sum(c.make_s for c in calls),
          "make_inputs_share": sum(c.make_s for c in calls) / window_s,
          "window_compiles": window_compiles,
          "trace_reduce_s": traced.get("reduce_s")})
    if trace:
        emit({"info": "trace", **traced.get("layout", {})})

    with _span(trace, "check"):
        checks = check(cell, calls, make, seed)
    view = RunView(cell, cell.chips, peaks, calls, window_s, t0 - t_start, peak,
                   window_compiles, traced.get("summary"))
    section = "per_layer" if trace else "end_to_end"
    units = metric_units(spec)
    metrics = {}
    for name in metric_names(spec, section, cell.name):
        value = metric_reader(name).read(view)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": passes(checks), "attempted": len(calls) * cell.problems,
              "failed": checks["failed"]["value"], "metrics": metrics, "device": device}
    summary = view.trace
    if trace and summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    try:
        spec = load_spec()
        cell = load_cell(args.workload, spec)
        guard_environment()
        devices = chip_devices(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
        enable_compile_cache()
        guard_program()
    except Refused as e:
        print(f"benchmark: {e}; no result", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices, peaks, spec, t_start)
    for name, v in result["checks"].items():
        print(f"check {name} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    emit(result)
    return 0
