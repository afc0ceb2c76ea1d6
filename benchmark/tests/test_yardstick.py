"""The yardstick: flop counts, peaks, the trace reduction, the benchmark's
definition and the run's refusals."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import flops, harness, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))


# -- flop counts against naive operation counts (LAWN 41 conventions) --------


def naive_potrf(n):
    """Multiplies (divisions and square roots among them) and adds of an
    unblocked Cholesky."""
    muls = adds = 0
    for j in range(n):
        muls += j + 1          # l_jk^2 terms, then the square root
        adds += j
        rows = n - 1 - j
        muls += rows * (j + 1)  # l_ik l_jk terms, then the division
        adds += rows * j
    return muls, adds


def naive_getrf(n):
    """Unblocked LU: one reciprocal per column, the column scaling and the
    rank-1 update."""
    muls = adds = 0
    for k in range(n):
        m = n - 1 - k
        muls += 1 + m + m * m
        adds += m * m
    return muls, adds


def naive_trsv_pair(n, unit_lower: bool):
    """Forward then back substitution for one right-hand side."""
    lower_muls = n * (n - 1) // 2 + (0 if unit_lower else n)
    lower_adds = n * (n - 1) // 2
    return lower_muls + n * (n + 1) // 2, lower_adds + n * (n - 1) // 2


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 13])
def test_factor_counts_match_naive_counts(n):
    assert (flops.fmuls_potrf(n), flops.fadds_potrf(n)) == pytest.approx(naive_potrf(n))
    assert (flops.fmuls_getrf(n, n), flops.fadds_getrf(n, n)) == pytest.approx(naive_getrf(n))


@pytest.mark.parametrize("n,nrhs", [(1, 1), (4, 3), (9, 16)])
def test_solve_counts_match_naive_counts(n, nrhs):
    muls, adds = naive_trsv_pair(n, unit_lower=False)
    assert (flops.fmuls_potrs(n, nrhs), flops.fadds_potrs(n, nrhs)) == pytest.approx(
        (nrhs * muls, nrhs * adds))
    muls, adds = naive_trsv_pair(n, unit_lower=True)
    assert (flops.fmuls_getrs(n, nrhs), flops.fadds_getrs(n, nrhs)) == pytest.approx(
        (nrhs * muls, nrhs * adds))


def test_cell_call_flops():
    assert flops.posv(30720, 16) == pytest.approx(9.69e12, rel=1e-3)
    assert flops.gesv(32768, 16) == pytest.approx(2.349e13, rel=1e-3)
    assert 8 * flops.posv(1024, 4) == pytest.approx(2.935e9, rel=1e-3)
    assert flops.gesv(16384, 16) == pytest.approx(2.94e12, rel=1e-3)


def test_operation_modules_count_a_call():
    posv = harness.load_module("operations", "posv")
    gesv = harness.load_module("operations", "gesv")
    batch = {"n": 1024, "nrhs": 4, "batch": 8}
    assert posv.problems(batch) == 8 and gesv.problems({"n": 4, "nrhs": 1}) == 1
    assert posv.call_flops(batch) == pytest.approx(8 * flops.posv(1024, 4))
    assert gesv.call_flops({"n": 32768, "nrhs": 16}) == flops.gesv(32768, 16)
    assert posv.call_bytes(batch, 4) == 8 * 4 * (1024 * 1024 + 2 * 1024 * 4)


def test_least_seconds_takes_the_larger_bound():
    assert flops.least_seconds(2e12, 1.0, 1e12, 1e9, 1) == 2.0
    assert flops.least_seconds(1.0, 8e9, 1e12, 1e9, 4) == 2.0


# -- traffic --------------------------------------------------------------------


def test_operator_index_without_repeats_is_the_call():
    assert [harness.operator_index({}, 5, i) for i in range(50)] == list(range(50))
    assert harness.operator_index({"repeat_share": 1.0}, 5, harness.WARMUP_INDEX) == \
        harness.WARMUP_INDEX


def test_operator_index_repeats_a_share_of_calls():
    traffic = {"repeat_share": 0.75}
    seed = 2**40 + 9
    ops = [harness.operator_index(traffic, seed, i) for i in range(400)]
    assert ops == [harness.operator_index(traffic, seed, i) for i in range(400)]
    assert ops[0] == 0
    assert all(o == i or o == ops[i - 1] for i, o in enumerate(ops) if i)
    repeats = sum(o != i for i, o in enumerate(ops)) / len(ops)
    assert 0.65 < repeats < 0.85
    assert ops != [harness.operator_index(traffic, seed + 1, i) for i in range(400)]
    assert set(harness.operator_index({"repeat_share": 1.0}, 3, i) for i in range(20)) == {0}


def test_a_repeated_operator_keeps_a_and_takes_new_right_hand_sides():
    import numpy as np

    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/spd-f32.json")
    cell = harness.make_cell("t", 1, cfg, {"path": "api", "n": 16, "nrhs": 2,
                                           "repeat_share": 1.0}, {})
    make = harness.input_maker(cell, 77)
    (a0, b0), (a1, b1) = make(0), make(1)
    np.testing.assert_array_equal(a0, a1)
    assert not np.array_equal(b0, b1)
    cell.traffic.pop("repeat_share")
    (a2, b2) = harness.input_maker(cell, 77)(1)
    assert not np.array_equal(a0, a2)
    np.testing.assert_array_equal(b1, b2)


# -- peaks -----------------------------------------------------------------------


def test_peaks_of_v5e():
    p = harness.peaks_for("TPU v5 lite")
    assert (p["bf16_flops"], p["int8_ops"], p["hbm_bytes_per_s"]) == (197e12, 393e12, 819e9)


def test_unknown_device_kind_is_refused():
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v4")


# -- trace reduction ------------------------------------------------------------


@pytest.fixture(scope="module")
def small_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "small_trace.pbtxt")) as f:
        return tracereduce.read_profile(ProfileData.from_text_proto(f.read()))


def test_trace_read(small_trace):
    devices, spans = small_trace
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(devices["/device:TPU:0"]) == 7
    assert ("while.7", 1000.0, 1100.0, "XLA Ops") in devices["/device:TPU:0"]
    assert sorted(n for n, _, _ in spans) == ["call", "make_inputs", "to_host", "window"]


def test_trace_summary(small_trace):
    s = tracereduce.summarize(*small_trace)
    assert s["chips"] == 2
    assert s["window_s"] == pytest.approx(200e-9)
    assert s["busy_s"] == pytest.approx((110 + 150) / 2 * 1e-9)  # union, clipped to the window
    assert s["collective_s"] == pytest.approx((20 + 50) / 2 * 1e-9)
    assert s["exposed_collective_s"] == pytest.approx((10 + 50) / 2 * 1e-9)
    assert dict(s["idle_gaps"]) == pytest.approx({"make_inputs": 60e-9, "to_host": 10e-9})
    ops = dict(s["device_ops"])
    assert ops["while.7"] == pytest.approx(45 / 2 * 1e-9)  # self time, children taken out
    assert ops["fusion.2"] == pytest.approx(50e-9)
    assert ops["fusion.1"] == pytest.approx(15e-9)
    assert ops["copy-start.4"] == pytest.approx(20e-9)
    assert s["device_ops"][0][0] == "fusion.2"


def test_trace_without_window_or_device_reads_nothing(small_trace):
    devices, spans = small_trace
    assert tracereduce.summarize(devices, [sp for sp in spans if sp[0] != "window"]) is None
    assert tracereduce.summarize({}, spans) is None


def test_interval_helpers():
    merged = tracereduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert tracereduce.complement(merged, 0, 10) == [(3, 5), (9, 10)]
    assert tracereduce.minus([[0, 10]], [[2, 3], [5, 8]]) == 6


# -- the benchmark's definition -------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_benchmark_json_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "benchmark/run.py"] and spec["paths"] == ["benchmark"]
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and NAME.match(c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert harness.load_json(os.path.join(harness.ROOT, c["file"]))["reduced"] == c["reduced"]
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and NAME.match(w["name"])
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(1, len(spec["workloads"]) // 2)
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [x["name"] for k in ("configs", "workloads") for x in spec[k]]
    names += [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names))
    assert len(json.dumps(spec)) < 64 * 1024


def test_every_cell_finds_its_files_by_name(spec):
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell.chips == w["chips"]
        assert cell.limits["backward_error"]["limit"] > 0
        for fn in ("problems", "call_flops", "call_bytes", "input_body", "per_problem",
                   "outputs", "readings"):
            assert callable(getattr(cell.operation, fn))
        assert callable(cell.entry.build) and callable(cell.entry.shardings)
        assert callable(cell.operand.make) and callable(cell.reference.solve_plain)
        for section in ("end_to_end", "per_layer"):
            names = harness.metric_names(spec, section, w["name"])
            assert names
            for name in names:
                assert callable(harness.metric_reader(name).read)


def test_a_missing_cell_is_refused(spec):
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell", spec)


def test_a_set_slate_variable_is_refused(monkeypatch):
    monkeypatch.setenv("SLATE_TPU_OBS", "1")
    with pytest.raises(harness.Refused):
        harness.guard_environment()


def test_a_run_off_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload", "posv-f32-n30720",
                          "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
                         cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr
