"""DGEMM's cell on the CPU at n 1024, phi 2: the float64 reference passes
the gemm tester's limit and the float32 control fails it by two orders; a
sound run of the entry is correct and reports the slice count its
products ran; a fixed 6-slice product in the program's place is not
correct.

Off a TPU the dispatch (``ops/matmul.matmul``) sends float64 to XLA's own
product, so the tests that run the program point it at the Ozaki path
(the platform check answers yes and the size gate is lowered), as
``tests/test_ozaki.py`` does.  Loading the cell's entry turns on 64-bit
mode, as a float64 user of JAX does.
"""

import importlib
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness

N = 1024
CAP = {"backward_error": {"limit": 3 * 2.0**-52}}
SEEDS = [11, 2**31 + 3, 4_000_000_007]


def small_cell(limits=CAP) -> harness.Cell:
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/dgemm-f64.json")
    return harness.make_cell("test-gemm", 1, cfg, {"path": "api", "m": N, "k": N, "n": N}, limits)


@pytest.fixture
def ozaki_dispatch(monkeypatch):
    mm = importlib.import_module("slate_tpu.ops.matmul")  # the package exports a function of that name
    monkeypatch.setattr(mm, "_tpu_is_default", lambda: True)
    monkeypatch.setattr(mm, "_OZAKI_MIN_ELEMS", N**3)
    monkeypatch.setattr(mm, "_OZAKI_MIN_DIM", N)


def run(cell: harness.Cell, seed: int = 2**31 + 7) -> dict:
    return harness.run(cell, seed, 0.3, False, jax.devices()[:1],
                       harness.peaks_for("TPU v5 lite"), harness.load_spec(), time.perf_counter())


def test_the_operand_spreads_as_the_configuration_states():
    cell = small_cell()
    assert cell.config["phi"] == cell.operand.PHI
    a, b = harness.input_maker(cell, SEEDS[0])(0)
    assert a.dtype == b.dtype == jnp.float64 and a.shape == b.shape == (N, N)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    # a full float64 significand: the entries are not float32 numbers
    assert np.mean(np.asarray(a) != np.asarray(a).astype(np.float32)) > 0.99


@pytest.mark.parametrize("seed", SEEDS)
def test_float64_reference_passes_and_float32_control_fails(seed):
    cell = small_cell()
    make = harness.input_maker(cell, seed)
    got = {}
    for who, multiply in (("f64", cell.reference.multiply_f64),
                          ("control", cell.reference.multiply_plain)):
        c = jax.device_get(jax.jit(multiply)(*make(0)))
        assert c.dtype == np.float64
        got[who] = harness.call_readings(cell, make, seed, 0, [c])["backward_error"]
    assert got["f64"] <= CAP["backward_error"]["limit"] < got["control"] / 100, got


def test_a_sound_run_is_correct_and_reports_its_slice_count(ozaki_dispatch):
    cell = small_cell()
    seed = 2**33 + 5
    devices = jax.devices()[:1]
    call = cell.entry.build(cell.traffic, devices)
    make = harness.input_maker(cell, seed, cell.entry.shardings(cell.traffic, devices))
    calls, t0, t1 = harness.run_window(cell, call, make, 0.2, False)
    assert calls and all(c.ok for c in calls)
    checks = harness.check(cell, calls, make, seed)
    assert harness.passes(checks), checks
    # at least ten times under the tester's limit
    assert checks["backward_error"]["value"] * 10 <= CAP["backward_error"]["limit"], checks
    view = harness.RunView(cell, 1, {"int8_ops": 393e12}, calls, t1 - t0, 0.0, 0, 0,
                           {"busy_s": 1.0})
    slices = harness.metric_reader("ozaki_slices").read(view)
    counts = [c.x[0][2] for c in calls]
    assert 9 < min(counts) and slices == np.mean(counts) and max(counts) <= 12
    work = sum(s * (s + 1) / 2 * 2.0 * N**3 for s in counts)
    assert harness.metric_reader("int8_roofline").read(view) == pytest.approx(100 * work / 393e12)


def test_the_slice_metrics_read_nothing_without_counts():
    rows = np.arange(4)
    calls = [harness.CallRecord(0, 0.0, 1.0, 1.0, 1.0, True, [(rows, np.zeros((4, N)), 0)])]
    view = harness.RunView(small_cell(), 1, {"int8_ops": 393e12}, calls, 1.0, 0.0, 0, 0,
                           {"busy_s": 1.0})
    assert harness.metric_reader("ozaki_slices").read(view) is None
    assert harness.metric_reader("int8_roofline").read(view) is None


def test_a_fixed_six_slice_product_is_not_correct(ozaki_dispatch):
    from slate_tpu.ops.ozaki import matmul_f64

    cell = small_cell()
    entry = cell.entry
    def six_slices(a, b):
        c = matmul_f64(a, b, n_slices=6)
        hi = c.astype(jnp.float32)
        return (hi, (c - hi.astype(c.dtype)).astype(jnp.float32)), jnp.int32(6)

    six = jax.jit(six_slices)

    class SixSlices:
        def shardings(self, traffic, devices):
            return entry.shardings(traffic, devices)

        def build(self, traffic, devices):
            return lambda a, b: (six(a, b), None)

    assert run(cell)["correct"]
    cell.entry = SixSlices()
    res = run(cell)
    assert not res["correct"], res["checks"]
    assert res["checks"]["backward_error"]["value"] > 100 * CAP["backward_error"]["limit"]


def test_the_entry_keeps_host_memory_on_a_tpu_host_only(monkeypatch):
    cell = small_cell()
    kept = []
    monkeypatch.setattr(cell.entry, "keep_host_memory", lambda: kept.append(True))
    cell.entry.build(cell.traffic, jax.devices()[:1])
    assert jax.devices()[0].platform == "tpu" or not kept


def test_glibc_takes_the_host_memory_settings():
    # in a process of its own: the settings last for the process
    code = ("from benchmark import harness; "
            "print(harness.load_module('entries', 'gemm_api').keep_host_memory())")
    out = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT, capture_output=True,
                         text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == "True"
