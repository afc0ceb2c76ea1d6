"""HPL-MxP's cell on the CPU at n 512: the operation's readings pass the
plain reference with its float64 residual and fail the control, the same
reference with its residual in float32; a sound run of the entry is
correct and counts its GMRES steps.

The limit is the cell's cap, HPL-MxP's threshold 16 in the yardstick's
normalization: 16 * 2^-52.  Loading the cell's entry turns on 64-bit
mode, as a float64 user of JAX does.
"""

import jax
import numpy as np
import pytest

from benchmark import harness

CAP = {"backward_error": {"limit": 16 * 2.0**-52}}
SEEDS = [11, 2**31 + 3, 4_000_000_007]


def small_cell():
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/hpl-mxp-f64.json")
    return harness.make_cell("test-gesv_mxp", 1, cfg, {"path": "api", "n": 512, "nrhs": 1}, CAP)


@pytest.mark.parametrize("seed", SEEDS)
def test_float64_reference_passes_and_float32_control_fails(seed):
    cell = small_cell()
    make = harness.input_maker(cell, seed)
    got = {}
    for who, solve in (("f64", cell.reference.solve_f64), ("control", cell.reference.solve_plain)):
        x, steps = jax.device_get(jax.jit(solve)(*make(0)))
        assert x.dtype == np.float64
        got[who] = harness.call_readings(cell, make, seed, 0, [(x, int(steps))])["backward_error"]
        got[who + "_steps"] = int(steps)
    limit = CAP["backward_error"]["limit"]
    assert got["f64_steps"] >= 1, got
    assert got["f64"] <= limit < got["control"] / 100, got


def test_a_sound_run_is_correct_and_counts_its_steps():
    cell = small_cell()
    seed = 2**33 + 5
    devices = jax.devices()[:1]
    call = cell.entry.build(cell.traffic, devices)
    make = harness.input_maker(cell, seed, cell.entry.shardings(cell.traffic, devices))
    calls, t0, t1 = harness.run_window(cell, call, make, 0.2, False)
    assert calls and all(c.ok for c in calls)
    assert harness.passes(harness.check(cell, calls, make, seed))
    view = harness.RunView(cell, 1, {}, calls, t1 - t0, 0.0, 0, 0, None)
    steps = harness.metric_reader("refine_iters").read(view)
    assert steps >= 1 and steps == np.mean([c.x[0][1] for c in calls])


def test_refine_iters_reads_nothing_without_step_counts():
    view = harness.RunView(small_cell(), 1, {}, [harness.CallRecord(0, 0.0, 1.0, 1.0, 1.0, True,
                                                                    [np.zeros(4)])],
                           1.0, 0.0, 0, 0, None)
    assert harness.metric_reader("refine_iters").read(view) is None
