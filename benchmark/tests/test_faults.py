"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (``harness.run``: set-up, window,
check, result) on the CPU at a small size, with the chip check skipped,
and breaks the entry: a solve that returns its input unchanged, half of
a batch left out, one answer altered where it is produced, the exchange
between chips left out, and the plain reference in three bfloat16 passes
(the control) in the program's place.  A sound run of each path comes
out correct.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, harness

# 25 float32 eps, the reference testers' gate: the limit at these small
# sizes, where a sound solve reads 1e-11 to 1e-9
LIMIT = {"backward_error": {"limit": 25 * float(np.finfo(np.float32).eps)}}

PATHS = {
    "posv_api": ("spd-f32", {"path": "api", "n": 256, "nrhs": 4}, 1),
    "gesv_api": ("hpl-f32", {"path": "api", "n": 256, "nrhs": 4}, 1),
    "gesv_mesh": ("hpl-f32", {"path": "mesh", "n": 128, "nrhs": 4, "nb": 16, "grid": [2, 2]}, 4),
    "posv_router": ("spd-f32", {"path": "router", "n": 128, "nrhs": 4, "batch": 4}, 1),
}


def small_cell(path: str, limits=LIMIT) -> harness.Cell:
    config, traffic, chips = PATHS[path]
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/{config}.json")
    return harness.make_cell(f"test-{path}", chips, cfg, traffic, limits)


def run(cell: harness.Cell, seed: int = 2**31 + 7) -> dict:
    spec = harness.load_spec()
    devices = jax.devices()[: cell.chips]
    return harness.run(cell, seed, 0.3, False, devices, harness.peaks_for("TPU v5 lite"),
                       spec, time.perf_counter())


class BrokenEntry:
    """The cell's entry with its output changed by ``fault(cell, a, b, x)``."""

    def __init__(self, entry, fault, cell):
        self.entry, self.fault, self.cell = entry, fault, cell

    def shardings(self, traffic, devices):
        return self.entry.shardings(traffic, devices)

    def build(self, traffic, devices):
        call = self.entry.build(traffic, devices)

        def broken(a, b):
            x, info = call(a, b)
            return self.fault(self.cell, a, b, x), info

        return broken


def unchanged(cell, a, b, x):
    """The solve hands back its input."""
    return list(b) if cell.traffic.get("batch") else b


def half_left_out(cell, a, b, x):
    """Half of the batch is not solved; it repeats the other half's answers
    (one call's right-hand sides are its batch where it has one problem)."""
    if cell.traffic.get("batch"):
        half = len(x) // 2
        return list(x[:half]) * 2
    half = x.shape[1] // 2
    return jnp.concatenate([x[:, :half], x[:, :half]], axis=1)


def altered(cell, a, b, x):
    """One answer is changed where it is produced."""
    def bump(xi):
        return xi.at[0, 0].add(10 * jnp.max(jnp.abs(xi)))
    return [bump(x[0])] + list(x[1:]) if cell.traffic.get("batch") else bump(x)


def broken(path, fault):
    cell = small_cell(path)
    cell.entry = BrokenEntry(cell.entry, fault, cell)
    return cell


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_sound_run_is_correct(path):
    res = run(small_cell(path))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["backward_error"]["value"] < LIMIT["backward_error"]["limit"]


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered])
def test_a_broken_answer_is_not_correct(path, fault):
    res = run(broken(path, fault))
    assert not res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_the_exchange_between_chips_left_out_is_not_correct(monkeypatch):
    """Every reduction and permutation across the mesh returns what the
    chip already held."""
    for name in ("psum", "pmax", "pmin", "ppermute"):
        monkeypatch.setattr(jax.lax, name, lambda x, *args, **kw: x)
    jax.clear_caches()
    try:
        res = run(small_cell("gesv_mesh"))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("path", sorted(PATHS))
def test_the_control_in_the_programs_place_is_not_correct(path):
    """The plain reference in three bfloat16 passes reads at least three
    times the program's worst backward error on the same inputs, and with
    a limit set between the two readings by the committed limits' rule
    (lower^(1/3) upper^(2/3)) the run that serves it comes out not
    correct, while the program's own run comes out correct."""
    cell = small_cell(path)
    devices = jax.devices()[: cell.chips]
    seeds = [11, 2**31 + 3, 4_000_000_007]
    got = {"program": [], "control": []}
    for rec in control.readings(cell, devices, seeds, 1, True):
        got[rec["who"]].append(rec["backward_error"])
    lower, upper = max(got["program"]), min(got["control"])
    assert upper >= 3 * lower, got
    limits = {"backward_error": {"limit": float(lower ** (1 / 3) * upper ** (2 / 3))}}
    cell = small_cell(path, limits)
    solve = jax.jit(cell.reference.solve_plain)
    entry, batch = cell.entry, cell.traffic.get("batch")

    class Control:
        def shardings(self, traffic, devices):
            return entry.shardings(traffic, devices)

        def build(self, traffic, devices):
            def call(a, b):
                if batch:
                    return [solve(ai, bi) for ai, bi in zip(a, b)], None
                return solve(a, b), None
            return call

    cell.entry = Control()
    assert not run(cell)["correct"]
    assert run(small_cell(path, limits))["correct"]
