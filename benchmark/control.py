#!/usr/bin/env python3
"""Readings that a cell's limits are set from, at the cell's own size:

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 [--calls 1]
                                 [--program 1]

For each seed and each of the first ``--calls`` call indices, the inputs
are made exactly as a run makes them.  ``--program 1`` solves them with
the cell's entry (the program's readings, the lower end of a limit).
The plain reference (``reference/<op>.py``, every product in three
bfloat16 passes, the nearest precision below the configurations'
float32 at HIGHEST) solves them in the program's place: the control,
the upper end.  Every answer is judged by the same numbers as a run's
(``operations/<op>.py``).  One JSON line per reading, then a summary
line with each number's largest and smallest reading beside the cell's
limit.  Runs only on a TPU, like a run.
"""

import os
import sys
import time

sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import argparse  # noqa: E402

from benchmark import harness  # noqa: E402


def control_solver(cell: harness.Cell):
    """(seed, call index) -> the plain reference's answers, one per
    problem, with the call's inputs made in the same program from the
    seed (no second copy of A sits beside the factor)."""
    import jax
    import jax.numpy as jnp

    op, traffic = cell.operation, cell.traffic
    body = op.input_body(traffic, cell.operand, cell.dtype)

    def solve(key, index, operator):
        return tuple(cell.reference.solve_plain(*args)
                     for args in op.per_problem(traffic, body(key, index, operator)))

    run = jax.jit(solve)

    def answers(seed, index):
        operator = harness.operator_index(traffic, seed, index)
        return [jax.device_get(x) for x in
                run(harness.base_key(seed), jnp.uint32(index), jnp.uint32(operator))]

    return answers


def readings(cell, devices, seeds, calls, program: bool):
    shardings = cell.entry.shardings(cell.traffic, devices) if program else None
    call = cell.entry.build(cell.traffic, devices) if program else None
    control = control_solver(cell)
    for seed in seeds:
        make = harness.input_maker(cell, seed, shardings)
        for index in range(calls):
            if program:
                inputs = make(index)
                xs, ok, secs = harness.one_call(cell, call, inputs, False)
                del inputs
                got = harness.call_readings(cell, make, seed, index, xs) if ok else {}
                yield {"who": "program", "seed": seed, "index": index, "ok": ok,
                       "seconds": secs, **got}
            t0 = time.perf_counter()
            xs = control(seed, index)
            secs = time.perf_counter() - t0
            yield {"who": "control", "seed": seed, "index": index, "seconds": secs,
                   **harness.call_readings(cell, make, seed, index, xs)}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description="Program and control readings for a cell's limits.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--calls", type=int, default=1)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        spec = harness.load_spec()
        cell = harness.load_cell(args.workload, spec)
        harness.guard_environment()
        devices = harness.chip_devices(cell.chips if args.program else 1)
        harness.enable_compile_cache()
        harness.guard_program()
    except harness.Refused as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    by_who = {}
    for rec in readings(cell, devices, seeds, args.calls, bool(args.program)):
        harness.emit(rec)
        by_who.setdefault(rec["who"], []).append(rec)
    summary = {"workload": cell.name, "seeds": len(seeds), "calls": args.calls}
    for who, recs in by_who.items():
        summary[who] = {"unsolved": sum(1 for r in recs if not r.get("ok", True))}
        for name, lim in cell.limits.items():
            got = [r[name] for r in recs if name in r]
            summary[who][name] = {"max": max(got) if got else None,
                                  "min": min(got) if got else None, "limit": lim["limit"]}
    harness.emit(summary)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
