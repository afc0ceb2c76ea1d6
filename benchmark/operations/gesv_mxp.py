"""HPL-MxP's solve: A and b in float64, any precision for the factor, the
answer brought to HPL's float64 check.  The work counted is HPL's, LAWN
41's getrf and getrs flops for one right-hand side (2/3 n^3 + 3/2 n^2 to
O(n^2)), whatever precision did it; the least bytes read the float64 A
once.  Inputs and the check are the square solve's (``benchmark/solve.py``).

A call's output is (x, GMRES steps): each problem's host answer keeps its
step count beside x, for ``metrics/refine_iters.py``."""

from benchmark import flops, solve
from benchmark.solve import call_bytes, input_body, per_problem, problems  # noqa: F401


def call_flops(traffic) -> float:
    return problems(traffic) * flops.gesv(traffic["n"], traffic["nrhs"])


def outputs(traffic, x):
    """[(host solution, steps)]: one problem per call."""
    sol, steps = x
    return [(sol, int(steps))]


def readings(traffic, inputs, xs, seed: int, index: int) -> dict:
    return solve.readings(traffic, inputs, [sol for sol, _ in xs], seed, index)
