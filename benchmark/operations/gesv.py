"""General solve by LU with partial pivoting (LAPACK gesv): LAWN 41's
getrf and getrs flops for every problem of a call.  Inputs, least bytes
and the check are the square solve's (``benchmark/solve.py``)."""

from benchmark import flops
from benchmark.solve import (call_bytes, input_body, outputs, per_problem,  # noqa: F401
                             problems, readings)


def call_flops(traffic) -> float:
    return problems(traffic) * flops.gesv(traffic["n"], traffic["nrhs"])
