"""Square solves A X = B, as the harness drives them: a call's inputs, its
problems, its least bytes and the numbers its check compares.

A call solves one problem, or ``batch`` of them where the traffic sets
it; each problem is an n x n operator (``configs/<config>.json`` names
its generator under ``operands/``) and n x nrhs right-hand sides drawn
standard normal.  An operation module (``operations/<op>.py``) takes
these pieces and adds its own flop count.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import residual


def problems(traffic) -> int:
    """Solves in one call."""
    return int(traffic.get("batch") or 1)


def call_bytes(traffic, itemsize: int) -> float:
    """Least bytes: read A and B once and write X once, for every problem."""
    n, nrhs = traffic["n"], traffic["nrhs"]
    return problems(traffic) * float(itemsize) * (n * n + 2 * n * nrhs)


def input_body(traffic, operand, dtype):
    """(key, call index, operator index) -> the call's (A, B), or tuples of
    them for a batch; traced inside jit.  A comes from the operator index
    and B from the call index, so a call that repeats an earlier operator
    gets the same A and new right-hand sides."""
    import jax

    n, nrhs, count = int(traffic["n"]), int(traffic["nrhs"]), problems(traffic)
    batch = bool(traffic.get("batch"))

    def body(key, index, operator):
        a_keys = jax.random.split(jax.random.fold_in(key, operator), 2 * count)
        b_keys = jax.random.split(jax.random.fold_in(key, index), 2 * count)
        a = tuple(operand.make(a_keys[2 * j], n, dtype) for j in range(count))
        b = tuple(jax.random.normal(b_keys[2 * j + 1], (n, nrhs), dtype) for j in range(count))
        return (a, b) if batch else (a[0], b[0])

    return body


def per_problem(traffic, inputs):
    """The (A, B) of each problem of a call."""
    a, b = inputs
    return list(zip(a, b)) if traffic.get("batch") else [(a, b)]


def outputs(traffic, x):
    """The host solution of each problem of a call."""
    return list(x) if traffic.get("batch") else [x]


@functools.cache
def _rows_and_norm():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, rows: (a[rows], jnp.max(jnp.sum(jnp.abs(a), axis=1))))


def readings(traffic, inputs, xs, seed: int, index: int) -> dict:
    """The worst float64 backward error over the problems of call
    ``index``, whose inputs (made again from the seed) are ``inputs`` and
    whose host solutions are ``xs``."""
    n = int(traffic["n"])
    errs = []
    for j, (aj, bj) in enumerate(per_problem(traffic, inputs)):
        rows = residual.sample_rows(n, [seed % 2**64, index, j])
        a_rows, a_norm = _rows_and_norm()(aj, rows)
        b_rows = np.asarray(bj)[rows]
        errs.append(residual.solve_error(np.asarray(a_rows), b_rows, xs[j], float(a_norm), n))
    return {"backward_error": max(errs)}
