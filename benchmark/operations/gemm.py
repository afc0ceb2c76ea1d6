"""General matrix multiply C = A B (BLAS gemm, ScaLAPACK pdgemm) as the
harness drives it, with SLATE's gemm tester's check.

A call multiplies a new m x k A by a new k x n B, both made on the device
by the configuration's operand generator (``operands/<kind>.py``) from
separate keys of (seed, call index).  The work is LAWN 41's 2 m n k; the
least bytes read A and B once and write C once.

The entry answers (C, S): the product, as its float32 pair (see
``entries/gemm_api.py``), and the slice count the int8 Ozaki path ran (0
where the product took another path).  C comes to the host whole;
``outputs`` keeps ``SAMPLE_ROWS`` of its rows, drawn by this module's own
generator after the answer has arrived, so the program cannot know which
rows are read.  The check is the tester's

    ||C_S - A_S B||_inf / ((sqrt(k) + 2) ||A_S||_inf ||B||_inf)

over those rows S, in float64 on the host.  Every entry of B enters every
sampled row.  B comes to the host as three float32 words whose sum is B
exactly on any platform (a float64 copy off a TPU is slow, see the
entry).
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark import residual

SAMPLE_ROWS = 64
_DRAW = np.random.default_rng(0x67656D6D)  # the rows of C kept, one draw per answer


def _dims(traffic):
    return int(traffic["m"]), int(traffic["k"]), int(traffic["n"])


def problems(traffic) -> int:
    return 1


def call_flops(traffic) -> float:
    m, k, n = _dims(traffic)
    return 2.0 * m * n * k


def call_bytes(traffic, itemsize: int) -> float:
    m, k, n = _dims(traffic)
    return float(itemsize) * (m * k + k * n + m * n)


def int8_ops(traffic, n_slices: int) -> float:
    """int8 operations of the Ozaki products at ``n_slices`` slices: the
    digit pairs t + u < S, S(S+1)/2 unit products of 2 m n k each."""
    return n_slices * (n_slices + 1) / 2 * call_flops(traffic)


def input_body(traffic, operand, dtype):
    """(key, call index, operator index) -> the call's (A, B), traced inside
    jit.  Both come from the operator index, A and B from separate keys."""
    import jax

    m, k, n = _dims(traffic)

    def body(key, index, operator):
        ka, kb = jax.random.split(jax.random.fold_in(key, operator))
        return operand.make(ka, (m, k), dtype), operand.make(kb, (k, n), dtype)

    return body


def per_problem(traffic, inputs):
    """The (A, B) of the call's one product."""
    return [inputs]


def outputs(traffic, x):
    """[(row indices, those rows of C, slice count)]: C's rows drawn here,
    C added up from its float32 pair."""
    (hi, lo), count = x
    rows = np.sort(_DRAW.choice(hi.shape[0], size=min(SAMPLE_ROWS, hi.shape[0]), replace=False))
    return [(rows, hi[rows].astype(np.float64) + lo[rows].astype(np.float64), int(count))]


def slice_counts(xs):
    """The slice counts a call's answers carry, where the int8 path ran."""
    return [ans[2] for ans in xs or [] if isinstance(ans, tuple) and ans[2] > 0]


@functools.cache
def _take_rows():
    import jax

    return jax.jit(lambda a, rows: a[rows])


@functools.cache
def _words():
    """float64 -> three float32 words that add up to it exactly."""
    import jax
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.float32)
        rest = x - hi.astype(x.dtype)
        mid = rest.astype(jnp.float32)
        return hi, mid, (rest - mid.astype(x.dtype)).astype(jnp.float32)

    return jax.jit(split)


def to_host(x) -> np.ndarray:
    """A float64 device array on the host, exactly, through its words."""
    import jax

    hi, mid, lo = jax.device_get(_words()(x))
    out = hi.astype(np.float64)
    out += mid
    if lo.any():  # a TPU's float64 fits two words
        out += lo
    return out


def _sampled(x, seed: int, index: int):
    """(rows, C's rows) of an answer: kept by ``outputs``, or drawn here
    from a whole product (the control's, ``benchmark/control.py``)."""
    if isinstance(x, tuple):
        return x[0], x[1]
    c = np.asarray(x, np.float64)
    rows = residual.sample_rows(c.shape[0], [seed % 2**64, index], SAMPLE_ROWS)
    return rows, c[rows]


def gemm_error(a_rows, b, c_rows) -> float:
    """The tester's normwise error over the sampled rows, in float64."""
    a_rows, b = np.asarray(a_rows, np.float64), np.asarray(b, np.float64)
    r = np.asarray(c_rows, np.float64) - a_rows @ b
    denom = (np.sqrt(b.shape[0]) + 2.0) * residual.norm_inf(a_rows) * residual.norm_inf(b)
    if not np.isfinite(denom) or denom == 0.0:
        return float("inf")
    return residual.norm_inf(r) / denom


def readings(traffic, inputs, xs, seed: int, index: int) -> dict:
    """The tester's error of call ``index``, whose inputs (made again from
    the seed) are ``inputs`` and whose host answer is ``xs``."""
    import jax

    a, b = inputs
    rows, c_rows = _sampled(xs[0], seed, index)
    a_rows = jax.device_get(_take_rows()(a, rows))
    return {"backward_error": gemm_error(a_rows, to_host(b), c_rows)}
