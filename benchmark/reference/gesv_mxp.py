"""Plain reference of HPL-MxP's solve: a right-looking blocked LU without
pivoting in a ``fori_loop`` with static shapes, in float32, whose trailing
updates multiply bfloat16 inputs and accumulate in float32, then classic
iterative refinement.  It imports nothing of the program.

The panel is the diagonal block's unblocked LU, then L21 = A21 U11^-1 and
U12 = L11^-1 A12 against the block's inverses in three bfloat16 passes
(``plaindot.dot``); the trailing block columns are updated one at a time,
so no matrix-sized temporary sits beside the factor.  Each refinement step
solves with the factor (``plaindot.forward`` / ``backward``) and computes
the residual b - A x:

- ``solve_plain``, the control, computes it in float32, the nearest
  precision below the float64 the configuration states;
- ``solve_f64`` computes it in float64, as HPL-MxP asks.

Both stop on HPL's test ||b - A x||_inf <= 16 u n ||A||_inf ||x||_inf (u
the residual's unit roundoff) or after ``MAX_STEPS`` steps, and return
(x in b's dtype, steps)."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from benchmark.plaindot import backward, block_size, dot, forward, inv_lower, inv_upper, pad

MAX_STEPS = 30


def diag_lu(d):
    """Unblocked LU without pivoting of a small square block, in place."""
    m = d.shape[0]
    idx = jnp.arange(m)

    def column(j, d):
        lcol = jnp.where(idx > j, d[:, j] / d[j, j], 0)
        d = d.at[:, j].set(jnp.where(idx > j, lcol, d[:, j]))
        return d - jnp.outer(lcol, jnp.where(idx > j, d[j], 0))

    return lax.fori_loop(0, m, column, d)


def bf16_dot(x, y):
    return jnp.matmul(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def lu_nopiv(a, nb: int):
    """Packed L\\U of a float32 array whose size is a multiple of nb."""
    np_ = a.shape[0]
    idx = jnp.arange(np_)
    rows, cols = idx[:, None], idx[None, :]

    def step(k, a):
        k0 = k * nb
        col = lax.dynamic_slice(a, (0, k0), (np_, nb))
        d = diag_lu(lax.dynamic_slice(col, (k0, 0), (nb, nb)))
        l21 = jnp.where(rows >= k0 + nb, dot(col, inv_upper(jnp.triu(d))), 0)
        dcol = lax.dynamic_update_slice(jnp.zeros_like(col), d, (k0, 0))
        a = lax.dynamic_update_slice(
            a, jnp.where(rows >= k0 + nb, l21, jnp.where(rows >= k0, dcol, col)), (0, k0))
        brow = lax.dynamic_slice(a, (k0, 0), (nb, np_))
        urow = jnp.where(cols >= k0 + nb, dot(inv_lower(d, True), brow), 0)
        a = lax.dynamic_update_slice(a, jnp.where(cols >= k0 + nb, urow, brow), (k0, 0))

        def update(j, a):  # one block column at a time
            j0 = j * nb
            cur = lax.dynamic_slice(a, (0, j0), (np_, nb))
            ublk = lax.dynamic_slice(urow, (0, j0), (nb, nb))
            return lax.dynamic_update_slice(a, cur - bf16_dot(l21, ublk), (0, j0))

        return lax.fori_loop(k + 1, np_ // nb, update, a)

    return lax.fori_loop(0, np_ // nb, step, a)


def refine(a, b, residual_dtype):
    """Classic refinement on the no-pivot LU, the residual in
    ``residual_dtype``: (x, steps)."""
    n = a.shape[0]
    nb = block_size(n)
    lu, _ = pad(a.astype(jnp.float32), b, nb)
    lu = lu_nopiv(lu, nb)
    a_r, b_r = a.astype(residual_dtype), b.astype(residual_dtype)
    cte = 16 * (jnp.finfo(residual_dtype).eps / 2) * n * jnp.max(jnp.sum(jnp.abs(a_r), axis=1))

    def correction(r):
        rp = jnp.pad(r.astype(jnp.float32), ((0, lu.shape[0] - n), (0, 0)))
        return backward(lu, forward(lu, rp, nb, unit=True), nb)[:n].astype(residual_dtype)

    def residual(x):
        return b_r - jnp.matmul(a_r, x, precision=lax.Precision.HIGHEST)

    def more(c):
        x, r, steps = c
        return (steps < MAX_STEPS) & (jnp.max(jnp.abs(r)) > cte * jnp.max(jnp.abs(x)))

    def step(c):
        x, r, steps = c
        x = x + correction(r)
        return x, residual(x), steps + 1

    x0 = correction(b_r)
    x, _, steps = lax.while_loop(more, step, (x0, residual(x0), jnp.int32(0)))
    return x.astype(b.dtype), steps


def solve_plain(a, b):
    return refine(a, b, jnp.float32)


def solve_f64(a, b):
    return refine(a, b, jnp.float64)
