"""Plain reference of the SPD solve: a right-looking blocked Cholesky in a
``fori_loop`` with static shapes, then two blocked triangular sweeps.
It imports nothing of the program.  Each step updates the whole matrix
with the panel's outer product; the panel is zero above the trailing
rows, so only the trailing block changes."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from benchmark.plaindot import backward, block_size, dot, forward, inv_lower, pad


def solve_plain(a, b):
    n = a.shape[0]
    nb = block_size(n)
    a, b = pad(a, b, nb)
    np_ = a.shape[0]
    rows = jnp.arange(np_)[:, None]

    def step(k, a):
        k0 = k * nb
        lkk = lax.linalg.cholesky(lax.dynamic_slice(a, (k0, k0), (nb, nb)))
        col = lax.dynamic_slice(a, (0, k0), (np_, nb))
        below = rows >= k0 + nb
        panel = jnp.where(below, dot(col, inv_lower(lkk, False).T), 0)
        col = lax.dynamic_update_slice(jnp.where(below, panel, col), lkk, (k0, 0))
        a = lax.dynamic_update_slice(a, col, (0, k0))
        return a - dot(panel, panel.T)

    l = jnp.tril(lax.fori_loop(0, np_ // nb, step, a))
    y = forward(l, b, nb, unit=False)
    return backward(l.T, y, nb)[:n]

