"""Plain reference of the general solve with partial pivoting: a
right-looking blocked LU in a ``fori_loop`` with static shapes, then two
blocked triangular sweeps.  It imports nothing of the program.

The rows stay where they are in memory; ``order`` maps each row of the
factored matrix to the memory row that holds it, so that a row swap moves
no data.  Each step gathers the panel in that order, rolls it so that
its first unfactored row is on top, zeroes the factored rows (which then
never win a pivot search), factors the tall panel column by column with
partial pivoting, forms the block row of U and updates the trailing
block columns one at a time.  (XLA's own LU of a tall panel does not
compile for a v5e at these heights: it runs out of vector memory.)"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from benchmark.plaindot import backward, block_size, dot, forward, inv_lower, pad


def panel_lu(p):
    """Unblocked LU with partial pivoting of a tall (m, nb) panel, in
    float32 on the vector units.  Returns the factors in place and the
    row order: row r of the result is row ``order[r]`` of ``p``."""
    m, nb = p.shape
    rows, cols = jnp.arange(m), jnp.arange(nb)

    def column(j, carry):
        p, order = carry
        piv = jnp.argmax(jnp.where(rows >= j, jnp.abs(p[:, j]), -1.0))
        pj, pp = p[j], p[piv]
        p = p.at[j].set(pp).at[piv].set(pj)
        oj, op = order[j], order[piv]
        order = order.at[j].set(op).at[piv].set(oj)
        below = rows > j
        lcol = jnp.where(below, p[:, j] / p[j, j], 0)
        p = p.at[:, j].set(jnp.where(below, lcol, p[:, j]))
        return p - jnp.outer(lcol, jnp.where(cols > j, p[j], 0)), order

    return lax.fori_loop(0, nb, column, (p, rows))


def solve_plain(a, b):
    n = a.shape[0]
    nb = block_size(n)
    a, b = pad(a, b, nb)
    np_ = a.shape[0]
    idx = jnp.arange(np_)
    rows, cols = idx[:, None], idx[None, :]

    def to_memory(x, order):
        return jnp.zeros_like(x).at[order].set(x)

    def step(k, carry):
        a, order = carry
        k0 = k * nb
        col = lax.dynamic_slice(a, (0, k0), (np_, nb))[order]
        rolled = jnp.where(rows < np_ - k0, jnp.roll(col, -k0, axis=0), 0)
        lu, p = panel_lu(rolled)
        order = jnp.where(idx < k0, order, order[(p[(idx - k0) % np_] + k0) % np_])
        col = jnp.where(rows >= k0, jnp.roll(lu, k0, axis=0), col)
        a = lax.dynamic_update_slice(a, to_memory(col, order), (0, k0))
        block = lax.dynamic_slice(order, (k0,), (nb,))
        brow = a[block]
        right = cols >= k0 + nb
        urow = jnp.where(right, dot(inv_lower(lu[:nb], True), brow), 0)
        a = a.at[block].set(jnp.where(right, urow, brow))
        l21 = to_memory(jnp.where(rows >= k0 + nb, col, 0), order)

        def update(j, a):  # one block column at a time: no matrix-sized temporary
            j0 = j * nb
            cur = lax.dynamic_slice(a, (0, j0), (np_, nb))
            ublk = lax.dynamic_slice(urow, (0, j0), (nb, nb))
            return lax.dynamic_update_slice(a, cur - dot(l21, ublk), (0, j0))

        return lax.fori_loop(k + 1, np_ // nb, update, a), order

    a, order = lax.fori_loop(0, np_ // nb, step, (a, idx))
    y = forward(a, b[order], nb, unit=True, order=order)
    return backward(a, y, nb, order=order)[:n]

