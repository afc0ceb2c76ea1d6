"""Matrix products and blocked triangular sweeps for the plain references.

Every product that carries the O(n^3) and O(n^2 nrhs) work runs in three
bfloat16 passes (hi*hi + hi*lo + lo*hi), XLA's ``Precision.HIGH`` on a
TPU written out, so that it computes the same on any backend.  That is
the control: the nearest precision below the float32 at
``Precision.HIGHEST`` that the configurations state.

The diagonal blocks (nb x nb) are factored and inverted by XLA's own
Cholesky, LU and triangular solve; their work is a small share.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(x.dtype)).astype(jnp.bfloat16)


def dot(x, y):
    xh, xl = _split(x)
    yh, yl = _split(y)

    def mm(p, q):
        return jnp.matmul(p, q, preferred_element_type=jnp.float32).astype(x.dtype)

    return mm(xh, yh) + (mm(xh, yl) + mm(xl, yh))


def block_size(n: int) -> int:
    return 512 if n >= 4096 else 128


def pad(a, b, nb: int):
    """Pad A with an identity tail and B with zero rows to a multiple of nb."""
    n = a.shape[0]
    np_ = -(-n // nb) * nb
    if np_ == n:
        return a, b
    a = jnp.pad(a, ((0, np_ - n), (0, np_ - n)))
    tail = jnp.arange(n, np_)
    a = a.at[tail, tail].set(1)
    return a, jnp.pad(b, ((0, np_ - n), (0, 0)))


def inv_lower(t, unit: bool):
    eye = jnp.eye(t.shape[0], dtype=t.dtype)
    return lax.linalg.triangular_solve(t, eye, left_side=True, lower=True,
                                       unit_diagonal=unit)


def inv_upper(t):
    eye = jnp.eye(t.shape[0], dtype=t.dtype)
    return lax.linalg.triangular_solve(t, eye, left_side=True, lower=False)


def block_row(t, k0: int, nb: int, order=None):
    """Rows k0..k0+nb of T, or of T[order] where ``order`` is given."""
    if order is None:
        return lax.dynamic_slice(t, (k0, 0), (nb, t.shape[1]))
    return t[lax.dynamic_slice(order, (k0,), (nb,))]


def forward(l, b, nb: int, unit: bool, order=None):
    """Solve L Y = B by block rows; L lower (unit when ``unit``), read as
    L[order] where ``order`` is given."""
    nt = l.shape[0] // nb

    def step(k, y):
        k0 = k * nb
        lrow = block_row(l, k0, nb, order)
        lkk = lax.dynamic_slice(lrow, (0, k0), (nb, nb))
        r = lax.dynamic_slice(b, (k0, 0), (nb, b.shape[1])) - dot(lrow, y)
        return lax.dynamic_update_slice(y, dot(inv_lower(lkk, unit), r), (k0, 0))

    return lax.fori_loop(0, nt, step, jnp.zeros_like(b))


def backward(u, y, nb: int, order=None):
    """Solve U X = Y by block rows, last first; U upper, read as U[order]
    where ``order`` is given."""
    nt = u.shape[0] // nb

    def step(j, x):
        k0 = (nt - 1 - j) * nb
        urow = block_row(u, k0, nb, order)
        ukk = lax.dynamic_slice(urow, (0, k0), (nb, nb))
        r = lax.dynamic_slice(y, (k0, 0), (nb, y.shape[1])) - dot(urow, x)
        return lax.dynamic_update_slice(x, dot(inv_upper(ukk), r), (k0, 0))

    return lax.fori_loop(0, nt, step, jnp.zeros_like(y))
