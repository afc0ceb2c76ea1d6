"""HPL-MxP's operand: off-diagonal entries uniform in [-0.5, 0.5], as HPL's,
and each diagonal entry the sum of its row's off-diagonal magnitudes plus
one, so that A is strictly diagonally dominant by rows and LU without
pivoting is stable (HPL-MxP's rule; the exact diagonal of its reference
generator is not in this repository, see ``configs/hpl-mxp-f64.json``)."""

import jax
import jax.numpy as jnp


def make(key, n: int, dtype):
    a = jax.random.uniform(key, (n, n), dtype, -0.5, 0.5)
    i = jnp.arange(n)
    off = jnp.sum(jnp.abs(a), axis=1) - jnp.abs(a[i, i])
    return a.at[i, i].set(off + 1)
