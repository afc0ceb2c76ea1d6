"""Wigner-shifted SPD operand, 3 I + (G + G^T) / (2 sqrt n), copied from
the repository's ``chip_smoke._spd``: the spectrum sits in
[3 - sqrt 2, 3 + sqrt 2], with no Gram product."""

import jax
import jax.numpy as jnp
import numpy as np


def make(key, n: int, dtype):
    g = jax.random.normal(key, (n, n), dtype)
    return (g + g.T) / jnp.asarray(2.0 * np.sqrt(n), dtype) + 3 * jnp.eye(n, dtype=dtype)
