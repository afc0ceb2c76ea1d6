"""HPL's operand, uniform in [-0.5, 0.5], copied from the repository's
``chip_smoke._general``."""

import jax


def make(key, n: int, dtype):
    return jax.random.uniform(key, (n, n), dtype, -0.5, 0.5)
