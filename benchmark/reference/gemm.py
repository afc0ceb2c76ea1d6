"""Plain reference of DGEMM.  It imports nothing of the program.

- ``multiply_f64``: ``jnp.matmul`` in float64 at HIGHEST, XLA's own
  float64 product (float32 pairs on a TPU), which shares nothing with the
  program's int8 Ozaki path;
- ``multiply_plain``, the control: the operands rounded to float32,
  multiplied at HIGHEST and the product widened, the nearest precision
  below the configuration's float64.  ``solve_plain`` is the name
  ``benchmark/control.py`` calls it by.
"""

import jax.numpy as jnp
from jax import lax


def multiply_f64(a, b):
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def multiply_plain(a, b):
    c = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    return c.astype(a.dtype)


solve_plain = multiply_plain
