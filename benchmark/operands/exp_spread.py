"""The Ozaki-scheme literature's test operand, a = (u - 1/2) exp(phi g)
(Ozaki, Ogita, Oishi and Rump, Numer. Algorithms 59, 2012, and the int8
DGEMM papers since): u uniform in [0, 1) in the operand's float64, so
every entry carries a full 53-bit significand, and g standard normal, so
that phi sets how many binades a row spans.  ``PHI`` is
``configs/dgemm-f64.json``'s phi.  g and exp(phi g) are drawn in float32:
that factor sets each entry's scale only."""

import jax
import jax.numpy as jnp

PHI = 2.0


def make(key, shape, dtype, phi: float = PHI):
    ku, kg = jax.random.split(key)
    u = jax.random.uniform(ku, shape, dtype)
    g = jax.random.normal(kg, shape, jnp.float32)
    return (u - 0.5) * jnp.exp(phi * g).astype(dtype)
