"""SPD solve through ``api.chol_solve`` on one chip, compiled whole with
``jax.jit`` as a user of a JAX library calls it."""

import jax
from jax.sharding import SingleDeviceSharding


def shardings(traffic, devices):
    one = SingleDeviceSharding(devices[0])
    return one, one


def build(traffic, devices):
    from slate_tpu import api

    return jax.jit(api.chol_solve)  # (x, info)
