"""HPL-MxP's solve through ``api.lu_solve_mixed`` on one chip, compiled
whole with ``jax.jit``: the float32 LU without pivoting, its trailing
updates with bfloat16 products accumulated in float32
(``Precision.Fast``), preconditions float64 GMRES-IR.  The call returns
((x, GMRES steps), info).

A float64 user of JAX turns 64-bit mode on before making any array, and
so does this module, as it is imported: without it ``float64`` silently
makes float32.  Each cell runs in a process of its own."""

import jax
from jax.sharding import SingleDeviceSharding

jax.config.update("jax_enable_x64", True)


def shardings(traffic, devices):
    one = SingleDeviceSharding(devices[0])
    return one, one


def build(traffic, devices):
    from slate_tpu import api
    from slate_tpu.types import MethodLU, Option, Precision

    mixed = api.lu_solve_mixed
    opts = {Option.MethodLU: MethodLU.NoPiv, Option.Precision: Precision.Fast}
    solve = jax.jit(lambda a, b: mixed(a, b, opts))

    def call(a, b):
        res = solve(a, b)
        return (res.x, res.iters), res.info

    return call
