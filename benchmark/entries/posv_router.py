"""Batches of SPD solves through the serving Router
(``api.serve_router().solve_batch``) on one chip: admission, binning,
stacking, the executable cache and unstacking on the host, one stacked
program on the device.  A request the Router does not serve raises, so
a call that returns served every request in it."""

from jax.sharding import SingleDeviceSharding


def shardings(traffic, devices):
    one = SingleDeviceSharding(devices[0])
    return one, one


def build(traffic, devices):
    from slate_tpu import api

    router = api.serve_router()
    return lambda a, b: (router.solve_batch(
        [("posv", ai, bi) for ai, bi in zip(a, b)]), None)
