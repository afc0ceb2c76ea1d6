"""General solve with partial pivoting through ``parallel.gesv_mesh`` on a
P x Q mesh of chips, block size NB, called eagerly as the driver is
written to be called.  A is born split over the mesh, B replicated."""

from jax.sharding import NamedSharding, PartitionSpec


def _mesh(traffic, devices):
    from slate_tpu.parallel import make_mesh

    p, q = traffic["grid"]
    return make_mesh(p, q, devices=devices[: p * q])


def shardings(traffic, devices):
    from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

    mesh = _mesh(traffic, devices)
    return (NamedSharding(mesh, PartitionSpec(ROW_AXIS, COL_AXIS)),
            NamedSharding(mesh, PartitionSpec()))


def build(traffic, devices):
    from slate_tpu.parallel import gesv_mesh

    mesh, nb = _mesh(traffic, devices), traffic["nb"]
    return lambda a, b: gesv_mesh(a, b, mesh, nb=nb)  # (x, info)
