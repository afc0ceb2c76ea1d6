"""Compile the default TPU path's Pallas kernels for a described v5e.

No chip is attached: ``jax.experimental.topologies`` describes a v5e and
the TPU compiler (Mosaic for the kernels) runs here, so a kernel that
interpret mode accepts but the chip's compiler refuses fails in tier-1
instead of on the chip.  Real widths: nb = 256, an 8 x 8 local trailing
grid.  ``_interpret()`` is steered inside each test; the topology is described in a module-scoped fixture, never at
import time (only one process may load libtpu, and every xdist worker
imports this file).  A failure to describe it fails the tests: the
installed libtpu can always describe a v5e.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from slate_tpu.ops import pallas_ops as po
from slate_tpu.ops.matmul import matmul_pallas

NB = 256
TRAIL = 8  # local trailing tile grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Trace the TPU branches: kernels go to Mosaic, auto resolves as on
    the chip.  The persistent cache is off around these compiles (an
    entry compiled for a described chip cannot be read back here)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(po, "_interpret", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _trailing_args(shape, mask=True):
    view = shape((TRAIL, TRAIL, NB, NB))
    pan = shape((TRAIL, NB, NB))
    rest = (shape((TRAIL, TRAIL), jnp.bool_),) if mask else ()
    return (view, pan, pan) + rest


DEFAULT_PATH_KERNELS = {
    "summa_update": (po.summa_update_pallas, lambda s: _trailing_args(s, mask=False)),
    "chol_trailing_update": (po.chol_trailing_update_pallas, _trailing_args),
    "lu_trailing_update": (po.lu_trailing_update_pallas, _trailing_args),
    "transpose": (po.transpose_pallas, lambda s: (s((TRAIL, NB, NB)),)),
    "geadd": (
        lambda a, b: po.geadd_pallas(2.0, a, 0.5, b),
        lambda s: (s((TRAIL, NB, NB)), s((TRAIL, NB, NB))),
    ),
    "genorm_max": (po.genorm_max_pallas, lambda s: (s((TRAIL, NB, NB)),)),
    "matmul": (matmul_pallas, lambda s: (s((2048, 2048)), s((2048, 2048)))),
}


@pytest.mark.parametrize("name", sorted(DEFAULT_PATH_KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, on_tpu):
    fn, make_args = DEFAULT_PATH_KERNELS[name]

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(fn).lower(*make_args(shape)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_auto_resolves_as_on_the_chip(on_tpu):
    """auto: trailing updates take the kernels above within the VMEM
    cap, and fall back to XLA past it."""
    assert po.update_active_impl() == "pallas"
    assert po.update_engaged(jnp.float32, 2 * TRAIL * NB * NB * 4)
    assert not po.update_engaged(jnp.float32, po._UPDATE_VMEM_CAP + 1)


@pytest.mark.parametrize("driver,fused", [("posv_mesh", True), ("gesv_mesh", False)])
def test_mesh_solve_compiles_for_2x2(driver, fused, topo, on_tpu):
    """The public mesh solves compile for a described 2x2 v5e mesh with
    x64 on (as the test suite and every f64 user runs): the pmin'd info
    scalars must stay 32-bit.  At this local grid the Cholesky trailing
    update takes the fused kernel inside shard_map; the partial-pivot LU
    has no fused update, so its program holds no Mosaic kernel."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from slate_tpu import parallel
    from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

    assert jax.config.jax_enable_x64
    mesh = parallel.make_mesh(2, 2, devices=topo.devices)
    n = 4 * NB
    a = jax.ShapeDtypeStruct((n, n), jnp.float32,
                             sharding=NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
    b = jax.ShapeDtypeStruct((n, 4), jnp.float32, sharding=NamedSharding(mesh, P()))
    solve = getattr(parallel, driver)
    compiled = jax.jit(lambda a, b: solve(a, b, mesh, nb=NB)).lower(a, b).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == fused


def test_pp_panel_slab_row_major_for_v5e(topo, on_tpu):
    """The mesh LU's pivoted panel on a described 2x2 v5e, as
    ``gesv_mesh`` runs it: every column loop carries its sub-block as a
    row-major f32[ib, rows] slab (rows on the lanes, so a narrow slab
    pads nothing) and copies nothing larger than one slab column, and no
    panel-sized array is copied anywhere in the program."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from slate_tpu import parallel
    from slate_tpu.parallel.dist_lu import _pp_jit, _pp_sub_width
    from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

    mesh = parallel.make_mesh(2, 2, devices=topo.devices)
    nt = 4
    rows, ib = nt // 2 * NB, _pp_sub_width(NB)
    t = jax.ShapeDtypeStruct((nt, nt, NB, NB), jnp.float32,
                             sharding=NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
    hlo = _pp_jit.lower(t, mesh, 2, 2, nt, nt * NB, 1, "auto").compile().as_text()
    slab = f"f32[{ib},{rows}]{{1,0"
    loops = re.findall(r"= \((.*?)\) while\(.*?body=%?([\w.\-]+)", hlo)
    col_loops = [body for carry, body in loops if slab in carry]
    assert len(col_loops) == NB // ib, loops

    def copied(text):  # shapes of the f32 copies in ``text``
        return re.findall(r"= (f32\[[\d,]*\])\{\S* copy(?:-start)?\(", text)

    assert not {f"f32[{rows},{NB}]", f"f32[{NB},{rows}]"} & set(copied(hlo))
    for body in col_loops:
        text = hlo.split(f"\n%{body} ", 1)[1].split("\n}", 1)[0]
        assert set(copied(text)) <= {f"f32[{ib},1]"}, body  # one slab column


@pytest.mark.parametrize("la", [0, 1])
def test_pp_loop_copies_no_local_matrix_for_v5e(la, topo, on_tpu):
    """The mesh LU's k-loop on a described 2x2 v5e, as ``gesv_mesh``
    runs it (lookahead 1) and at lookahead 0: its body copies nothing as
    large as the local matrix.  Carried as a tile stack, each step
    converted the whole stack to the trailing product's layout, to the
    row swap's and back (three copies of the local matrix per step)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from conftest import loop_copies_at_least
    from slate_tpu import parallel
    from slate_tpu.parallel.dist_lu import _pp_jit
    from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

    mesh = parallel.make_mesh(2, 2, devices=topo.devices)
    nt = 16
    t = jax.ShapeDtypeStruct((nt, nt, NB, NB), jnp.float32,
                             sharding=NamedSharding(mesh, P(ROW_AXIS, COL_AXIS)))
    hlo = _pp_jit.lower(t, mesh, 2, 2, nt, nt * NB, la, "auto").compile().as_text()
    local = (nt // 2 * NB) ** 2
    copies = loop_copies_at_least(hlo, local)
    assert len(copies) == 1, copies  # the k-loop, the one loop carrying the matrix
    assert not any(copies.values()), copies


def test_potrf_scan_carry_in_place_for_v5e(one_chip, on_tpu):
    """The scanned Cholesky's loop carry stays in place on a TPU: no
    whole-view copy in any bucket's loop body.  Left to layout
    assignment, a TPU carries the view column-major, as the panel
    column's ops prefer, and converts it to and from the update's
    row-major output every k-step.  The input is symmetrized as
    ``potrf_array`` gives it; a small n shows the same layout choice as
    n = 30720."""
    from conftest import loop_view_copies
    from slate_tpu.core.matrix import symmetrize
    from slate_tpu.linalg.chol import _potrf_scan
    from slate_tpu.types import Uplo

    n, nb = 1024, 128
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    factor = lambda a: _potrf_scan(symmetrize(a, Uplo.Lower, conj=False), nb=nb)
    copies = loop_view_copies(jax.jit(factor).lower(a).compile().as_text(), min_dim=2 * nb)
    assert sorted(copies) == [256, 512, 768, 1024], copies
    assert not any(copies.values()), copies


@pytest.mark.parametrize("dtype", [jnp.complex64, jnp.float64])
def test_potrf_scan_64bit_elements_compile_for_v5e(dtype, one_chip, on_tpu):
    """A TPU rewrites 64-bit elements into 32-bit pairs, and that rewrite
    refuses a layout constraint: the scanned Cholesky pins only 32-bit
    carries, so complex64 (``potrf_array``'s path past n = 16384) and
    float64 still compile."""
    from slate_tpu.core.matrix import symmetrize
    from slate_tpu.linalg.chol import _potrf_scan
    from slate_tpu.types import Uplo

    cplx = jnp.issubdtype(dtype, jnp.complexfloating)
    a = jax.ShapeDtypeStruct((512, 512), dtype, sharding=one_chip)
    factor = lambda a: _potrf_scan(symmetrize(a, Uplo.Lower, conj=cplx), nb=128)
    assert "while" in jax.jit(factor).lower(a).compile().as_text()


@pytest.mark.parametrize("precision", ["fast", "highest"])
def test_getrf_nopiv_scan_carry_in_place_for_v5e(precision, one_chip, on_tpu):
    """The scanned LU without pivoting shares the Cholesky scan's step
    driver and order, and likewise keeps its carry in place on a TPU at
    either update tier: no whole-view copy in any bucket's loop body."""
    from conftest import loop_view_copies
    from slate_tpu.linalg.lu import _getrf_nopiv_scan
    from slate_tpu.types import Precision

    n, nb = 1024, 128
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    factor = lambda a: _getrf_nopiv_scan(a, nb=nb, precision=Precision(precision))
    copies = loop_view_copies(jax.jit(factor).lower(a).compile().as_text(), min_dim=2 * nb)
    assert sorted(copies) == [256, 512, 768, 1024], copies
    assert not any(copies.values()), copies


def test_lu_solve_mixed_compiles_for_v5e(one_chip, on_tpu):
    """HPL-MxP's solve compiles for a v5e with x64 on: the float64 GMRES
    loops, Givens rotations and row-block products lower there."""
    from slate_tpu import api
    from slate_tpu.types import MethodLU, Option, Precision

    assert jax.config.jax_enable_x64
    n = 1024
    a = jax.ShapeDtypeStruct((n, n), jnp.float64, sharding=one_chip)
    b = jax.ShapeDtypeStruct((n, 1), jnp.float64, sharding=one_chip)
    opts = {Option.MethodLU: MethodLU.NoPiv, Option.Precision: Precision.Fast}
    compiled = jax.jit(lambda a, b: api.lu_solve_mixed(a, b, opts)).lower(a, b).compile()
    assert "while" in compiled.as_text()
