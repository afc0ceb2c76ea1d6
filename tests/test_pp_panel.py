"""The mesh's partial-pivot panel, blocked in sub-blocks (``_pp_panel_factor``).

nb = 128 puts two 64-column sub-blocks in every panel, so each column
loop touches only its slab and the columns right of it take one product
per sub-block.  Every caller of the panel is checked against NumPy's
partial pivoting on a 2x2 mesh with n not a multiple of nb; the panel
alone is checked, at several sub-block widths, against its own unblocked
form (``ib == nb``); and the column loop is checked to hold nothing as
large as the panel.  The dense kernel's step on its local matrix is
checked against the same step on the tile stack, as the kernel carried
it before.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from slate_tpu.ft import ckpt
from slate_tpu.ops.pallas_ops import update_impl_scope
from slate_tpu.parallel import from_dense, make_mesh, to_dense
from slate_tpu.parallel import dist_lu
from slate_tpu.parallel.comm import local_indices, shard_map_compat
from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

from conftest import cpu_devices

N, NB = 300, 128  # 3 tile rows, padded to 4 on the 2x2 mesh
IB = dist_lu._pp_sub_width(NB)  # 64: two sub-blocks a panel
KL, KU = 40, 30


def _mesh():
    return make_mesh(2, 2, devices=cpu_devices(4))


def _numpy_pp_perm(a):
    """Row order of unblocked partial pivoting (LAPACK getf2) in NumPy."""
    a = np.array(a, dtype=np.float64)
    n = a.shape[0]
    perm = np.arange(n)
    for j in range(n):
        p = j + int(np.argmax(np.abs(a[j:, j])))
        a[[j, p]] = a[[p, j]]
        perm[[j, p]] = perm[[p, j]]
        a[j + 1:, j] /= a[j, j]
        a[j + 1:, j + 1:] -= np.outer(a[j + 1:, j], a[j, j + 1:])
    return perm


def _operand(kind):
    rng = np.random.default_rng(26)
    a = rng.standard_normal((N, N))
    if kind == "band":  # zero outside the band: the band kernel's operand
        i, j = np.indices((N, N))
        a = np.where((i - j <= KL) & (j - i <= KU), a, 0.0)
    return a


FACTORS = {
    "getrf_pp_la0": ("dense", lambda d: dist_lu.getrf_pp_dist(d, lookahead=0)),
    "getrf_pp_la1": ("dense", lambda d: dist_lu.getrf_pp_dist(d, lookahead=1)),
    "gbtrf_band": ("band", lambda d: dist_lu.gbtrf_band_dist(d, KL, KU)),
    "getrf_pp_ckpt": ("dense", lambda d: ckpt.getrf_pp_ckpt(d, every=2)),
}


@pytest.mark.parametrize("name", sorted(FACTORS))
def test_blocked_panel_factors_like_numpy_partial_pivoting(name):
    kind, factor = FACTORS[name]
    a = _operand(kind)
    lu, perm, info = factor(from_dense(jnp.asarray(a), _mesh(), NB, diag_pad_one=True))
    assert int(info) == 0
    perm = np.asarray(perm)
    np.testing.assert_array_equal(perm[:N], _numpy_pp_perm(a))
    lud = np.asarray(to_dense(lu))[:N, :N]
    l, u = np.tril(lud, -1) + np.eye(N), np.triu(lud)
    resid = np.abs(a[perm[:N]] - l @ u).max() / (N * np.abs(a).max())
    assert resid < 4 * np.finfo(np.float64).eps, resid
    assert np.abs(l).max() <= 1.0


def test_blocked_panel_checkpointed_is_bitwise_the_plain_factor():
    d = from_dense(jnp.asarray(_operand("dense")), _mesh(), NB, diag_pad_one=True)
    ref, got = dist_lu.getrf_pp_dist(d), ckpt.getrf_pp_ckpt(d, every=2)
    np.testing.assert_array_equal(np.asarray(to_dense(got[0])),
                                  np.asarray(to_dense(ref[0])))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]))


def _tile_stack_factor(d):
    """``getrf_pp_dist`` as its step ran on the tile stack (strict
    schedule): the shared panel and row swaps, then the no-pivot row
    solve and trailing einsum.  Returns (LU tiles, perm, info)."""
    def kernel(t):
        mtl, ntl, nb, _ = t.shape
        r, c, i_log, j_log = local_indices(2, 2, mtl, ntl)
        zero = jnp.zeros((), jnp.int32)

        def step(k, carry):
            t, rowperm = dist_lu._pp_panel_and_swaps(
                *carry, k, 2, 2, r, c, d.nt, d.m, zero, mtl, zero, ntl)
            t = dist_lu._nopiv_step(t, k, 2, 2, i_log, j_log, r, c, panel_done=True)
            return t, rowperm

        t, rowperm = lax.fori_loop(0, d.nt, step, (t, jnp.arange(d.nt * nb)))
        info = dist_lu._lu_info_dist(t, i_log, j_log, d.nt, nb)
        return t, rowperm[None], info[None, None]

    spec = P(ROW_AXIS, COL_AXIS)
    with update_impl_scope("xla"):
        lut, perm, info = jax.jit(shard_map_compat(
            kernel, mesh=d.mesh, in_specs=(spec,),
            out_specs=(spec, P(ROW_AXIS), spec), check_vma=False))(d.tiles)
    return lut, perm[0], jnp.max(info)


@pytest.mark.parametrize("la", [0, 1])
def test_local_matrix_step_matches_the_tile_stack_step(la):
    """The dense kernel carries its local tile stack as one row-major
    matrix: the same pivots and info as the tile-stack step, and packed
    LU factors within the rounding bound of a length-n dot,
    gamma_{n+1} (|PA| + |L| |U|), divided by |u_jj| below the diagonal."""
    a = _operand("dense")
    d = from_dense(jnp.asarray(a), _mesh(), NB, diag_pad_one=True)
    lu, perm, info = dist_lu.getrf_pp_dist(d, lookahead=la)
    ref_t, ref_perm, ref_info = _tile_stack_factor(d)
    assert int(info) == int(ref_info) == 0
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref_perm))
    got = np.asarray(to_dense(lu))[:N, :N]
    ref = np.asarray(to_dense(d.__class__(
        tiles=ref_t, m=d.m, n=d.n, nb=NB, mesh=d.mesh, diag_pad=True)))[:N, :N]
    l, u = np.tril(ref, -1) + np.eye(N), np.triu(ref)
    eps = np.finfo(ref.dtype).eps
    gamma = (N + 1) * eps / (1 - (N + 1) * eps)
    bound = gamma * (np.abs(a[np.asarray(perm)[:N]]) + np.abs(l) @ np.abs(u))
    bound = np.where(np.tri(N, k=-1, dtype=bool), bound / np.abs(np.diag(u))[None, :], bound)
    err = np.abs(got - ref)
    assert (err <= bound).all(), float((err - bound).max())


def _panel(tiles, nt, m, k, ib):
    """``_pp_panel_factor`` of panel column k on the 2x2 mesh: the
    factored window rows and pivot positions of every mesh row."""
    def kernel(t):
        mtl, ntl = t.shape[:2]
        r, c, _, _ = local_indices(2, 2, mtl, ntl)
        flat, piv = dist_lu._pp_panel_factor(
            t, jnp.int32(k), 2, 2, r, c, nt, m, jnp.int32(0), mtl, ib=ib)
        return flat[None], piv[None]

    return jax.jit(shard_map_compat(
        kernel, mesh=_mesh(), in_specs=(P(ROW_AXIS, COL_AXIS),),
        out_specs=(P(ROW_AXIS), P(ROW_AXIS)), check_vma=False))(tiles)


@pytest.mark.parametrize("ib,k", [(32, 0), (64, 0), (64, 1)])
def test_blocked_panel_matches_unblocked_panel(ib, k):
    d = from_dense(jnp.asarray(_operand("dense")), _mesh(), NB, diag_pad_one=True)
    flat, piv = (np.asarray(x) for x in _panel(d.tiles, d.nt, d.m, k, ib))
    flat1, piv1 = (np.asarray(x) for x in _panel(d.tiles, d.nt, d.m, k, NB))
    np.testing.assert_array_equal(piv, piv1)
    np.testing.assert_allclose(flat, flat1, rtol=0, atol=1e-13 * np.abs(flat1).max())


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _loops(jaxpr):
    """Every scan / while equation under ``jaxpr``, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("scan", "while"):
            yield eqn
        for inner in _subjaxprs(eqn):
            yield from _loops(inner)


def _made(jaxpr):
    """The shape of every value computed under ``jaxpr``."""
    for eqn in jaxpr.eqns:
        yield from (o.aval.shape for o in eqn.outvars)
        for inner in _subjaxprs(eqn):
            yield from _made(inner)


def test_column_loop_touches_only_the_slab():
    """At nb 128 the column loops carry the (64, rows) slab and make
    nothing larger: no array of the panel's (rows, nb) size is carried
    or computed in them.  The panel as it stood at the sub-block's start
    is read there one row at a time."""
    n = 6 * NB  # 6 tile rows: 3 per mesh row
    d = from_dense(jnp.zeros((n, n)), _mesh(), NB, diag_pad_one=True)
    rows = d.tiles.shape[0] // 2 * NB
    closed = jax.make_jaxpr(lambda t: _panel(t, d.nt, d.m, 0, None))(d.tiles)
    col_loops = [eqn for eqn in _loops(closed.jaxpr)
                 if (IB, rows) in [v.aval.shape for v in eqn.outvars]]
    assert len(col_loops) == NB // IB
    for eqn in col_loops:
        body = (eqn.params.get("jaxpr") or eqn.params["body_jaxpr"]).jaxpr
        carried = [v.aval.shape for v in eqn.outvars]
        made = list(_made(body))
        assert (rows, NB) not in carried + made and (NB, rows) not in carried + made
        assert max(int(np.prod(s)) for s in carried + made) == IB * rows
