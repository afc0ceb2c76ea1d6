"""HPL-MxP on one chip: the scanned LU without pivoting, its bfloat16
update tier, and float64 GMRES-IR through ``api.lu_solve_mixed``.

CPU, small n, seeded.  The operand is HPL-MxP's: off-diagonal entries
uniform in [-0.5, 0.5], each diagonal entry its row's off-diagonal
magnitude sum plus one (strictly diagonally dominant, so no pivoting is
needed).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu import api
from slate_tpu.linalg import lu, refine
from slate_tpu.types import MethodLU, Option, Precision


def hpl_mxp(n, seed):
    rng = np.random.default_rng(seed)
    a = rng.uniform(-0.5, 0.5, (n, n))
    i = np.arange(n)
    a[i, i] = np.abs(a).sum(axis=1) - np.abs(a[i, i]) + 1
    return a, rng.standard_normal((n, 1))


def mixed(precision, method=MethodLU.NoPiv):
    opts = {Option.MethodLU: method, Option.Precision: precision}
    return jax.jit(lambda a, b: api.lu_solve_mixed(a, b, opts))


@pytest.mark.parametrize("n", [512, 1000])
def test_scanned_nopiv_lu_matches_recursive(n):
    """The scanned form (4 buckets, nb 128; n 1000 pads to 1024) against
    the recursive one at Highest: the same factor up to float32 rounding,
    64 eps32 of the largest entry."""
    a = jnp.asarray(hpl_mxp(n, 5)[0], jnp.float32)
    scan = np.asarray(jax.jit(lambda a: lu._getrf_nopiv_scan(a, nb=128))(a))
    rec = np.asarray(jax.jit(lu._getrf_nopiv_rec)(a))
    assert np.abs(scan - rec).max() <= 64 * np.finfo(np.float32).eps * np.abs(rec).max()


def _update_dots(precision):
    """(result, lhs, rhs) element types and shapes of every dot of the
    lowered scanned factor, read from the HLO text."""
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    hlo = jax.jit(lambda a: lu._getrf_nopiv_scan(a, nb=128, precision=precision)).lower(
        a).as_text(dialect="hlo")
    types = dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])", hlo, re.M))
    dots = re.findall(r"= (\w+\[[\d,]*\])\S* dot\(%?([\w.\-]+), %?([\w.\-]+)\)", hlo)
    return [(out, types[lhs], types[rhs]) for out, lhs, rhs in dots]


def test_fast_update_takes_bf16_operands_and_gives_f32():
    """Under Precision.Fast each bucket's (nv, 128) x (128, nv) update is a
    bfloat16 dot accumulated in float32; at Highest no dot takes bf16."""
    fast = _update_dots(Precision.Fast)
    for nv in (512, 384, 256, 128):
        assert (f"f32[{nv},{nv}]", f"bf16[{nv},128]", f"bf16[128,{nv}]") in fast, fast
    assert not any("bf16" in lhs for _, lhs, _ in _update_dots(Precision.Highest))


def hessenberg_lstsq(h, beta):
    """min ||beta e1 - H y|| for an (m + 1, m) upper Hessenberg H by the
    Givens steps and back substitution GMRES takes: (y, residual norm)."""
    m = h.shape[1]
    cs, sn = jnp.zeros(m, jnp.real(h).dtype), jnp.zeros(m, h.dtype)
    g = jnp.zeros(m + 1, h.dtype).at[0].set(beta)

    def column(j, c):
        r, cs, sn, g = c
        col, cs, sn, g = refine._givens_step(j, r[:, j], cs, sn, g)
        return r.at[:, j].set(col), cs, sn, g

    r, cs, sn, g = jax.lax.fori_loop(0, m, column, (h, cs, sn, g))
    return refine._upper_solve(r, g, m), jnp.abs(g[m])


@pytest.mark.parametrize("dtype", [np.float64, np.complex128])
def test_givens_least_squares_matches_numpy(dtype):
    rng = np.random.default_rng(7)
    m = 12
    h = rng.standard_normal((m + 1, m))
    if dtype == np.complex128:
        h = h + 1j * rng.standard_normal((m + 1, m))
    h = np.triu(h, -1)
    beta = 2.5
    y, res = jax.jit(hessenberg_lstsq)(jnp.asarray(h), beta)
    e1 = np.zeros(m + 1, dtype)
    e1[0] = beta
    ref = np.linalg.lstsq(h, e1, rcond=None)[0]
    np.testing.assert_allclose(np.asarray(y), ref, rtol=0, atol=1e-12)
    assert float(res) == pytest.approx(np.linalg.norm(e1 - h @ ref), rel=1e-10)


def test_row_blocks_match_a_product():
    """The refinement's products with A over row blocks (1000 rows: two
    blocks, the second overlapping the first) match a float64 product."""
    rng = np.random.default_rng(8)
    a, v = rng.standard_normal((1000, 300)), rng.standard_normal(300)
    hi, lo = refine._split_pair(jnp.asarray(a), jnp.float32)
    got = refine._row_blocks(hi, lo, jnp.float64, lambda m: jnp.sum(m * v, axis=-1))
    # hi + lo holds 48 bits of each entry of A
    np.testing.assert_allclose(np.asarray(got), a @ v, rtol=0,
                               atol=2.0**-46 * (np.abs(a) @ np.abs(v)).max())


def test_lu_solve_mixed_meets_hpl_check_in_float64():
    """n 512, float64, the bf16-product factor: HPL's check in this
    normalization, ||b - A x||_inf / (n ||A||_inf ||x||_inf) <= 16 * 2^-52,
    and agreement with numpy.linalg.solve to 1e-13 relative: A is
    diagonally dominant, cond_inf(A) < 3, so the forward error is at most
    cond times the backward error n * 16 * 2^-53 ~ 1e-12 and in practice
    near 1e-15."""
    a, b = hpl_mxp(512, 11)
    res = mixed(Precision.Fast)(jnp.asarray(a), jnp.asarray(b))
    x = np.asarray(res.x)
    assert x.dtype == np.float64 and bool(res.converged) and int(res.info) == 0
    backward = np.abs(b - a @ x).max() / (512 * np.abs(a).sum(axis=1).max() * np.abs(x).max())
    assert backward <= 16 * 2.0**-52
    ref = np.linalg.solve(a, b)
    assert np.abs(x - ref).max() <= 1e-13 * np.abs(ref).max()


def test_fast_tier_takes_more_gmres_steps_than_highest():
    """The bf16-product factor is a worse preconditioner than the float32
    one, so GMRES needs more steps to the same stop test: the tier is
    engaged.  Both meet HPL's test."""
    a, b = (jnp.asarray(t) for t in hpl_mxp(512, 12))
    fast, full = mixed(Precision.Fast)(a, b), mixed(Precision.Highest)(a, b)
    assert bool(fast.converged) and bool(full.converged)
    assert int(fast.iters) > int(full.iters) >= 1


def test_pivoted_factor_refuses_a_reduced_tier():
    a, b = (jnp.asarray(t) for t in hpl_mxp(64, 13))
    with pytest.raises(ValueError, match="NoPiv"):
        api.lu_solve_mixed(a, b, {Option.Precision: Precision.Fast})
    res = api.lu_solve_mixed(a, b)  # partial pivoting, full precision
    assert bool(res.converged) and int(res.iters) >= 1


def test_scopes_in_the_lowered_program(monkeypatch):
    """Stages getrf and gmres, the scanned factor's panel / bulk / regroup
    phases and GMRES's residual / precond / arnoldi phases reach the
    compiled program's op names (the factor takes its scanned form from
    n 1024 here: four buckets of one step each)."""
    monkeypatch.setattr(lu, "_GETRF_NOPIV_SCAN_MIN_N", 1024)
    a = jax.ShapeDtypeStruct((1024, 1024), jnp.float64)
    b = jax.ShapeDtypeStruct((1024, 1), jnp.float64)
    opts = {Option.MethodLU: MethodLU.NoPiv, Option.Precision: Precision.Fast}
    text = jax.jit(lambda a, b: api.lu_solve_mixed(a, b, opts)).lower(a, b).compile().as_text()
    parts = set()
    for name in re.findall(r'op_name="([^"]*)"', text):
        parts |= set(name.split("/"))
    assert {"getrf", "gmres", "panel", "bulk", "regroup", "residual", "precond",
            "arnoldi"} <= parts, sorted(parts)
