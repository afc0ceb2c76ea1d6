"""Elementwise / norm / transpose tile kernels.

TPU-native replacements for the reference's 15 CUDA kernel files
(``src/cuda/device_{geadd,gecopy,gescale,geset,genorm,transpose,...}.cu``,
declared in include/slate/internal/device.hh:73-283) and their HIP/omptarget
clones.  Each reference kernel is *batched over arrays of tile pointers*; the
TPU analogue operates on whole arrays or ``(..., nb, nb)`` tile stacks and
lets XLA fuse/vectorize — one implementation replaces all three reference
backends.  Hot variants have Pallas twins in ``pallas_ops.py``; these XLA
forms are the reference semantics and the fallback for every dtype.

All functions are pure and jit-safe; `uplo` masks use trace-time shapes.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..types import Diag, Norm, NormScope, Uplo
from ..core.matrix import band_project, tri_project

# ---------------------------------------------------------------------------
# Elementwise (device_geadd.cu, device_gecopy.cu, device_gescale.cu,
# device_geset.cu and tz* trapezoid variants)
# ---------------------------------------------------------------------------


def geadd(alpha, a: jax.Array, beta, b: jax.Array) -> jax.Array:
    """B := alpha*A + beta*B (device_geadd.cu)."""
    return alpha * a + beta * b


def tzadd(uplo: Uplo, alpha, a: jax.Array, beta, b: jax.Array) -> jax.Array:
    """Trapezoid add: only the uplo triangle is updated (device_tzadd.cu)."""
    full = alpha * a + beta * b
    m, n = a.shape[-2:]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    mask = (i >= j) if uplo == Uplo.Lower else (i <= j)
    return jnp.where(mask, full, b)


def gecopy(a: jax.Array, dtype=None) -> jax.Array:
    """Copy with optional precision conversion (device_gecopy.cu)."""
    return a.astype(dtype) if dtype is not None else a + 0


def tzcopy(uplo: Uplo, a: jax.Array, b: jax.Array, dtype=None) -> jax.Array:
    """Copy the uplo triangle of A over B (device_tzcopy.cu)."""
    if dtype is not None:
        a = a.astype(dtype)
    m, n = a.shape[-2:]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    mask = (i >= j) if uplo == Uplo.Lower else (i <= j)
    return jnp.where(mask, a, b)


def gescale(numer, denom, a: jax.Array) -> jax.Array:
    """A := (numer/denom) * A (device_gescale.cu).  Two-scalar form matches
    the reference's overflow-safe ratio scaling."""
    return a * (jnp.asarray(numer, a.dtype) / jnp.asarray(denom, a.dtype))


def tzscale(uplo: Uplo, numer, denom, a: jax.Array) -> jax.Array:
    scaled = gescale(numer, denom, a)
    m, n = a.shape[-2:]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    mask = (i >= j) if uplo == Uplo.Lower else (i <= j)
    return jnp.where(mask, scaled, a)


def gescale_row_col(r: jax.Array, c: jax.Array, a: jax.Array) -> jax.Array:
    """A := diag(r) * A * diag(c) — row/col equilibration
    (device_gescale_row_col.cu)."""
    return a * r[:, None].astype(a.dtype) * c[None, :].astype(a.dtype)


def geset(offdiag, diag, shape: Tuple[int, int], dtype=jnp.float32) -> jax.Array:
    """A := offdiag everywhere, diag on the diagonal (device_geset.cu)."""
    m, n = shape
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    return jnp.where(i == j, jnp.asarray(diag, dtype), jnp.asarray(offdiag, dtype))


def tzset(uplo: Uplo, offdiag, diag, a: jax.Array) -> jax.Array:
    """Set the uplo triangle to offdiag/diag, leave the rest (device_tzset.cu)."""
    m, n = a.shape[-2:]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    mask = (i >= j) if uplo == Uplo.Lower else (i <= j)
    vals = jnp.where(i == j, jnp.asarray(diag, a.dtype), jnp.asarray(offdiag, a.dtype))
    return jnp.where(mask, vals, a)


def transpose(a: jax.Array, conj: bool = False) -> jax.Array:
    """Tile transpose (device_transpose.cu). Layout conversion collapses to a
    logical transpose under XLA — no extended-buffer dance (Tile.hh
    makeTransposable is runtime machinery XLA subsumes).  Big f32/bf16
    tile stacks on TPU take the explicit Pallas grid (pallas_ops.py)."""
    from .pallas_ops import transpose_pallas, use_pallas_tiles

    if not conj and use_pallas_tiles(a):
        return transpose_pallas(a)
    at = jnp.swapaxes(a, -1, -2)
    return jnp.conj(at) if conj else at


def row_major(x: jax.Array) -> jax.Array:
    """``x`` (2-D) pinned row-major, XLA's {1,0}.  64-bit elements (f64,
    c64) are left to layout assignment: a TPU rewrites them into 32-bit
    pairs, and that rewrite cannot carry a layout constraint."""
    if x.dtype.itemsize > 4:
        return x
    return with_layout_constraint(x, Layout((0, 1)))


# ---------------------------------------------------------------------------
# Norms (device_genorm.cu, device_henorm.cu, device_synorm.cu,
# device_trnorm.cu; drivers src/internal/internal_*norm.cc)
# ---------------------------------------------------------------------------


def _safe_abs(a: jax.Array) -> jax.Array:
    return jnp.abs(a)


def genorm(norm: Norm, a: jax.Array, scope: NormScope = NormScope.Matrix) -> jax.Array:
    """General-matrix norm (device_genorm.cu + internal_genorm.cc)."""
    aa = _safe_abs(a)
    if scope == NormScope.Columns:
        return jnp.max(aa, axis=0) if norm == Norm.Max else jnp.sum(aa, axis=0)
    if scope == NormScope.Rows:
        return jnp.max(aa, axis=1) if norm == Norm.Max else jnp.sum(aa, axis=1)
    if norm == Norm.Max:
        return jnp.max(aa)
    if norm == Norm.One:
        return jnp.max(jnp.sum(aa, axis=0))
    if norm == Norm.Inf:
        return jnp.max(jnp.sum(aa, axis=1))
    if norm == Norm.Fro:
        # scaled sum-of-squares like LAPACK lassq to dodge overflow
        scale = jnp.max(aa)
        scale = jnp.where(scale == 0, 1, scale)
        return scale * jnp.sqrt(jnp.sum((aa / scale) ** 2))
    raise ValueError(norm)


def _herm_full_abs(a: jax.Array, uplo: Uplo) -> jax.Array:
    n = a.shape[0]
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    keep = (i >= j) if uplo == Uplo.Lower else (i <= j)
    t = jnp.where(keep, a, 0)
    strict = (i > j) if uplo == Uplo.Lower else (i < j)
    return jnp.abs(t) + jnp.where(strict.T, jnp.abs(t).T, 0)


def henorm(norm: Norm, a: jax.Array, uplo: Uplo) -> jax.Array:
    """Hermitian norm from one stored triangle (device_henorm.cu)."""
    aa = _herm_full_abs(a, uplo)
    if norm == Norm.Max:
        return jnp.max(aa)
    if norm in (Norm.One, Norm.Inf):  # symmetric: row sums == col sums
        return jnp.max(jnp.sum(aa, axis=0))
    if norm == Norm.Fro:
        scale = jnp.max(aa)
        scale = jnp.where(scale == 0, 1, scale)
        return scale * jnp.sqrt(jnp.sum((aa / scale) ** 2))
    raise ValueError(norm)


synorm = henorm  # same absolute-value structure (device_synorm.cu)


def trnorm(norm: Norm, a: jax.Array, uplo: Uplo, diag: Diag = Diag.NonUnit) -> jax.Array:
    """Trapezoid/triangular norm (device_trnorm.cu)."""
    t = tri_project(a, uplo, diag)
    return genorm(norm, t)


def gbnorm(norm: Norm, a: jax.Array, kl: int, ku: int) -> jax.Array:
    """Band norm (internal_gbnorm.cc): zero outside band then reduce."""
    return genorm(norm, band_project(a, kl, ku))


def hbnorm(norm: Norm, a: jax.Array, uplo: Uplo, kd: int) -> jax.Array:
    kl, ku = (kd, 0) if uplo == Uplo.Lower else (0, kd)
    return henorm(norm, band_project(a, kl, ku), uplo)


def col_norms(a: jax.Array) -> jax.Array:
    """Per-column max-abs (colNorms driver, NormScope::Columns)."""
    return jnp.max(jnp.abs(a), axis=0)
