"""Pallas twins of the hot tile kernels and the fused trailing updates.

The reference backs the elementwise kernels with dedicated CUDA kernels
batched over tile-pointer arrays (``src/cuda/device_transpose.cu``,
``device_geadd.cu``, ``device_genorm.cu``; decl
include/slate/internal/device.hh:73-283).  The XLA forms in
``tile_ops.py`` are the reference semantics for every dtype; the Pallas
grids are the explicit-kernel variants for f32/bf16 tile stacks on TPU —
one grid step per tile, VMEM-resident blocks, no intermediate HBM
round-trips between the elementwise ops they fuse.

The FUSED TRAILING-UPDATE KERNELS below are the O(n^3) bulk of a mesh
k-step as one grid dispatch over the local trailing tile stack:

- :func:`summa_update_pallas` — the SUMMA accumulation step
  (``summa.py``'s stationary-C consume);
- :func:`chol_trailing_update_pallas` — the potrf herk
  (``dist_chol._chol_bulk``);
- :func:`lu_trailing_update_pallas` — the LU-nopiv gemm
  (``dist_lu._nopiv_bulk``).

Dispatch is gated by ``Option.UpdateImpl`` (:func:`resolve_update_impl`,
the ``Option.BcastImpl`` pattern).  On CPU/tier-1 every kernel runs under
the Pallas interpreter and is parity-tested against its XLA einsum
(tests/test_pallas_update.py); tests/test_chip_compile.py pins that each
compiles for a described v5e.

Use :func:`use_pallas_tiles` to gate the elementwise twins exactly like
``ops.matmul._use_pallas`` does for the gemm kernel.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def use_pallas_tiles(a: jax.Array) -> bool:
    """Pallas path: TPU backend, supported dtype, (k, nb, nb) tile stack
    big enough that a grid launch beats XLA's fused form."""
    if jax.default_backend() != "tpu":
        return False
    if a.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    return a.ndim == 3 and a.shape[-1] >= 128 and a.shape[0] >= 8


def _transpose_kernel(a_ref, o_ref):
    o_ref[:] = jnp.swapaxes(a_ref[:], -1, -2)


@jax.jit
def transpose_pallas(a: jax.Array) -> jax.Array:
    """Batched tile transpose over a (k, nb, nb) stack
    (device_transpose.cu): one grid step per tile."""
    k, mb, nb = a.shape
    return _pallas_call(
        _transpose_kernel,
        out_shape=jax.ShapeDtypeStruct((k, nb, mb), a.dtype),
        grid=(k,),
        in_specs=[pl.BlockSpec((1, mb, nb), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, nb, mb), lambda i: (i, 0, 0)),
    )(a)


def _geadd_kernel(alpha_ref, beta_ref, a_ref, b_ref, o_ref):
    o_ref[:] = alpha_ref[0] * a_ref[:] + beta_ref[0] * b_ref[:]


@jax.jit
def geadd_pallas(alpha, a: jax.Array, beta, b: jax.Array) -> jax.Array:
    """Batched B := alpha A + beta B over a tile stack (device_geadd.cu)."""
    k, mb, nb = a.shape
    al = jnp.asarray([alpha], a.dtype)
    be = jnp.asarray([beta], a.dtype)
    return _pallas_call(
        _geadd_kernel,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        grid=(k,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, mb, nb), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, mb, nb), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, mb, nb), lambda i: (i, 0, 0)),
    )(al, be, a, b)


def _norm_max_kernel(a_ref, o_ref):
    # two-stage reduction: lanes stay vectorized (column maxes) in-kernel,
    # the final fold over nb happens in XLA outside.  The (8, nb) output
    # block satisfies the TPU (8, 128) tiling floor.
    cm = jnp.max(jnp.abs(a_ref[:]), axis=-2)  # (1, nb)
    o_ref[:] = jnp.broadcast_to(cm, o_ref.shape)


@jax.jit
def genorm_max_pallas(a: jax.Array) -> jax.Array:
    """Per-tile max-abs over a (k, nb, nb) stack (device_genorm.cu,
    NormScope::Matrix reduced tile-wise)."""
    k, mb, nb = a.shape
    colmax = _pallas_call(
        _norm_max_kernel,
        out_shape=jax.ShapeDtypeStruct((k, 8, nb), a.dtype),
        grid=(k,),
        in_specs=[pl.BlockSpec((1, mb, nb), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, 8, nb), lambda i: (i, 0, 0)),
    )(a)
    return jnp.max(colmax[:, 0, :], axis=-1)


# ---------------------------------------------------------------------------
# Option.UpdateImpl gate (ISSUE 20): the lowering of the TRAILING UPDATE —
# the O(n^3) bulk of every k-step.  Selection is a TRACE-TIME property:
# mesh kernels thread the resolved impl through their jit as a static
# argument and wrap tracing in ``update_impl_scope``; ``xla`` IS today's
# einsum bulk (jaxpr-identical by construction), ``pallas`` swaps only
# the local compute for the fused grid kernels below — the broadcast
# schedule and comm bytes are untouched.
# ---------------------------------------------------------------------------

# the update kernels engage only when their broadcast panels fit
# comfortably in VMEM (~16 MB/core on v5e; headroom for double buffering)
_UPDATE_VMEM_CAP = 4 * 1024 * 1024


def _interpret() -> bool:
    """Pallas interpreter mode: anywhere the real TPU backend is absent
    (CPU tier-1/CI), kernels run interpreted — same lax semantics, pure
    JAX — so every kernel is testable off-chip.  On a TPU backend every
    kernel goes to Mosaic; nothing falls back to the interpreter."""
    from .matmul import _tpu_is_default

    return not _tpu_is_default()


def _pallas_dtype_ok(dtype, nbytes: Optional[int] = None) -> bool:
    """The dtype/size gate behind ``update_engaged``: real-floating
    always under the interpreter, MXU dtypes within the VMEM cap on a
    real TPU, complex never."""
    dt = jnp.dtype(dtype)
    if dt.kind == "c":
        return False
    if _interpret():
        return True
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    return nbytes is None or nbytes <= _UPDATE_VMEM_CAP


UPDATE_IMPLS = ("xla", "pallas", "auto")
UPDATE_IMPL_ENV = "SLATE_TPU_UPDATE_IMPL"

_UPDATE_DEFAULT = [None]  # session default (use_update_impl), outside jit
_UPDATE_ACTIVE = ["__chain__"]  # trace-time impl (update_impl_scope)


def _check_update_impl(impl: str) -> str:
    if impl not in UPDATE_IMPLS:
        raise ValueError(
            f"unknown update impl {impl!r}; expected one of {UPDATE_IMPLS}"
        )
    return impl


def resolve_update_impl(impl: Optional[str] = None) -> str:
    """Resolve an Option.UpdateImpl value at driver level (OUTSIDE jit):
    explicit argument > ``use_update_impl`` context default >
    ``SLATE_TPU_UPDATE_IMPL`` environment > ``auto``.  ``auto`` stays
    ``auto``: the concrete choice depends on the trailing stack's
    dtype/size and is made at the dispatch site
    (:func:`update_engaged`)."""
    if impl is None:
        impl = _UPDATE_DEFAULT[-1]
    if impl is None:
        impl = os.environ.get(UPDATE_IMPL_ENV) or "auto"
    return _check_update_impl(impl)


@contextlib.contextmanager
def use_update_impl(impl: str):
    """Set the session-default trailing-update lowering for drivers
    called inside (tests / CI sweeps); an explicit ``update_impl=``
    argument still wins."""
    _UPDATE_DEFAULT.append(_check_update_impl(impl))
    try:
        yield
    finally:
        _UPDATE_DEFAULT.pop()


@contextlib.contextmanager
def update_impl_scope(impl: str):
    """Activate a lowering for the trailing-update dispatch traced
    inside — used by the mesh kernels around their shard_map call, with
    ``impl`` a static jit argument of the enclosing kernel."""
    _UPDATE_ACTIVE.append(_check_update_impl(impl))
    try:
        yield
    finally:
        _UPDATE_ACTIVE.pop()


def update_active_impl() -> str:
    """Concrete trace-time impl: the innermost ``update_impl_scope``
    when a kernel pinned one (static jit arg), else the resolve chain;
    with ``auto`` mapped to ``pallas`` on a real TPU backend and ``xla``
    elsewhere (CPU tier-1 stays bitwise today's results unless pallas is
    requested explicitly)."""
    impl = _UPDATE_ACTIVE[-1]
    if impl == "__chain__":
        impl = resolve_update_impl()
    if impl == "auto":
        impl = "xla" if _interpret() else "pallas"
    return impl


def update_engaged(dtype, nbytes: Optional[int] = None) -> bool:
    """Whether the fused Pallas trailing-update kernels take this
    dispatch under the ``update_impl_scope`` chain.  ``nbytes`` is the broadcast-panel
    working set (the VMEM-resident operands; the trailing tiles
    stream)."""
    impl = update_active_impl()
    if impl != "pallas":
        return False
    return _pallas_dtype_ok(dtype, nbytes)


def _pallas_call(kernel, **kw):
    """``pl.pallas_call`` for this backend: interpreted off-TPU; on a TPU
    the kernel and its index maps trace with x64 off, since Mosaic lowers
    32-bit grid indices only (the library runs with ``jax_enable_x64`` on
    for its f64 paths; on-chip kernels take f32/bf16/int32 operands)."""
    interpret = _interpret()
    call = pl.pallas_call(kernel, interpret=interpret, **kw)
    if interpret:
        return call

    def run(*args):
        with jax.enable_x64(False):
            return call(*args)

    return run


# ---------------------------------------------------------------------------
# fused trailing-update kernels (ISSUE 20): one grid dispatch over the
# local trailing tile stack per k-step.  The broadcast panels ride VMEM
# blocks shared across the grid; the trailing tiles stream through one
# (nb, nb) block per step.  Each kernel runs the SAME dot_general
# contraction + select/accumulate op sequence as its XLA einsum bulk.  In
# float64 the interpreter reproduces the einsum bitwise; in float32 the
# contraction's accumulation order is the backend's own, so the results
# agree to a length-nb dot's rounding bound, not bitwise
# (tests/test_pallas_update.py asserts both).
# ---------------------------------------------------------------------------


def summa_update_pallas(
    acc: jax.Array, pan: jax.Array, urow: jax.Array
) -> jax.Array:
    """One SUMMA accumulation step over the local (I, J) tile grid:
    ``acc[i, j] += pan[i] @ urow[j]``, consumed by ``summa.py``'s
    stationary-C consume."""
    I, nb, _ = pan.shape
    J = urow.shape[0]

    def kern(p_ref, u_ref, a_ref, o_ref):
        upd = jnp.matmul(p_ref[0], u_ref[0], precision=_HIGHEST)
        o_ref[:] = a_ref[:] + upd[None, None].astype(a_ref.dtype)

    return _pallas_call(
        kern,
        grid=(J, I),
        in_specs=[
            pl.BlockSpec((1, nb, nb), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, nb, nb), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(acc.shape, acc.dtype),
    )(pan, urow, acc)


# The trailing kernels' per-tile (I, J) keep mask.  Interpreted, each
# grid step (j, i) gets its own (1, 1) block.  Mosaic refuses (1, 1)
# blocks, so on the chip the mask rides SMEM whole, flattened row-major,
# and each step reads its own entry by program id — the element interpret
# mode reads (a whole-array SMEM block read at [0, 0] would hand every
# step tile (0, 0)'s mask).


def _mask_input(mask: jax.Array):
    """(BlockSpec, operand) for the keep mask on this backend."""
    m32 = mask.astype(jnp.int32)
    if _interpret():
        return pl.BlockSpec((1, 1), lambda j, i: (i, j)), m32
    return pl.BlockSpec(memory_space=pltpu.SMEM), m32.reshape(-1)


def _tile_mask(m_ref, J: int):
    if _interpret():
        return m_ref[0, 0]
    return m_ref[pl.program_id(1) * J + pl.program_id(0)]


def chol_trailing_update_pallas(
    view: jax.Array, pan: jax.Array, pan_t: jax.Array, mask: jax.Array
) -> jax.Array:
    """The potrf trailing update (``dist_chol._chol_bulk``'s herk) as one
    grid dispatch: ``view[i, j] -= mask[i, j] ? pan[i] @ pan_t[j]^T : 0``
    with the per-tile lower/exclusion ``mask`` (int32, possibly traced —
    it folds the ``i_log >= j_log`` lower select and the lookahead
    ``excl_kc`` column) computed in XLA outside and riding SMEM."""
    I, nb, _ = pan.shape
    J = pan_t.shape[0]
    mask_spec, m = _mask_input(mask)

    def kern(m_ref, p_ref, t_ref, a_ref, o_ref):
        upd = lax.dot_general(
            p_ref[0], t_ref[0], (((1,), (1,)), ((), ())),
            precision=_HIGHEST,
        ).astype(a_ref.dtype)
        sel = jnp.where(_tile_mask(m_ref, J) != 0, upd, jnp.zeros_like(upd))
        o_ref[:] = a_ref[:] - sel[None, None]

    return _pallas_call(
        kern,
        grid=(J, I),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, nb, nb), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, nb, nb), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
    )(m, pan, pan_t, view)


def lu_trailing_update_pallas(
    t_loc: jax.Array, pan: jax.Array, urow: jax.Array, mask: jax.Array
) -> jax.Array:
    """The LU-nopiv trailing update (``dist_lu._nopiv_bulk``'s gemm) as
    one grid dispatch: ``t[i, j] -= mask[i, j] ? pan[i] @ urow[j] : 0``
    with the per-tile keep ``mask`` (the lookahead ``excl_kr``/``excl_kc``
    exclusions; all-ones on the plain sweep) computed in XLA outside."""
    I, nb, _ = pan.shape
    J = urow.shape[0]
    mask_spec, m = _mask_input(mask)

    def kern(m_ref, p_ref, u_ref, a_ref, o_ref):
        upd = jnp.matmul(
            p_ref[0], u_ref[0], precision=_HIGHEST
        ).astype(a_ref.dtype)
        sel = jnp.where(_tile_mask(m_ref, J) != 0, upd, jnp.zeros_like(upd))
        o_ref[:] = a_ref[:] - sel[None, None]

    return _pallas_call(
        kern,
        grid=(J, I),
        in_specs=[
            mask_spec,
            pl.BlockSpec((1, nb, nb), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, nb, nb), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(t_loc.shape, t_loc.dtype),
    )(m, pan, urow, t_loc)


