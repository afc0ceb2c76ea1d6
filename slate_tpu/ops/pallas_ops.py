"""Pallas twins of the hot tile kernels, and the FUSED PANEL layer.

The reference backs the elementwise kernels with dedicated CUDA kernels
batched over tile-pointer arrays (``src/cuda/device_transpose.cu``,
``device_geadd.cu``, ``device_genorm.cu``; decl
include/slate/internal/device.hh:73-283).  The XLA forms in
``tile_ops.py`` are the reference semantics for every dtype; the Pallas
grids are the explicit-kernel variants for f32/bf16 tile stacks on TPU —
one grid step per tile, VMEM-resident blocks, no intermediate HBM
round-trips between the elementwise ops they fuse.

The FUSED PANEL KERNELS below are this module's hot half (SURVEY "Hard
parts": the panel factorization is the latency bottleneck — nb tiny XLA
dispatches per k-step).  MAGMA-style batched one-sided panels
(Abdelfattah et al.) factor the whole panel in ONE on-chip kernel; the
Pallas forms here do the same:

- :func:`chol_diag_inv_pallas` — (L, L^-1) of one nb x nb block: the
  column-loop factor and the forward-substitution inverse run inside a
  single ``pallas_call`` over a VMEM-resident block, replacing the
  ``lax.linalg.cholesky`` + ``triangular_solve`` dispatch pair.
- :func:`chol_panel_tiles_pallas` — the full potrf panel phase: grid
  step 0 factors the diagonal tile (+ inverse, kept in VMEM scratch),
  steps 1..L solve the below-panel tiles ``A_i L^-H`` on the MXU.
- :func:`lu_panel_tiles_pallas` / :func:`lu_rowsolve_tiles_pallas` —
  the getrf-nopiv panel: packed L\\U diag factor with U^-1 (resp. the
  unit-L^-1 row sweep) in scratch, tile solves as MXU matmuls.
- :func:`qr_panel_pallas` / :func:`qr_panel_offset_pallas` — the
  tall-skinny Householder panel: reflector generation AND the compact-WY
  ``_larft`` T accumulation fused into one kernel over a VMEM-resident
  panel (the CAQR / two-stage building block).
- :func:`ft_summa_update_pallas` — the ABFT trailing update: one pass
  computes the MXU tile products AND accumulates the Huang-Abraham
  weighted row sums the discrepancy check needs (ft/abft.py).

Numerics: the triangular solves inside the panel kernels use the
explicit-inverse form (MAGMA trtri+gemm; the idiom ``_potrf_scan``
already uses), so results match the XLA references to the documented
O(eps * cond(diag block)) class, not bitwise; the QR kernels run the
SAME ``_panel_qr``/``_larft`` op sequence as the XLA reference and are
bitwise under interpret mode.  The XLA forms remain the reference
semantics for every dtype; dispatch is gated by ``Option.PanelImpl``
(:func:`resolve_panel_impl`, the ``Option.BcastImpl`` pattern) and on
CPU/tier-1 every kernel runs under the Pallas interpreter and is
parity-tested against its XLA reference (tests/test_pallas_panels.py).
``auto`` resolves panels to ``xla`` on every backend (no chip evidence
for the fused panels yet).  The panel column loops slice values with
``lax.dynamic_slice``, which Mosaic does not lower, so every panel
kernel raises on a TPU (:func:`_require_mosaic_lowering`) until a
benchmark shows a Mosaic form pays off; the elementwise, gemm and
trailing-update kernels compile.  tests/test_chip_compile.py pins both
for a described v5e.

Use :func:`use_pallas_tiles` to gate the elementwise twins exactly like
``ops.matmul._use_pallas`` does for the gemm kernel.
"""

from __future__ import annotations

import contextlib
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..types import SlateError

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
# Option.PanelImpl gate (the Option.BcastImpl pattern, comm.py:189-259).
#
# Selection is a TRACE-TIME property: the mesh kernels that consume the
# panel dispatch thread the resolved impl through their jit as a static
# argument and wrap tracing in ``panel_impl_scope`` — a cache hit on a
# different impl is impossible by construction.  The single-chip linalg
# facades (qr/chol) read the resolve chain directly at trace time, the
# same contract ``ops.matmul``'s f64 dispatch already has: switching the
# impl between calls of identical shape needs a retrace
# (``jax.clear_caches()``), which the tests and smokes do.
# ---------------------------------------------------------------------------

PANEL_IMPLS = ("xla", "pallas", "auto")
PANEL_IMPL_ENV = "SLATE_TPU_PANEL_IMPL"

_PANEL_DEFAULT = [None]  # session default (use_panel_impl), outside jit
_PANEL_ACTIVE = ["__chain__"]  # trace-time impl (panel_impl_scope)

# auto only engages an update whose broadcast panels fit comfortably in
# VMEM (~16 MB/core on v5e; headroom for double buffering)
_PANEL_VMEM_CAP = 4 * 1024 * 1024


def _check_panel_impl(impl: str) -> str:
    if impl not in PANEL_IMPLS:
        raise ValueError(
            f"unknown panel impl {impl!r}; expected one of {PANEL_IMPLS}"
        )
    return impl


def resolve_panel_impl(impl: Optional[str] = None) -> str:
    """Resolve an Option.PanelImpl value at driver level (OUTSIDE jit):
    explicit argument > ``use_panel_impl`` context default >
    ``SLATE_TPU_PANEL_IMPL`` environment > ``auto``.  ``auto`` stays
    ``auto``: the concrete choice depends on each panel's dtype/size and
    is made at the dispatch site (:func:`panel_engaged`)."""
    if impl is None:
        impl = _PANEL_DEFAULT[-1]
    if impl is None:
        impl = os.environ.get(PANEL_IMPL_ENV) or "auto"
    return _check_panel_impl(impl)


@contextlib.contextmanager
def use_panel_impl(impl: str):
    """Set the session-default panel lowering for drivers called inside
    (tests / CI sweeps); an explicit ``panel_impl=`` argument still
    wins."""
    _PANEL_DEFAULT.append(_check_panel_impl(impl))
    try:
        yield
    finally:
        _PANEL_DEFAULT.pop()


@contextlib.contextmanager
def panel_impl_scope(impl: str):
    """Activate a lowering for the panel dispatch traced inside — used by
    the mesh kernels around their shard_map call, with ``impl`` a static
    jit argument of the enclosing kernel."""
    _PANEL_ACTIVE.append(_check_panel_impl(impl))
    try:
        yield
    finally:
        _PANEL_ACTIVE.pop()


def _interpret() -> bool:
    """Pallas interpreter mode: anywhere the real TPU backend is absent
    (CPU tier-1/CI), kernels run interpreted — same lax semantics, pure
    JAX — so every kernel is testable off-chip.  On a TPU backend every
    kernel goes to Mosaic; nothing falls back to the interpreter."""
    from .matmul import _tpu_is_default

    return not _tpu_is_default()


def _require_mosaic_lowering(kernel: str) -> None:
    """Refuse, on a TPU backend, a panel kernel Mosaic cannot lower: the
    Cholesky / LU / QR column loops slice values with
    ``lax.dynamic_slice``, which the TPU Pallas lowering does not
    implement (tests/test_chip_compile.py pins which kernels compile).
    Raising here keeps an explicit ``Option.PanelImpl=pallas`` from
    failing deep in the compiler, and from ever dropping silently to XLA
    or to the interpreter."""
    if not _interpret():
        raise SlateError(
            f"{kernel} does not lower for the TPU (Mosaic has no "
            "in-kernel dynamic_slice); use Option.PanelImpl='xla' or "
            "'auto' on a TPU backend"
        )


def panel_active_impl() -> str:
    """Concrete trace-time impl: the innermost ``panel_impl_scope`` when
    a kernel pinned one (static jit arg), else the resolve chain; with
    ``auto`` mapped to ``xla`` on every backend.  The fused panel kernels
    run only when asked for (auto has no chip evidence for them yet); on
    a TPU they raise (:func:`_require_mosaic_lowering`)."""
    impl = _PANEL_ACTIVE[-1]
    if impl == "__chain__":
        impl = resolve_panel_impl()
    if impl == "auto":
        impl = "xla"
    return impl


def panel_engaged(dtype, nbytes: Optional[int] = None) -> bool:
    """Whether the fused Pallas panel kernels take this dispatch.

    ``xla`` never engages (the reference semantics).  ``pallas`` engages
    every real-floating dtype under the interpreter (CPU parity runs) but
    only MXU dtypes (f32/bf16) on a real TPU — f64/complex panels have no
    on-chip kernel and silently keep the XLA forms, like the Ozaki gate
    keeps thin-k shapes.  ``nbytes`` (the panel working set) lets auto
    bail out of panels that would not fit VMEM."""
    impl = panel_active_impl()
    if impl != "pallas":
        return False
    return _pallas_dtype_ok(dtype, nbytes)


def _pallas_dtype_ok(dtype, nbytes: Optional[int] = None) -> bool:
    """The shared dtype/size gate behind ``panel_engaged`` and
    ``update_engaged``: real-floating always under the interpreter,
    MXU dtypes within the VMEM cap on a real TPU, complex never."""
    dt = jnp.dtype(dtype)
    if dt.kind == "c":
        return False
    if _interpret():
        return True
    if dt not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return False
    return nbytes is None or nbytes <= _PANEL_VMEM_CAP


# ---------------------------------------------------------------------------
# Option.UpdateImpl gate (ISSUE 20): the Option.PanelImpl pattern applied
# to the TRAILING UPDATE — the O(n^3) bulk of every k-step.  Same
# trace-time contract: mesh kernels thread the resolved impl through
# their jit as a static argument and wrap tracing in
# ``update_impl_scope``; ``xla`` IS today's einsum bulk (jaxpr-identical
# by construction), ``pallas`` swaps only the local compute for the
# fused grid kernels below — the broadcast schedule and comm bytes are
# untouched.
# ---------------------------------------------------------------------------

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
    dispatch — the :func:`panel_engaged` gate read against the
    ``update_impl_scope`` chain.  ``nbytes`` is the broadcast-panel
    working set (the VMEM-resident operands; the trailing tiles
    stream)."""
    impl = update_active_impl()
    if impl != "pallas":
        return False
    return _pallas_dtype_ok(dtype, nbytes)


# ---------------------------------------------------------------------------
# in-kernel factor bodies (pure value math; run inside pallas kernels)
# ---------------------------------------------------------------------------


def _chol_inv_body(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Column-loop lower Cholesky + row-loop forward-substitution inverse
    of one nb x nb block.  Non-SPD input NaN-poisons through the sqrt,
    matching the XLA cholesky convention (the drivers' info checks read
    the poisoned diagonal)."""
    n = a.shape[0]
    rows = jnp.arange(n)
    cols = jnp.arange(n)

    def col_step(j, w):
        col = lax.dynamic_slice(w, (jnp.zeros_like(j), j), (n, 1))[:, 0]
        d = jnp.sqrt(col[j])
        lcol = jnp.where(rows >= j, col / d, 0.0).astype(a.dtype)
        lcol = lcol.at[j].set(d.astype(a.dtype))
        w = jnp.where((cols == j)[None, :], lcol[:, None], w)
        return w - jnp.where(
            (cols > j)[None, :], lcol[:, None] * lcol[None, :], 0.0
        ).astype(a.dtype)

    l = jnp.tril(lax.fori_loop(0, n, col_step, a))

    def inv_step(t, x):
        lrow = lax.dynamic_slice(l, (t, jnp.zeros_like(t)), (1, n))[0]
        acc = jnp.matmul(
            jnp.where(cols < t, lrow, 0.0)[None, :], x, precision=_HIGHEST
        )[0]
        e = (cols == t).astype(a.dtype)
        xrow = (e - acc) / lrow[t]
        return jnp.where((rows == t)[:, None], xrow[None, :], x)

    x = lax.fori_loop(0, n, inv_step, jnp.zeros_like(a))
    return l, jnp.tril(x)


def _unit_linv_body(lu: jax.Array) -> jax.Array:
    """unit-L^-1 from a packed L\\U block by row-wise forward
    substitution (shared by the LU row-solve kernel)."""
    n = lu.shape[0]
    rows = jnp.arange(n)
    cols = jnp.arange(n)

    def linv_step(t, x):
        lrow = lax.dynamic_slice(lu, (t, jnp.zeros_like(t)), (1, n))[0]
        acc = jnp.matmul(
            jnp.where(cols < t, lrow, 0.0)[None, :], x, precision=_HIGHEST
        )[0]
        xrow = (cols == t).astype(lu.dtype) - acc.astype(lu.dtype)
        return jnp.where((rows == t)[:, None], xrow[None, :], x)

    return jnp.tril(lax.fori_loop(0, n, linv_step, jnp.zeros_like(lu)))


def _lu_inv_body(a: jax.Array):
    """Packed no-pivot L\\U of one nb x nb block (the `_nopiv_base`
    column loop run on-chip) plus the U^-1 the panel-column solves
    consume (back substitution; the row solves' unit-L^-1 lives in
    :func:`_unit_linv_body`)."""
    n = a.shape[0]
    rows = jnp.arange(n)
    cols = jnp.arange(n)

    def col_step(j, w):
        col = lax.dynamic_slice(w, (jnp.zeros_like(j), j), (n, 1))[:, 0]
        piv = col[j]
        denom = jnp.where(piv == 0, jnp.ones_like(piv), piv)
        lcol = jnp.where(rows > j, col / denom, 0.0).astype(a.dtype)
        w = jnp.where(
            (cols == j)[None, :],
            jnp.where(rows > j, lcol, col)[:, None],
            w,
        )
        urow = lax.dynamic_slice(w, (j, jnp.zeros_like(j)), (1, n))[0]
        return w - jnp.where(
            (cols > j)[None, :], lcol[:, None] * urow[None, :], 0.0
        ).astype(a.dtype)

    lu = lax.fori_loop(0, n, col_step, a)

    def uinv_step(s, x):
        t = n - 1 - s
        urow = lax.dynamic_slice(lu, (t, jnp.zeros_like(t)), (1, n))[0]
        acc = jnp.matmul(
            jnp.where(cols > t, urow, 0.0)[None, :], x, precision=_HIGHEST
        )[0]
        e = (cols == t).astype(a.dtype)
        xrow = (e - acc) / urow[t]
        return jnp.where((rows == t)[:, None], xrow[None, :], x)

    uinv = lax.fori_loop(0, n, uinv_step, jnp.zeros_like(a))
    return lu, jnp.triu(uinv)


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
# fused Cholesky panel kernels
# ---------------------------------------------------------------------------


def chol_diag_inv_pallas(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(L, L^-1) of one nb x nb Hermitian block in ONE kernel dispatch:
    the on-chip replacement for the ``cholesky`` + ``triangular_solve``
    pair (each of which unrolls into per-column micro-ops on TPU)."""
    _require_mosaic_lowering("chol_diag_inv_pallas")
    n = a.shape[0]

    def kern(a_ref, l_ref, x_ref):
        l, x = _chol_inv_body(a_ref[:])
        l_ref[:] = l
        x_ref[:] = x

    return _pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((n, n), a.dtype),
            jax.ShapeDtypeStruct((n, n), a.dtype),
        ),
    )(a)


def chol_panel_tiles_pallas(
    dtile: jax.Array, tiles: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The full potrf panel phase in one ``pallas_call``: grid step 0
    factors the diagonal tile (column loop, inverse kept in VMEM
    scratch), steps 1..L solve the panel tiles ``A_i L^-H`` on the MXU.
    Returns (tril L_kk, solved tile stack)."""
    _require_mosaic_lowering("chol_panel_tiles_pallas")
    nb = dtile.shape[0]
    L = tiles.shape[0]

    def kern(d_ref, t_ref, l_ref, s_ref, linv_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            l, x = _chol_inv_body(d_ref[:])
            l_ref[:] = l
            linv_ref[:] = x

        @pl.when(i > 0)
        def _():
            s_ref[:] = jnp.matmul(
                t_ref[0], linv_ref[:].T, precision=_HIGHEST
            )[None].astype(s_ref.dtype)

    l, solved = _pallas_call(
        kern,
        grid=(L + 1,),
        in_specs=[
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nb, nb), dtile.dtype),
            jax.ShapeDtypeStruct((L, nb, nb), tiles.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((nb, nb), dtile.dtype)],
    )(dtile, tiles)
    return l, solved


# ---------------------------------------------------------------------------
# fused LU-nopiv panel kernels
# ---------------------------------------------------------------------------


def lu_panel_tiles_pallas(
    dtile: jax.Array, tiles: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """The getrf-nopiv panel-column phase in one kernel: step 0 computes
    the packed L\\U of the diagonal tile (+ U^-1 in scratch), steps 1..L
    solve the column tiles ``A_i U^-1`` on the MXU.  Returns
    (packed L\\U, solved tile stack)."""
    _require_mosaic_lowering("lu_panel_tiles_pallas")
    nb = dtile.shape[0]
    L = tiles.shape[0]

    def kern(d_ref, t_ref, lu_ref, s_ref, uinv_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            lu, uinv = _lu_inv_body(d_ref[:])
            lu_ref[:] = lu
            uinv_ref[:] = uinv

        @pl.when(i > 0)
        def _():
            s_ref[:] = jnp.matmul(
                t_ref[0], uinv_ref[:], precision=_HIGHEST
            )[None].astype(s_ref.dtype)

    lu, solved = _pallas_call(
        kern,
        grid=(L + 1,),
        in_specs=[
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nb, nb), dtile.dtype),
            jax.ShapeDtypeStruct((L, nb, nb), tiles.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((nb, nb), dtile.dtype)],
    )(dtile, tiles)
    return lu, solved


def lu_rowsolve_tiles_pallas(luk: jax.Array, tiles: jax.Array) -> jax.Array:
    """The getrf-nopiv panel-row phase: step 0 computes unit-L^-1 from
    the packed diagonal L\\U (scratch), steps 1..L solve the row tiles
    ``L^-1 A_j`` on the MXU."""
    _require_mosaic_lowering("lu_rowsolve_tiles_pallas")
    nb = luk.shape[0]
    L = tiles.shape[0]

    def kern(d_ref, t_ref, s_ref, linv_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            linv_ref[:] = _unit_linv_body(d_ref[:])

        @pl.when(i > 0)
        def _():
            s_ref[:] = jnp.matmul(
                linv_ref[:], t_ref[0], precision=_HIGHEST
            )[None].astype(s_ref.dtype)

    return _pallas_call(
        kern,
        grid=(L + 1,),
        in_specs=[
            pl.BlockSpec((nb, nb), lambda i: (0, 0)),
            pl.BlockSpec((1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, nb, nb), lambda i: (jnp.maximum(i - 1, 0), 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((L, nb, nb), tiles.dtype),
        scratch_shapes=[pltpu.VMEM((nb, nb), luk.dtype)],
    )(luk, tiles)


# ---------------------------------------------------------------------------
# fused Householder panel kernels (QR)
# ---------------------------------------------------------------------------


def qr_panel_pallas(a: jax.Array):
    """Unblocked Householder QR of an (m, w) panel WITH the compact-WY T
    accumulation, fused into one kernel over the VMEM-resident panel —
    the reference's internal_geqrf panel + larft pair as a single
    dispatch.  Returns (packed VR, tau, T); runs the SAME op sequence as
    ``linalg.qr._panel_qr`` + ``_larft`` (bitwise under interpret)."""
    _require_mosaic_lowering("qr_panel_pallas")
    m, w = a.shape

    def kern(a_ref, vr_ref, tau_ref, t_ref):
        from ..linalg.qr import _larft, _panel_qr

        vr, tau = _panel_qr(a_ref[:])
        vr_ref[:] = vr
        tau_ref[:] = tau[None, :]
        t_ref[:] = _larft(vr, tau)

    vr, tau, t = _pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct((m, w), a.dtype),
            jax.ShapeDtypeStruct((1, w), a.dtype),
            jax.ShapeDtypeStruct((w, w), a.dtype),
        ),
    )(a)
    return vr, tau[0], t


def qr_panel_offset_pallas(a: jax.Array, row0):
    """Fused offset-pivot Householder panel (+ T): the scanned / CAQR
    building block ``_panel_qr_offset`` + ``_larft_v`` as one dispatch.
    ``row0`` may be traced (a loop residue); it rides along as a scalar
    operand.  Returns (r, v, tau, T)."""
    _require_mosaic_lowering("qr_panel_offset_pallas")
    m, w = a.shape
    r0 = jnp.asarray(row0, jnp.int32).reshape(1, 1)

    def kern(r0_ref, a_ref, r_ref, v_ref, tau_ref, t_ref):
        from ..linalg.qr import _larft_v, _panel_qr_offset

        r, v, tau = _panel_qr_offset(a_ref[:], r0_ref[0, 0])
        r_ref[:] = r
        v_ref[:] = v
        tau_ref[:] = tau[None, :]
        t_ref[:] = _larft_v(v, tau)

    r, v, tau, t = _pallas_call(
        kern,
        in_specs=[
            pl.BlockSpec((1, 1), lambda: (0, 0)),
            pl.BlockSpec((m, w), lambda: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((m, w), lambda: (0, 0)),
            pl.BlockSpec((m, w), lambda: (0, 0)),
            pl.BlockSpec((1, w), lambda: (0, 0)),
            pl.BlockSpec((w, w), lambda: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, w), a.dtype),
            jax.ShapeDtypeStruct((m, w), a.dtype),
            jax.ShapeDtypeStruct((1, w), a.dtype),
            jax.ShapeDtypeStruct((w, w), a.dtype),
        ),
    )(r0, a)
    return r, v, tau[0], t


# ---------------------------------------------------------------------------
# fused trailing-update kernels (ISSUE 20): one grid dispatch over the
# local trailing tile stack per k-step.  The broadcast panels ride VMEM
# blocks shared across the grid; the trailing tiles stream through one
# (nb, nb) block per step.  Each kernel runs the SAME dot_general
# contraction + select/accumulate op sequence as its XLA einsum bulk —
# bitwise under interpret mode (asserted in tests/test_pallas_update.py).
# ---------------------------------------------------------------------------


def summa_update_pallas(
    acc: jax.Array, pan: jax.Array, urow: jax.Array
) -> jax.Array:
    """One SUMMA accumulation step over the local (I, J) tile grid:
    ``acc[i, j] += pan[i] @ urow[j]`` — the non-checksum sibling of
    :func:`ft_summa_update_pallas`, consumed by ``summa.py``'s
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


# ---------------------------------------------------------------------------
# fused ABFT trailing update + Huang-Abraham partial sums
# ---------------------------------------------------------------------------


def ft_summa_update_pallas(
    acc: jax.Array, pan: jax.Array, urow: jax.Array,
    w1: jax.Array, w2: jax.Array, part: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """One SUMMA accumulation step over the local tile grid, computing
    the MXU update AND the Huang-Abraham weighted row sums in the same
    pass: ``acc[i, j] += pan[i] @ urow[j]`` while ``part[:, j]``
    accumulates ``sum_i w{1,2}[i] * (pan[i] @ urow[j])`` — the per-device
    contribution to the recomputed checksum rows, so the discrepancy
    check costs no second sweep over the trailing tiles.  ``w1``/``w2``
    are the unit/ramp weights per local tile row (zero on checksum and
    pad rows)."""
    I, nb, _ = pan.shape
    J = urow.shape[0]

    def kern(p_ref, u_ref, a_ref, w1_ref, w2_ref, pin_ref, o_ref, part_ref,
             psum_ref):
        j = pl.program_id(0)
        i = pl.program_id(1)
        upd = jnp.matmul(p_ref[0], u_ref[0], precision=_HIGHEST)
        o_ref[:] = (a_ref[:] + upd[None, None].astype(a_ref.dtype))

        wu1 = w1_ref[i] * upd
        wu2 = w2_ref[i] * upd

        @pl.when(i == 0)
        def _():
            psum_ref[0] = pin_ref[0, 0] + wu1.astype(psum_ref.dtype)
            psum_ref[1] = pin_ref[1, 0] + wu2.astype(psum_ref.dtype)

        @pl.when(i > 0)
        def _():
            psum_ref[0] += wu1.astype(psum_ref.dtype)
            psum_ref[1] += wu2.astype(psum_ref.dtype)

        @pl.when(i == I - 1)
        def _():
            part_ref[:] = psum_ref[:][:, None]

    out, part_new = _pallas_call(
        kern,
        grid=(J, I),
        in_specs=[
            pl.BlockSpec((1, nb, nb), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, nb, nb), lambda j, i: (j, 0, 0)),
            pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((2, 1, nb, nb), lambda j, i: (0, j, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, nb, nb), lambda j, i: (i, j, 0, 0)),
            pl.BlockSpec((2, 1, nb, nb), lambda j, i: (0, j, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct(acc.shape, acc.dtype),
            jax.ShapeDtypeStruct(part.shape, part.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((2, nb, nb), part.dtype)],
    )(pan, urow, acc, w1, w2, part)
    return out, part_new
