"""Mixed-precision distributed solves: the fused f32-factor + f64-refine
engine behind the default mesh ``gesv``/``posv`` (ISSUE 8, SURVEY §2.4/§2.5
P8 at mesh scale).

The reference ships ``gesv_mixed``/``posv_mixed`` (f32 factor, f64
refinement, gesv_mixed.cc:16-44) as its high-performance solve tier.  On
TPU the gap is not a tier, it is the product: f64 getrf measures ~52 GF/s
against ~2 TF/s for f32 (pre-PR-1 figures), so refinement is how a distributed
f64 solve should run by default.  Three pieces live here:

- ``_ir_posv_jit`` / ``_ir_gesv_jit``: classic iterative refinement as ONE
  jitted on-device program — a ``lax.while_loop`` whose carry is the
  distributed solution/residual tile stacks plus the mesh-reduced norms,
  with the f32 triangular solves, the f64 (or Ozaki int8) residual SUMMA
  and the Inf-norm reductions all inlined in the loop body.  Zero host
  round-trips per iteration; the only readback is the final
  (x, iters, converged) at the driver.  (The predecessor ran a Python
  loop calling ``float(norm_dist(...))`` twice per step — one host sync
  per refinement iteration, and no opts threading at all.)
- ``Option.ResidualImpl``: the residual ``b - A x`` computed either by the
  plain f64 SUMMA (XLA's emulated f32-pair arithmetic on TPU) or by the
  Ozaki split-integer SUMMA (``summa.gemm_summa_ozaki`` — the int8 digit
  planes of A and X ride the unchanged broadcast schedule at
  slice_count/8 x the f64 panel bytes and run on the integer MXU).
- ``gesv_mixed_gmres_mesh`` / ``posv_mixed_gmres_mesh``: distributed
  left-preconditioned restarted GMRES — ``linalg.refine._gmres``'s
  static-shape Arnoldi with the operator application (SUMMA matvec) and
  the f32-factor preconditioner (mesh trsm sweeps) running on DistMatrix
  operands — the escalation tier between IR and the full-f64 fallback.

Routing (``Option.MixedPrecision``, resolve chain explicit >
``use_mixed`` context > ``SLATE_TPU_MIXED`` env > ``auto``): ``off`` keeps
``gesv_mesh``/``posv_mesh`` trace-identical to the direct f64 path;
``ir``/``gmres`` pin one tier; ``auto`` (the default) runs the ladder
IR -> GMRES-IR -> full-f64 fallback for real f64 inputs.  Convergence is
the reference's gate (refine.py): ||r|| <= ||x|| * ||A|| * eps * sqrt(n).
Every tier threads ``opts`` end-to-end, so the f32 factor gets ring
broadcasts (Option.BcastImpl), lookahead pipelining and ABFT
(Option.FaultTolerance) exactly like a direct factor call.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..linalg.refine import gate_cte, ir_count, ir_gauge
from ..obs import instrument
from ..types import (
    MethodGemm,
    Norm,
    Op,
    Option,
    Options,
    Uplo,
    Diag,
    get_option,
)
from .comm import resolve_bcast_impl
from .dist import DistMatrix, from_dense, padded_tiles, to_dense
from .dist_aux import norm_dist
from .dist_lu import permute_rows_dist
from .dist_trsm import trsm_dist
from .mesh import mesh_shape
from .summa import OzakiSplit, gemm_summa, gemm_summa_ozaki, \
    ozaki_presplit_cached

_DEFAULT_NB = 256

# ---------------------------------------------------------------------------
# Option.MixedPrecision / Option.ResidualImpl resolution (the
# comm.resolve_bcast_impl pattern: explicit > context > env > auto)
# ---------------------------------------------------------------------------

MIXED_MODES = ("off", "ir", "gmres", "auto")
MIXED_ENV = "SLATE_TPU_MIXED"
_MIXED_DEFAULT = [None]

RESIDUAL_IMPLS = ("f64", "ozaki", "auto")
RESIDUAL_ENV = "SLATE_TPU_RESIDUAL_IMPL"


def resolve_mixed(opts: Optional[Options] = None) -> str:
    """Resolved Option.MixedPrecision mode: explicit option >
    ``use_mixed`` context > ``SLATE_TPU_MIXED`` env > ``auto``."""
    mode = get_option(opts, Option.MixedPrecision)
    if mode is None:
        mode = _MIXED_DEFAULT[-1]
    if mode is None:
        mode = os.environ.get(MIXED_ENV) or "auto"
    mode = str(mode)
    if mode not in MIXED_MODES:
        raise ValueError(
            f"unknown mixed-precision mode {mode!r}; expected one of {MIXED_MODES}"
        )
    return mode


@contextlib.contextmanager
def use_mixed(mode: str):
    """Session-default mixed-precision mode for drivers called inside
    (tests / CI sweeps); an explicit Option.MixedPrecision still wins."""
    if mode not in MIXED_MODES:
        raise ValueError(
            f"unknown mixed-precision mode {mode!r}; expected one of {MIXED_MODES}"
        )
    _MIXED_DEFAULT.append(mode)
    try:
        yield
    finally:
        _MIXED_DEFAULT.pop()


def resolve_residual_impl(opts: Optional[Options] = None) -> str:
    """Resolved Option.ResidualImpl: explicit option >
    ``SLATE_TPU_RESIDUAL_IMPL`` env > auto (ozaki on a real TPU backend —
    where the int8 MXU is the fast path — f64 elsewhere)."""
    impl = get_option(opts, Option.ResidualImpl)
    if impl is None:
        impl = os.environ.get(RESIDUAL_ENV) or "auto"
    impl = str(impl)
    if impl not in RESIDUAL_IMPLS:
        raise ValueError(
            f"unknown residual impl {impl!r}; expected one of {RESIDUAL_IMPLS}"
        )
    if impl == "auto":
        from ..ops.matmul import _tpu_is_default

        return "ozaki" if _tpu_is_default() else "f64"
    return impl


def _la(opts):
    return get_option(opts, Option.Lookahead)


def _max_iter(opts, max_iter=None) -> int:
    if max_iter is not None:
        return int(max_iter)
    return int(get_option(opts, Option.MaxIterations, 30))


def _astype_dist(d: DistMatrix, dtype) -> DistMatrix:
    return DistMatrix(tiles=d.tiles.astype(dtype), m=d.m, n=d.n, nb=d.nb,
                      mesh=d.mesh, diag_pad=d.diag_pad)


def _require_f64(a: jax.Array, who: str) -> None:
    if a.dtype != jnp.float64:
        raise TypeError(
            f"{who} is the f32-factor + f64-refine path and requires float64 "
            f"input, got {a.dtype}; complex/f32 solves use the direct drivers"
        )


def residual_comm_bytes(
    mt: int, ntb: int, kt: int, nb: int, p: int, q: int,
    bcast_impl: Optional[str] = None, residual_impl: str = "f64",
    n_slices: int = 9,
) -> int:
    """Analytic audited comm bytes of ONE residual SUMMA over the
    refinement loop's operands (A (mt x kt tiles) against X (kt x ntb
    tiles)): the plain GemmC broadcast volume with the per-impl factor of
    tests/test_comm_audit.py, times the payload itemsize — 8 B/elem for
    the f64 panels, ``n_slices`` B/elem for the int8 digit planes (the
    slice-count x plain-volume factor).  Used for the ``ir.*`` metrics
    and proven against the traced audit in tests/test_mixed_mesh.py."""
    itemsize = n_slices if residual_impl == "ozaki" else 8
    mtl, ntl = mt // p, ntb // q
    a_bytes = mtl * nb * nb * itemsize
    b_bytes = ntl * nb * nb * itemsize
    if resolve_bcast_impl(bcast_impl) == "psum":
        return kt * (a_bytes + b_bytes)
    return kt * ((q - 1) * a_bytes + (p - 1) * b_bytes)


# ---------------------------------------------------------------------------
# The fused refinement program: lax.while_loop over distributed tiles with
# mesh-reduced norms in the carry; donated RHS buffer; zero host syncs.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _inf_norm_pair_jit(rt, xt, mesh, p, q, m_true, n_true):
    """Inf-norms of TWO same-shape tile stacks in ONE shard_map kernel —
    the refinement loop's (||r||, ||x||) carry update.  One kernel call
    per iteration keeps the mesh-reduction count minimal AND gives the
    trace-time comm audit a record per collective call site (a second
    ``_norm_jit`` call would be a jit-cache hit: eqns in the loop body,
    no audit records — the slate_lint loop-audit contract)."""
    from jax.sharding import PartitionSpec as P

    from .comm import local_indices, psum_a, shard_map_compat
    from .mesh import COL_AXIS, ROW_AXIS

    spec = P(ROW_AXIS, COL_AXIS)

    def kernel(r_loc, x_loc):
        mtl, ntl, nb, _ = r_loc.shape
        _r, _c, i_log, j_log = local_indices(p, q, mtl, ntl)
        gr = i_log[:, None, None, None] * nb + jnp.arange(nb)[None, None, :, None]
        gc = j_log[None, :, None, None] * nb + jnp.arange(nb)[None, None, None, :]
        mask = (gr < m_true) & (gc < n_true)
        st = jnp.stack([r_loc, x_loc])            # (2, mtl, ntl, nb, nb)
        absa = jnp.where(mask[None], jnp.abs(st), 0)
        rowsums = jnp.sum(absa, axis=(2, 4))      # (2, mtl, nb)
        rowsums = psum_a(rowsums, COL_AXIS)
        out = jnp.max(rowsums, axis=(1, 2))       # (2,)
        out = lax.pmax(out, ROW_AXIS)
        out = lax.pmax(out, COL_AXIS)
        return out[None, None]

    out = shard_map_compat(
        kernel, mesh=mesh, in_specs=(spec, spec), out_specs=P(ROW_AXIS, COL_AXIS),
        check_vma=False,
    )(rt, xt)
    return out[0, 0, 0], out[0, 0, 1]


def _ir_common(ad: DistMatrix, bd: DistMatrix, lo_solve, info,
               max_iter: int, la, bi: str, ri: str, nm: bool = False,
               qa=None, ea=None):
    """Shared refinement body over a factored low-precision solve.

    ``lo_solve(rd) -> DistMatrix`` applies the f32 factor to a distributed
    RHS and returns the f64 upcast.  Returns (x_tiles, r_tiles, iters,
    converged, rnorm, xnorm) — all device values; a failed factor
    (info != 0) skips the loop and NaN-fills x so misuse fails loudly.
    ``nm`` (Option.NumMonitor resolved) additionally carries a fixed-size
    (max_iter + 1, 2) history buffer of the per-iteration (||r||, ||x||)
    pair through the while_loop — the convergence TRAJECTORY, read back
    once at exit (rows never reached stay NaN); ``nm=False`` is
    jaxpr-identical to the unmonitored program and returns no buffer.

    Loop structure: the initial f32 solve IS the first ``lax.while_loop``
    trip (carry starts at x = 0, r = b, it = -1), so every distributed
    kernel — the f32 triangular sweeps, the residual SUMMA, the fused
    norm pair — has exactly ONE call site, inside the loop body.  That is
    both the audit contract (a second call site would be a jit-cache hit:
    counted eqns with no records) and what keeps the traced program
    minimal.  ``iters`` keeps the reference semantics: the number of
    CORRECTION steps after the initial solve (0 = converged at once)."""
    from .comm import audit_scope, phase_scope

    dtype = ad.tiles.dtype
    n = ad.m
    p, q = mesh_shape(ad.mesh)
    anorm = norm_dist(Norm.Inf, ad)
    cte = gate_cte(anorm, n, dtype)
    ok = info == 0

    def wrap(t, like):
        return DistMatrix(tiles=t, m=like.m, n=like.n, nb=like.nb,
                          mesh=like.mesh, diag_pad=like.diag_pad)

    def residual(x_t):
        if ri == "ozaki":
            # A's digit planes ride in as loop-invariant operands
            # (ozaki_presplit): the stationary operand is split ONCE per
            # request — and, through the buffer-identity cache, once per
            # OPERATOR — instead of once per refinement iteration
            split = None if qa is None else OzakiSplit(qa=qa, ea=ea)
            return gemm_summa_ozaki(-1.0, ad, wrap(x_t, bd), 1.0, bd,
                                    lookahead=la, bcast_impl=bi,
                                    a_split=split).tiles
        return gemm_summa(-1.0, ad, wrap(x_t, bd), 1.0, bd,
                          method=MethodGemm.GemmC, lookahead=la,
                          bcast_impl=bi).tiles

    def cond(state):
        it, done = state[4], state[5]
        return ok & (~done) & (it < max_iter)

    def body(state):
        x_t, r_t, _rn, _xn, it, _done = state[:6]
        with phase_scope("correct"):
            d = lo_solve(wrap(r_t, bd)).tiles
        x_t = x_t + d
        with phase_scope("residual"):
            r_t = residual(x_t)
        rn, xn = _inf_norm_pair_jit(r_t, x_t, ad.mesh, p, q, bd.m, bd.n)
        out = (x_t, r_t, rn, xn, it + 1, rn <= xn * cte)
        if nm:
            # trajectory buffer rides the carry: row it+1 (the trip the
            # initial solve counts as trip 0) gets this trip's norm pair
            hist = lax.dynamic_update_slice_in_dim(
                state[6], jnp.stack([rn, xn])[None], it + 1, axis=0)
            out = out + (hist,)
        return out

    # audit_scope(max_iter + 1): the while trip count is dynamic, so the
    # trace-time comm audit records the refinement loop's collectives at
    # the worst-case multiplicity (the lint loop-audit contract; the ir.*
    # metrics scale the per-iteration volume by the MEASURED iters)
    rdt = jnp.real(jnp.zeros((), dtype)).dtype
    init = (jnp.zeros_like(bd.tiles), bd.tiles, jnp.asarray(jnp.inf, rdt),
            jnp.zeros((), rdt), jnp.int32(-1), jnp.zeros((), bool))
    if nm:
        init = init + (jnp.full((max_iter + 1, 2), jnp.nan, rdt),)
    with audit_scope(max_iter + 1):
        out = lax.while_loop(cond, body, init)
    x_t, r_t, rn, xn, iters, done = out[:6]
    x_t = jnp.where(ok, x_t, jnp.full_like(x_t, jnp.nan))
    if nm:
        return x_t, r_t, iters, done & ok, rn, xn, out[6]
    return x_t, r_t, iters, done & ok, rn, xn


@functools.partial(
    jax.jit,
    static_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14),
    donate_argnums=(1,),
)
def _ir_posv_jit(at, bt, lt, info, mesh, p, q, m, nrhs, nb,
                 max_iter, la, bi, ri, nm=False, qa=None, ea=None):
    ad = DistMatrix(tiles=at, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)
    bd = DistMatrix(tiles=bt, m=m, n=nrhs, nb=nb, mesh=mesh, diag_pad=False)
    ld = DistMatrix(tiles=lt, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)

    def lo_solve(rd: DistMatrix) -> DistMatrix:
        r32 = _astype_dist(rd, jnp.float32)
        y = trsm_dist(ld, r32, Uplo.Lower, Op.NoTrans, lookahead=la,
                      bcast_impl=bi)
        x = trsm_dist(ld, y, Uplo.Lower, Op.ConjTrans, lookahead=la,
                      bcast_impl=bi)
        return _astype_dist(x, at.dtype)

    return _ir_common(ad, bd, lo_solve, info, max_iter, la, bi, ri, nm,
                      qa, ea)


@functools.partial(
    jax.jit,
    static_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    donate_argnums=(1,),
)
def _ir_gesv_jit(at, bt, lut, perm, info, mesh, p, q, m, nrhs, nb,
                 max_iter, la, bi, ri, nm=False, qa=None, ea=None):
    ad = DistMatrix(tiles=at, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)
    bd = DistMatrix(tiles=bt, m=m, n=nrhs, nb=nb, mesh=mesh, diag_pad=False)
    lud = DistMatrix(tiles=lut, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)

    def lo_solve(rd: DistMatrix) -> DistMatrix:
        r32 = _astype_dist(rd, jnp.float32)
        pr = permute_rows_dist(r32, perm)
        y = trsm_dist(lud, pr, Uplo.Lower, Op.NoTrans, Diag.Unit,
                      lookahead=la, bcast_impl=bi)
        x = trsm_dist(lud, y, Uplo.Upper, Op.NoTrans, lookahead=la,
                      bcast_impl=bi)
        return _astype_dist(x, at.dtype)

    return _ir_common(ad, bd, lo_solve, info, max_iter, la, bi, ri, nm,
                      qa, ea)


def _factor_f32(kind: str, a: jax.Array, mesh: Mesh, nb: int, opts):
    """The f32 mesh factor with ``opts`` threaded end-to-end: the factor
    drivers consume Option.Lookahead, Option.BcastImpl and
    Option.FaultTolerance exactly as a direct f32 call would (the
    whole point of the rebuild — the old facade factored bare)."""
    from .drivers import getrf_mesh, potrf_mesh

    a32 = a.astype(jnp.float32)
    if kind == "posv":
        l, info = potrf_mesh(a32, mesh, nb, opts)
        return l, None, info
    lu, perm, info = getrf_mesh(a32, mesh, nb, opts)
    return lu, perm, info


# stationary-operator prefactor memo (the serving case: ONE operator,
# a stream of right-hand sides): keyed on the dense operand's buffer
# identity + the factor-relevant config, holding a strong reference to
# the key array so its id cannot be recycled while the entry lives.
# Each entry holds the dense A, the distributed f64 A and its f32
# factor (~2.5 matrix copies), so residency is bounded two ways: the
# entry cap, and a per-operand byte ceiling — a large one-shot solve
# near the HBM ceiling must NOT have its buffers pinned by a serving
# cache it never asked for (the 256-4096 serving bins all fit under
# the default 256 MiB; SLATE_TPU_PREFACTOR_CACHE_MAX_BYTES overrides,
# 0 disables the memo entirely).
_PREFACTOR_MEMO: dict = {}
_PREFACTOR_ORDER: list = []
_PREFACTOR_CAP = 4
_PREFACTOR_MAX_BYTES_ENV = "SLATE_TPU_PREFACTOR_CACHE_MAX_BYTES"


def _prefactor_max_bytes() -> int:
    try:
        return int(float(os.environ.get(_PREFACTOR_MAX_BYTES_ENV, "") or
                         (1 << 28)))
    except ValueError:
        return 1 << 28


def clear_prefactor_cache() -> None:
    _PREFACTOR_MEMO.clear()
    _PREFACTOR_ORDER.clear()


def _prefactor_cached(kind: str, a: jax.Array, mesh: Mesh, nb: int, opts):
    """``_prefactor`` memoized on ``id(a)``: repeated routed solves
    against the SAME dense operand object (the stationary-A serving
    stream) reuse the f32 factor, the distributed f64 A — and, through
    ``ozaki_presplit_cached`` keying on the reused ad.tiles buffer, the
    Ozaki digit planes — instead of re-running the O(n^3) factor per
    request.  Tracers bypass the memo (host caching is runtime-only)."""
    if isinstance(a, jax.core.Tracer) or a.nbytes > _prefactor_max_bytes():
        return _prefactor(kind, a, mesh, nb, opts)
    from ..serve.cache import options_signature

    key = (id(a), kind, id(mesh), nb, options_signature(opts))
    hit = _PREFACTOR_MEMO.get(key)
    if hit is not None and hit[0] is a:
        return hit[1]
    pre = _prefactor(kind, a, mesh, nb, opts)
    _PREFACTOR_MEMO[key] = (a, pre)
    _PREFACTOR_ORDER.append(key)
    while len(_PREFACTOR_ORDER) > _PREFACTOR_CAP:
        _PREFACTOR_MEMO.pop(_PREFACTOR_ORDER.pop(0), None)
    return pre


def _prefactor(kind: str, a: jax.Array, mesh: Mesh, nb: int, opts):
    """(fact, perm, info, ad): the f32 factor plus the distributed f64 A.
    Computed once per routed solve and SHARED down the ladder — the
    GMRES escalation tier preconditions with the exact factor the IR
    tier just computed, never re-running the O(n^3) factorization on
    the (ill-conditioned, i.e. slowest) inputs that escalate."""
    if kind == "posv":
        # the potrf contract reads only the lower triangle (upper tile
        # ignored — dist_chol.potrf_dist), so lower-only storage is a
        # valid posv input; the refinement residual reads BOTH triangles,
        # so mirror the lower one first (refine.posv_mixed_array's
        # symmetrize at mesh scale; real f64 only, no conjugation).  For
        # a full symmetric array this is the bitwise identity.
        a = jnp.tril(a) + jnp.tril(a, -1).T
    fact, perm, info = _factor_f32(kind, a, mesh, nb, opts)
    ad = from_dense(a, mesh, nb, diag_pad_one=True)
    return fact, perm, info, ad


def _mixed_ir_solve(kind: str, a: jax.Array, b: jax.Array, mesh: Mesh,
                    nb: int, max_iter, opts, pre=None):
    """Factor + fused refinement; returns (x_dense, iters, converged,
    rnorm, xnorm, info, resid_bytes_per_iter, history) with
    iters/converged still on device.  ``history`` is the carried
    (||r||, ||x||) trajectory buffer under Option.NumMonitor=on, else
    None (the monitored program is a distinct static variant; off is
    jaxpr-identical to the pre-monitoring kernel)."""
    from ..obs import flight as _flight
    from ..obs import numerics as _num

    p, q = mesh_shape(mesh)
    la = _la(opts)
    bi = resolve_bcast_impl(get_option(opts, Option.BcastImpl))
    ri = resolve_residual_impl(opts)
    mi = _max_iter(opts, max_iter)
    nm = _num.resolve_num_monitor(_num.monitor_from_opts(opts)) == "on"
    fact, perm, info, ad = pre if pre is not None else _prefactor_cached(
        kind, a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    # the step-level flight recorder cannot descend into a fused
    # while_loop (its per-phase dispatches are host-driven); the factor
    # above records normally, the refinement runs as the one fused program
    # stationary-A digit planes: split once per operator (buffer-identity
    # cached) instead of once per refinement ITERATION — the planes enter
    # the fused program as loop-invariant operands (summa.ozaki_presplit)
    split = ozaki_presplit_cached(ad) if ri == "ozaki" else None
    qa, ea = (split.qa, split.ea) if split is not None else (None, None)
    with _flight.no_flight():
        if kind == "posv":
            out = _ir_posv_jit(
                ad.tiles, bd.tiles, fact.tiles, info, mesh, p, q, ad.m,
                bd.n, nb, mi, la, bi, ri, nm, qa, ea,
            )
        else:
            out = _ir_gesv_jit(
                ad.tiles, bd.tiles, fact.tiles, perm, info, mesh, p, q,
                ad.m, bd.n, nb, mi, la, bi, ri, nm, qa, ea,
            )
    x_t, _r_t, iters, conv, rn, xn = out[:6]
    hist = out[6] if nm else None
    xd = DistMatrix(tiles=x_t, m=bd.m, n=bd.n, nb=nb, mesh=mesh)
    per_iter = float(residual_comm_bytes(
        ad.tiles.shape[0], bd.tiles.shape[1], ad.nt, nb, p, q, bi, ri))
    return to_dense(xd), iters, conv, rn, xn, info, per_iter, hist


@instrument("posv_mixed_mesh")
def posv_mixed_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    max_iter: Optional[int] = None, opts: Optional[Options] = None,
    pre=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed SPD solve, f32 mesh factor + fused f64 mesh refinement
    (src/posv_mixed.cc).  Returns (x, iters, info); iters = -1 means the
    refinement did not converge (or the factor failed — x is then
    NaN-filled) and the caller should escalate (GMRES-IR / full f64).
    ``a`` holds the lower triangle (upper ignored, the potrf_mesh
    contract — the residual gemm reads the lower triangle mirrored; see
    ``_prefactor``).  ``pre`` is the routing ladder's shared
    ``_prefactor`` result (internal)."""
    _require_f64(a, "posv_mixed_mesh")
    x, raw_iters, conv, rn, xn, info, per_iter, hist = _mixed_ir_solve(
        "posv", a, b, mesh, nb, max_iter, opts, pre
    )
    iters = jnp.where(conv, raw_iters, -1).astype(jnp.int32)
    _record_ir("posv", iters, raw_iters, rn, xn, per_iter, hist)
    return x, iters, jnp.asarray(info, jnp.int32)


@instrument("gesv_mixed_mesh")
def gesv_mixed_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    max_iter: Optional[int] = None, opts: Optional[Options] = None,
    pre=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed general solve, f32 partial-pivot mesh factor + fused
    f64 mesh refinement (src/gesv_mixed.cc:16-44).  Returns
    (x, iters, info); see posv_mixed_mesh."""
    _require_f64(a, "gesv_mixed_mesh")
    x, raw_iters, conv, rn, xn, info, per_iter, hist = _mixed_ir_solve(
        "gesv", a, b, mesh, nb, max_iter, opts, pre
    )
    iters = jnp.where(conv, raw_iters, -1).astype(jnp.int32)
    _record_ir("gesv", iters, raw_iters, rn, xn, per_iter, hist)
    return x, iters, jnp.asarray(info, jnp.int32)


def _record_ir(kind: str, iters, raw_iters, rnorm, xnorm, per_iter,
               hist=None) -> None:
    """The ir.* observability surface (always-on, like the ft.* counters):
    per-solve gauges + the totals obs.report gates.  One host readback —
    the final (iters, norms) the drivers return anyway.  Under tracing
    (slate_lint's make_jaxpr over the registry) the values are tracers and
    the readback is skipped — metrics are a runtime surface.

    ``raw_iters`` is the pre-convergence-masking trip counter: the loop
    ran raw_iters + 1 residual SUMMAs (-1 = failed factor, loop never
    entered), so the residual comm bytes scale by the MEASURED trips."""
    try:
        it = int(iters)
        raw = int(raw_iters)
    except (jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        return
    ir_count("ir.solves", kind)
    ir_gauge("ir.iters", max(it, 0), kind)
    ir_gauge("ir.rnorm", float(rnorm), kind)
    ir_gauge("ir.xnorm", float(xnorm), kind)
    ir_count("ir.iters_total", kind, max(it, 0))
    ir_count("ir.residual_gemm_bytes", kind, per_iter * (raw + 1))
    if it >= 0:
        ir_count("ir.converged", kind)
    if hist is not None:
        # the carried (||r||, ||x||) trajectory (Option.NumMonitor=on):
        # lands as the ir.residual_history gauge series so a stalling-
        # but-eventually-converging solve is distinguishable from a
        # healthy one in the RunReport (ISSUE 10 satellite)
        from ..obs import numerics as _num

        _num.record_ir_history(kind, hist, raw)


# ---------------------------------------------------------------------------
# Distributed GMRES-IR (src/gesv_mixed_gmres.cc at mesh scale): the
# refine._gmres Arnoldi with DistMatrix operator/preconditioner application.
# The Krylov basis is an O(n (restart+1)) replicated buffer; the O(n^2)
# work (matvec, triangular sweeps) runs distributed.
# ---------------------------------------------------------------------------


def _vec_to_tiles(v, m, nb, p, q, mt, ntv):
    """Dense (m,) vector -> the cyclic tile stack of an (m, 1) DistMatrix
    (traceable: pure reshape/permutation, no host round trip)."""
    from ..core.tiling import to_cyclic, to_tiles

    x = jnp.zeros((mt * nb, ntv * nb), v.dtype).at[: v.shape[0], 0].set(v)
    return to_cyclic(to_tiles(x, nb), p, q)


def _tiles_to_vec(t, m, p, q):
    from ..core.tiling import from_cyclic, from_tiles

    return from_tiles(from_cyclic(t, p, q), m, 1)[:, 0]


def _gmres_dist(pm_resid, b, restart: int, tol, max_restarts: int):
    """Left-preconditioned restarted GMRES with the distributed operator
    applied at exactly ONE call site.

    ``pm_resid(v, c) -> M^-1 (c - A v)`` is the preconditioned-residual
    verb (the mesh trsm sweeps + SUMMA matvec).  The flat inner loop
    j = 0..restart folds the per-restart initial residual into the
    Arnoldi recurrence: j = 0 evaluates ``pm_resid(x, b)`` (the restart's
    TRUE preconditioned residual — normalized into V[0] and, crucially,
    the convergence measurement); j >= 1 evaluates ``-pm_resid(V[j-1],
    0) = M^-1 A V[j-1]`` (the next Krylov vector).  One call site means
    one copy of the distributed kernels in the traced program — the
    jit-cache/audit contract ``refine._gmres``'s three call sites cannot
    satisfy.

    Stopping is on MEASURED residuals only: with an f32 preconditioner
    the in-cycle least-squares estimate ||beta e1 - H y|| is
    systematically optimistic (Arnoldi orthogonality decays at eps32, so
    the estimate can read 1e-16 while the true residual sits at 1e-7 —
    observed), so each restart first measures ||M^-1 (b - A x)|| and the
    loop stops when THAT meets tol.  A converged solve pays exactly one
    extra matvec: the measuring cycle's j >= 1 steps and its update are
    gated off by ``lax.cond``/masking once beta <= tol.  Runs
    max_restarts + 1 cycles so the final update gets measured; a solve
    still unconverged at the budget reports the last measured rnorm
    (conservative: its final update is unmeasured)."""
    from ..ops.matmul import matmul

    n = b.shape[0]
    dtype = b.dtype
    m = restart
    rdt = jnp.real(b).dtype

    def restart_body(i, carry):
        x, rnorm, stop = carry

        def do(x):
            V0 = jnp.zeros((m + 1, n), dtype)
            H0 = jnp.zeros((m + 1, m), dtype)

            def inner(j, st):
                V, H, beta = st
                is0 = j == 0
                # once the j=0 measurement converged, later j skip the
                # operator entirely (the cond's false branch is free)
                active = is0 | (beta > tol)
                jm1 = jnp.maximum(j - 1, 0)
                u = jnp.where(is0, x, V[jm1])
                c = jnp.where(is0, b, jnp.zeros_like(b))
                out = lax.cond(active, lambda uc: pm_resid(*uc),
                               lambda uc: jnp.zeros_like(b), (u, c))
                r0 = out                        # j=0: M^-1 (b - A x)
                w = -out                        # j>=1: M^-1 A V[j-1]
                # j = 0: normalize the residual into V[0]
                b0 = jnp.linalg.norm(r0)
                v0 = r0 / jnp.where(b0 == 0, 1, b0)
                # j >= 1: modified Gram-Schmidt against rows <= j-1
                h = matmul(jnp.conj(V), w[:, None])[:, 0]
                h = h * (jnp.arange(m + 1) <= j - 1).astype(dtype)
                wg = w - matmul(h[None, :], V)[0]
                hn = jnp.linalg.norm(wg)
                vj = wg / jnp.where(hn == 0, 1, hn)
                V = V.at[j].set(jnp.where(is0, v0, jnp.where(active, vj, V[j])))
                Hupd = H.at[:, jm1].set(h + 0).at[j, jm1].set(hn.astype(dtype))
                H = jnp.where(is0 | ~active, H, Hupd)
                return V, H, jnp.where(is0, b0.astype(rdt), beta)

            V, H, beta = lax.fori_loop(
                0, m + 1, inner, (V0, H0, jnp.zeros((), rdt))
            )
            improve = beta > tol
            e1 = jnp.zeros(m + 1, dtype).at[0].set(beta.astype(dtype))
            y = jnp.linalg.lstsq(H, e1)[0]
            upd = matmul(y[None, :], V[:m])[0]
            x = x + jnp.where(improve, upd, jnp.zeros_like(upd))
            return x, beta, ~improve  # stop once a measurement meets tol

        return lax.cond(~stop, do, lambda xx: (xx, rnorm, stop), x)

    x, rnorm, _stop = lax.fori_loop(
        0, max_restarts + 1, restart_body,
        (jnp.zeros_like(b), jnp.asarray(jnp.inf, rdt), jnp.zeros((), bool)),
    )
    return x, rnorm


def _gmres_mesh_common(ad, fact_solve, bcol, restart, max_restarts, la, bi):
    """Left-preconditioned restarted GMRES on one RHS column with the
    operator and preconditioner applied on the mesh."""
    m = ad.m
    p, q = mesh_shape(ad.mesh)
    mt, ntv = ad.tiles.shape[0], padded_tiles(1, ad.nb, ad.mesh)
    dtype = ad.tiles.dtype

    def wrap(t):
        return DistMatrix(tiles=t, m=m, n=1, nb=ad.nb, mesh=ad.mesh)

    def pm_resid(v, c):
        # M^-1 (c - A v): SUMMA matvec + f32 factor sweeps, fused so the
        # whole distributed pipeline is one call site (see _gmres_dist)
        xd = wrap(_vec_to_tiles(v, m, ad.nb, p, q, mt, ntv))
        cd = wrap(_vec_to_tiles(c, m, ad.nb, p, q, mt, ntv))
        rd = gemm_summa(-1.0, ad, xd, 1.0, cd, method=MethodGemm.GemmC,
                        lookahead=la, bcast_impl=bi)
        out = fact_solve(rd)
        return _tiles_to_vec(out.tiles, m, p, q).astype(dtype)

    eps = jnp.finfo(dtype).eps
    tol = (eps * jnp.sqrt(jnp.asarray(float(m), dtype))
           * jnp.linalg.norm(bcol)).astype(dtype)
    from .comm import audit_scope

    # worst-case trip product of the restart x Arnoldi loops: the single
    # pm_resid call site sits inside both fori bodies — max_restarts + 1
    # cycles (the +1 is the final measuring cycle) of restart + 1 inner
    # steps — so the trace-time comm audit records its collectives at
    # the (dynamically unknowable) upper bound, the lint loop-audit
    # contract for dynamic-trip loops
    with audit_scope((max_restarts + 1) * (restart + 1)):
        x, rnorm = _gmres_dist(pm_resid, bcol, restart, tol, max_restarts)
    return x, rnorm, rnorm <= tol


@functools.partial(jax.jit, static_argnums=tuple(range(4, 13)))
def _gmres_posv_jit(at, bcol, lt, info, mesh, p, q, m, nb,
                    restart, max_restarts, la=None, bi="auto"):
    ad = DistMatrix(tiles=at, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)
    ld = DistMatrix(tiles=lt, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)

    def fact_solve(rd):
        r32 = _astype_dist(rd, jnp.float32)
        y = trsm_dist(ld, r32, Uplo.Lower, Op.NoTrans, lookahead=la,
                      bcast_impl=bi)
        return trsm_dist(ld, y, Uplo.Lower, Op.ConjTrans, lookahead=la,
                         bcast_impl=bi)

    x, rnorm, conv = _gmres_mesh_common(ad, fact_solve, bcol, restart,
                                        max_restarts, la, bi)
    bad = info != 0
    return jnp.where(bad, jnp.nan, x), rnorm, conv & ~bad


@functools.partial(jax.jit, static_argnums=tuple(range(5, 14)))
def _gmres_gesv_jit(at, bcol, lut, perm, info, mesh, p, q, m, nb,
                    restart, max_restarts, la=None, bi="auto"):
    ad = DistMatrix(tiles=at, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)
    lud = DistMatrix(tiles=lut, m=m, n=m, nb=nb, mesh=mesh, diag_pad=True)

    def fact_solve(rd):
        r32 = _astype_dist(rd, jnp.float32)
        pr = permute_rows_dist(r32, perm)
        y = trsm_dist(lud, pr, Uplo.Lower, Op.NoTrans, Diag.Unit,
                      lookahead=la, bcast_impl=bi)
        return trsm_dist(lud, y, Uplo.Upper, Op.NoTrans, lookahead=la,
                         bcast_impl=bi)

    x, rnorm, conv = _gmres_mesh_common(ad, fact_solve, bcol, restart,
                                        max_restarts, la, bi)
    bad = info != 0
    return jnp.where(bad, jnp.nan, x), rnorm, conv & ~bad


def _mixed_gmres_solve(kind: str, a, b, mesh, nb, opts, restart, pre=None):
    """Factor + per-column distributed GMRES.  Returns (x, rnorm,
    converged_all, info); the column loop reuses one compiled program.
    ``pre`` is the routing ladder's shared ``_prefactor`` result."""
    from ..obs import flight as _flight

    p, q = mesh_shape(mesh)
    la = _la(opts)
    bi = resolve_bcast_impl(get_option(opts, Option.BcastImpl))
    max_restarts = _max_iter(opts, None)
    from .comm import audit_scope

    fact, perm, info, ad = pre if pre is not None else _prefactor_cached(
        kind, a, mesh, nb, opts)
    b2 = b if b.ndim == 2 else b[:, None]
    cols, rnorms, convs = [], [], []
    # columns after the first are jit-cache hits (one compiled program);
    # the scope keeps the trace-time audit honest about the total volume
    with _flight.no_flight(), audit_scope(b2.shape[1]):
        for j in range(b2.shape[1]):
            if kind == "posv":
                x, rn, cv = _gmres_posv_jit(
                    ad.tiles, b2[:, j], fact.tiles, info, mesh, p, q, ad.m,
                    nb, restart, max_restarts, la, bi,
                )
            else:
                x, rn, cv = _gmres_gesv_jit(
                    ad.tiles, b2[:, j], fact.tiles, perm, info, mesh, p, q,
                    ad.m, nb, restart, max_restarts, la, bi,
                )
            cols.append(x)
            rnorms.append(rn)
            convs.append(cv)
    x = jnp.stack(cols, axis=1) if b.ndim == 2 else cols[0]
    rnorm = jnp.max(jnp.stack(rnorms))
    conv = jnp.all(jnp.stack(convs))
    if not isinstance(conv, jax.core.Tracer):  # metrics are a runtime
        ir_count("ir.gmres_solves", kind)      # surface (see _record_ir)
    return x, rnorm, conv, info


@instrument("posv_mixed_gmres_mesh")
def posv_mixed_gmres_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None, restart: int = 30,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed GMRES-IR SPD solve (src/posv_mixed_gmres.cc at mesh
    scale): f32 mesh Cholesky preconditioning f64 restarted GMRES.
    Returns (x, rnorm, info); converged when rnorm <= eps*sqrt(n)*||b||
    per column (the refine.py tolerance)."""
    _require_f64(a, "posv_mixed_gmres_mesh")
    x, rnorm, _conv, info = _mixed_gmres_solve("posv", a, b, mesh, nb, opts,
                                               restart)
    return x, rnorm, jnp.asarray(info, jnp.int32)


@instrument("gesv_mixed_gmres_mesh")
def gesv_mixed_gmres_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None, restart: int = 30,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Distributed GMRES-IR general solve (src/gesv_mixed_gmres.cc at
    mesh scale): f32 partial-pivot LU preconditioning f64 restarted
    GMRES.  Returns (x, rnorm, info)."""
    _require_f64(a, "gesv_mixed_gmres_mesh")
    x, rnorm, _conv, info = _mixed_gmres_solve("gesv", a, b, mesh, nb, opts,
                                               restart)
    return x, rnorm, jnp.asarray(info, jnp.int32)


# ---------------------------------------------------------------------------
# Default routing: the Option.MixedPrecision ladder behind gesv_mesh /
# posv_mesh.  IR -> GMRES-IR -> full-f64 fallback; each readback is one
# host sync BETWEEN programs (never inside a loop).
# ---------------------------------------------------------------------------


def _route_health(kind, pre, opts) -> bool:
    """The measured-health entry-tier decision for ``MixedPrecision=auto``
    under Option.NumMonitor=on: read the monitored f32 factor's in-carry
    gauges (element growth / Cholesky diagonal margin — already recorded
    by the factor kernel), run the distributed Hager-Higham condition
    estimate over the factored tiles (dist_aux.gecondest_dist /
    pocondest_dist: ~2*iters+1 single-column mesh trsm solve pairs), and
    return True when the input sits in the IR-cannot-converge regime so
    the ladder enters at GMRES-IR."""
    from ..obs import numerics as _num
    from .dist_aux import gecondest_dist, pocondest_dist

    fact, perm, info, ad = pre
    try:
        if int(info) != 0:
            return False  # failed factor: the existing NaN/fallback path
    except (jax.errors.TracerIntegerConversionError,
            jax.errors.ConcretizationTypeError):
        return False
    la = _la(opts)
    bi = get_option(opts, Option.BcastImpl)
    gauges = _num.last_gauges("potrf" if kind == "posv" else "getrf_pp")
    anorm = norm_dist(Norm.One, ad)
    if kind == "posv":
        rcond = pocondest_dist(fact, anorm, lookahead=la, bcast_impl=bi)
    else:
        rcond = gecondest_dist(fact, perm, anorm, lookahead=la,
                               bcast_impl=bi)
    if _num.route_entry_tier(kind, gauges, float(rcond)):
        _num.record_routed_gmres(kind)
        return True
    return False


def mixed_mesh_route(kind, a, b, mesh, nb, opts, plain_fn):
    """Route an f64 ``gesv_mesh``/``posv_mesh`` call through the mixed
    ladder per the resolved Option.MixedPrecision.  Returns (x, info), or
    None when the direct path should run (mode off, non-f64 dtype, a
    non-2D RHS, or TRACED operands) — all decided before any tracing, so
    the direct path's jaxpr is untouched (asserted in
    tests/test_mixed_mesh.py).

    The ladder is host-DRIVEN by design: each tier is a fused on-device
    program, but the tier-to-tier decision (converged? escalate?) is one
    scalar readback between programs.  Under an outer jit/vmap/make_jaxpr
    there is no host between programs, so traced calls keep the direct
    f64 path — which is also exactly the pre-mixed trace semantics of
    the public drivers (a user jitting gesv_mesh gets the same jaxpr as
    before this routing existed; the mixed tiers are reachable under
    jit via the explicit ``*_mixed_mesh`` drivers' fused programs).

    Health-aware entry tier (ISSUE 10): under Option.NumMonitor=on (auto
    = on when the obs layer is enabled) the f32 factor runs MONITORED —
    its element-growth / diagonal-margin gauges ride the k-loop carry —
    and ``auto`` mode additionally runs a distributed Hager-Higham
    condition estimate over the just-computed factor (a handful of mesh
    trsm solves on one column, no O(n^3)).  Pathological health —
    growth above numerics.GROWTH_THRESHOLD or cond(A) above
    numerics.CONDEST_THRESHOLD, the regime where classic IR on an f32
    factor is known to stall (Carson & Higham 2018) — skips the IR tier
    entirely and enters at GMRES-IR (``num.routed_gmres``), instead of
    burning max_iter refinement iterations to learn the same fact."""
    mode = resolve_mixed(opts)
    if (mode == "off" or getattr(a, "dtype", None) != jnp.float64
            or getattr(b, "ndim", 0) != 2
            or isinstance(a, jax.core.Tracer)
            or isinstance(b, jax.core.Tracer)):
        return None
    from ..obs import driver_span
    from ..obs import numerics as _num

    nm_on = _num.resolve_num_monitor(_num.monitor_from_opts(opts)) == "on"
    if nm_on:
        # pin the resolved mode into the opts every tier consumes, so the
        # f32 factor's k-loop carries the gauges the router reads
        opts = dict(opts or {})
        opts[Option.NumMonitor] = "on"
    drv = posv_mixed_mesh if kind == "posv" else gesv_mixed_mesh
    with driver_span(f"{kind}_mixed", mode=mode) as sp:
        # one f32 factor for the whole ladder: the GMRES tier
        # preconditions with the exact factor the IR tier refined on.
        # Clear the op's last-gauge entry first so the router only ever
        # reads THIS factor's health — a factor path that records no
        # gauges (e.g. Option.FaultTolerance routes to the ABFT kernels,
        # which carry no monitor) yields an empty dict and the routing
        # decision falls back to the condest alone
        if nm_on:
            _num.clear_last("potrf" if kind == "posv" else "getrf_pp")
        pre = _prefactor_cached(kind, a, mesh, nb, opts)
        skip_ir = False
        if nm_on and mode == "auto":
            with sp.phase("health"):
                skip_ir = _route_health(kind, pre, opts)
        if mode in ("ir", "auto") and not skip_ir:
            with sp.phase("ir"):
                x, iters, info = drv(a, b, mesh, nb, opts=opts, pre=pre)
            if int(info) == 0 and int(iters) >= 0:
                return x, info
        if mode in ("gmres", "auto"):
            if mode == "auto" and not skip_ir:
                # gmres-pinned runs it as tier 1 and a health-routed
                # entry (num.routed_gmres) is a ROUTE, not an escalation
                # — only an IR tier that actually ran and failed counts
                ir_count("ir.escalated_gmres", kind)
            with sp.phase("gmres"):
                x, rnorm, conv, info = _mixed_gmres_solve(
                    kind, a, b, mesh, nb, opts, restart=30, pre=pre
                )
            if int(info) == 0 and bool(conv):
                return x, info
        if not get_option(opts, Option.UseFallbackSolver, True):
            # the caller opted out of the f64 fallback: surface the best
            # mixed-tier result (NaN x / info != 0 on a failed factor)
            return x, info
        ir_count("ir.fallback", kind)
        with sp.phase("fallback"):
            return plain_fn()
