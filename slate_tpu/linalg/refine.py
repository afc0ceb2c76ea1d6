"""Mixed-precision iterative refinement: classic IR and GMRES-IR.

Analogues of ``src/{gesv_mixed,gesv_mixed_gmres,posv_mixed,
posv_mixed_gmres}.cc``.  The reference factors in FP32 and refines in FP64
(gesv_mixed.cc:16-44); that maps *natively* onto TPU where f32 (and bf16)
matmuls ride the MXU at full rate while f64 is emulated — mixed precision is
the performance path, not an option, so these drivers are first-class here.

Generic over a (factor, solve) pair so LU and Cholesky share the loop; the
convergence gate mirrors the reference: stop when the residual satisfies
``||r|| <= ||x|| * ||A|| * eps * sqrt(n) * stesp`` and fall back to the full
high-precision solver after max_iter failures when UseFallbackSolver is set.
GMRES-IR stops on HPL's test instead (``_gmres``).
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.matrix import symmetrize
from ..ops.matmul import matmul
from ..ops.tile_ops import genorm
from ..obs import instrument
from ..types import MethodLU, Norm, Option, Options, Precision, Uplo, get_option

Array = jax.Array


def gate_cte(anorm, n: int, dtype, tol_factor: float = 1.0):
    """The refinement convergence constant: the loop stops when
    ``||r|| <= ||x|| * cte`` with ``cte = ||A|| * eps * sqrt(n)`` — the
    reference's gesv_mixed.cc gate.  The single definition shared by the
    single-chip loop below and the fused mesh refinement
    (parallel/dist_refine.py), so the accuracy contract cannot drift."""
    eps = jnp.finfo(dtype).eps
    return anorm * eps * jnp.sqrt(jnp.asarray(float(n), dtype)) * tol_factor


# -- ir.* observability counters (the ft.policy pattern: always-on, cheap,
#    landed in every RunReport as the ``ir`` section) ------------------------

_IR_COUNTERS = (
    "ir.solves", "ir.converged", "ir.iters_total", "ir.gmres_solves",
    "ir.escalated_gmres", "ir.fallback", "ir.residual_gemm_bytes",
)


def _registry():
    from ..obs import REGISTRY

    return REGISTRY


def ir_count(name: str, op: str, n: float = 1.0) -> None:
    """Bump one ``ir.*`` counter, tagged by op (gesv/posv)."""
    _registry().counter_add(name, n, op=op)


def ir_gauge(name: str, value: float, op: str) -> None:
    _registry().gauge_set(name, float(value), op=op)


def ir_counter_values() -> dict:
    """Totals of every ``ir.*`` counter across op tags — the RunReport
    ``ir`` section (obs.report.make_report reads this), gated by
    ``obs.report --check`` like the ft.* outcome totals."""
    snap = _registry().snapshot()
    out = {name.split("ir.", 1)[1]: 0.0 for name in _IR_COUNTERS}
    for entry in snap.get("counters", []):
        if entry["name"] in _IR_COUNTERS:
            out[entry["name"].split("ir.", 1)[1]] += float(entry["value"])
    return out


class RefineResult(NamedTuple):
    """Result of a mixed-precision refined solve (ADVICE r4: the public
    return grew from 3 to 4 fields in round 4; the NamedTuple documents the
    arity in one place and keeps positional unpacking explicit).

    ``iters`` is -1 when the fallback full-precision solver produced ``x``;
    ``info`` is then that factorization's LAPACK code."""

    x: Array
    iters: Array
    converged: Array
    info: Array


def _refine_loop(
    a_hi: Array,
    b: Array,
    lo_solve: Callable[[Array], Array],
    max_iter: int,
    tol_factor: float = 1.0,
) -> Tuple[Array, Array, Array]:
    """Classic iterative refinement. Returns (x, iters, converged)."""
    n = a_hi.shape[0]
    anorm = genorm(Norm.Inf, a_hi)
    cte = gate_cte(anorm, n, a_hi.dtype, tol_factor)

    x = lo_solve(b).astype(a_hi.dtype)

    def cond(state):
        x, r, it, done = state
        return (~done) & (it < max_iter)

    def body(state):
        x, r, it, _ = state
        d = lo_solve(r).astype(a_hi.dtype)
        x = x + d
        r = b - matmul(a_hi, x).astype(b.dtype)
        xnorm = genorm(Norm.Inf, x)
        rnorm = genorm(Norm.Inf, r)
        done = rnorm <= xnorm * cte
        return x, r, it + 1, done

    r0 = b - matmul(a_hi, x).astype(b.dtype)
    done0 = genorm(Norm.Inf, r0) <= genorm(Norm.Inf, x) * cte
    x, r, iters, done = jax.lax.while_loop(cond, body, (x, r0, jnp.int32(0), done0))
    return x, iters, done


def _fallback(done, x, iters, full_solve):
    """Run the full high-precision solver only on non-convergence.  Eagerly,
    ``bool(done)`` is concrete and the expensive path is skipped entirely;
    under jit it falls back to lax.cond (one branch *executes*).
    ``full_solve`` returns (x, info); the converged path reports info 0
    (the f32 factor succeeded and the refinement met its gate)."""
    zero = jnp.zeros((), jnp.int32)
    try:
        if bool(done):
            return x, iters, zero
        xf, info = full_solve()
        return xf, jnp.asarray(-1, iters.dtype), jnp.asarray(info, jnp.int32)
    except jax.errors.TracerBoolConversionError:
        return jax.lax.cond(
            done,
            lambda: (x, iters, zero),
            lambda: (lambda out: (out[0], jnp.asarray(-1, iters.dtype),
                                  jnp.asarray(out[1], jnp.int32)))(full_solve()),
        )


def gesv_mixed_array(
    a: Array, b: Array, opts: Optional[Options] = None
) -> RefineResult:
    """FP32-factor + high-precision-refine LU solve (src/gesv_mixed.cc).
    Returns RefineResult(x, iters, converged, info); on non-convergence
    with fallback enabled the result is the full-precision solve, iters =
    -1, and info is that factorization's LAPACK code (first zero pivot
    index)."""
    from .lu import gesv_array, getrf_array, getrs_array

    lo_dtype = jnp.complex64 if jnp.issubdtype(a.dtype, jnp.complexfloating) else jnp.float32
    max_iter = get_option(opts, Option.MaxIterations, 30)
    f32 = getrf_array(a.astype(lo_dtype))
    solve = lambda rhs: getrs_array(f32, rhs.astype(lo_dtype))
    x, iters, done = _refine_loop(a, b, solve, max_iter)
    info = jnp.zeros((), jnp.int32)
    if get_option(opts, Option.UseFallbackSolver, True):
        x, iters, info = _fallback(
            done, x, iters, lambda: (lambda o: (o[0], o[1].info))(gesv_array(a, b))
        )
    return RefineResult(x, iters, done, info)


def posv_mixed_array(
    a: Array, b: Array, uplo: Uplo = Uplo.Lower, opts: Optional[Options] = None
) -> RefineResult:
    """src/posv_mixed.cc analogue.  Returns RefineResult(x, iters,
    converged, info)."""
    from .chol import posv_array, potrf_array, potrs_array

    lo_dtype = jnp.complex64 if jnp.issubdtype(a.dtype, jnp.complexfloating) else jnp.float32
    max_iter = get_option(opts, Option.MaxIterations, 30)
    f32, _ = potrf_array(a.astype(lo_dtype), uplo)
    solve = lambda rhs: potrs_array(f32, rhs.astype(lo_dtype), uplo)
    conj = jnp.issubdtype(a.dtype, jnp.complexfloating)
    a_full = symmetrize(a, uplo, conj=conj)
    x, iters, done = _refine_loop(a_full, b, solve, max_iter)
    info = jnp.zeros((), jnp.int32)
    if get_option(opts, Option.UseFallbackSolver, True):
        x, iters, info = _fallback(
            done, x, iters, lambda: (lambda o: (o[0], o[2]))(posv_array(a, b, uplo))
        )
    return RefineResult(x, iters, done, info)


# ---------------------------------------------------------------------------
# GMRES-IR (src/gesv_mixed_gmres.cc, 409 LoC; posv_mixed_gmres.cc)
# ---------------------------------------------------------------------------


_MV_ROWS = 512  # rows per block of the refinement's reads of A


def _split_pair(a: Array, lo_dtype) -> Tuple[Array, Array]:
    """``a`` as (hi, lo) in ``lo_dtype`` with hi + lo = a to twice that
    precision (the 48 bits a TPU keeps of a float64): hi is ``a`` rounded,
    and is what the factor reads.  A TPU splits every float64 array it
    reads into such a float32 pair, and would split the whole of A again
    for the refinement's loop: the refinement reads this pair instead."""
    hi = a.astype(lo_dtype)
    return hi, (a - hi.astype(a.dtype)).astype(lo_dtype)


def _row_blocks(hi: Array, lo: Array, dtype, fn: Callable[[Array], Array]) -> Array:
    """``fn`` (one value per row) of the matrix hi + lo in ``dtype``, over
    blocks of ``_MV_ROWS`` rows, so the widened block is the only
    temporary; the last block may overlap the one before it and writes the
    same rows again.  The refinement's products with A are elementwise
    products and row sums here, not dots: a TPU emulates a float64 dot by
    splitting both operands into float32 pieces (an (8, n, n) temporary for
    A, 18 GiB at n 24576), and ``ops.matmul``'s Ozaki dispatch splits them
    too."""
    rows = hi.shape[0]
    widen = lambda h, l: fn(h.astype(dtype) + l.astype(dtype))
    if rows <= _MV_ROWS:
        return widen(hi, lo)

    def block(i, out):
        r0 = jnp.minimum(i * _MV_ROWS, rows - _MV_ROWS)
        part = widen(jax.lax.dynamic_slice_in_dim(hi, r0, _MV_ROWS),
                     jax.lax.dynamic_slice_in_dim(lo, r0, _MV_ROWS))
        return jax.lax.dynamic_update_slice_in_dim(out, part, r0, 0)

    shape = jax.ShapeDtypeStruct((_MV_ROWS,) + hi.shape[1:], dtype)
    out = jnp.zeros(rows, jax.eval_shape(fn, shape).dtype)
    return jax.lax.fori_loop(0, -(-rows // _MV_ROWS), block, out)


def _givens_step(j, h: Array, cs: Array, sn: Array, g: Array):
    """Fold Hessenberg column ``j`` (``h``, length m + 1) into the QR of the
    least-squares problem min ||beta e1 - H y||: apply rotations 0..j-1,
    then form rotation j, which zeroes ``h[j + 1]`` and also rotates the
    right side ``g``.  Returns (R's column j, cs, sn, g); ``|g[j + 1]|`` is
    then the least-squares residual of the first j + 1 columns."""

    def rotate(i, h):
        hi, hi1 = h[i], h[i + 1]
        return h.at[i].set(cs[i] * hi + sn[i] * hi1).at[i + 1].set(
            -jnp.conj(sn[i]) * hi + cs[i] * hi1)

    h = jax.lax.fori_loop(0, j, rotate, h)
    top, low = h[j], h[j + 1]
    atop, alow = jnp.abs(top), jnp.abs(low)
    t = jnp.hypot(atop, alow)
    phase = jnp.where(atop == 0, 1, top / jnp.where(atop == 0, 1, atop))
    c = jnp.where(t == 0, 1, atop / jnp.where(t == 0, 1, t))
    s = jnp.where(t == 0, 0, phase * jnp.conj(low) / jnp.where(t == 0, 1, t))
    gj = g[j]
    g = g.at[j].set(c * gj).at[j + 1].set(-jnp.conj(s) * gj)
    return (h.at[j].set(phase * t).at[j + 1].set(0), cs.at[j].set(c),
            sn.at[j].set(s.astype(sn.dtype)), g)


def _upper_solve(r: Array, g: Array, j) -> Array:
    """y with R[:j, :j] y[:j] = g[:j] and y[j:] = 0, R (m, m) upper
    triangular in its first ``j`` columns: back substitution on scalars."""
    m = r.shape[1]
    used = jnp.arange(m) < j
    r = jnp.where(used[:, None] & used[None, :], r[:m], jnp.eye(m, dtype=r.dtype))
    g = jnp.where(used, g[:m], 0)

    def back(i, y):
        k = m - 1 - i
        return y.at[k].set((g[k] - r[k] @ y) / r[k, k])

    return jax.lax.fori_loop(0, m, back, jnp.zeros_like(g))


def _gmres(
    matvec: Callable[[Array], Array],
    precond: Callable[[Array], Array],
    b: Array,
    restart: int,
    max_steps: int,
    cte: Array,
) -> Tuple[Array, Array, Array, Array]:
    """Right-preconditioned flexible restarted GMRES (FGMRES) on one
    right-hand side, from x0 = precond(b).  Returns (x, ||b - A x||_inf,
    GMRES steps, converged).

    The stop test is HPL's, on the unpreconditioned residual in b's own
    precision, with ||b||_inf dropped from its denominator (stricter):
    ||b - A x||_inf <= cte ||x||_inf, cte = 16 u n ||A||_inf.  It is
    measured after x0 and after every cycle (phase ``residual``).  Inside
    a cycle the Givens-rotated residual |g[j + 1]| stops the Arnoldi loop
    early: with right preconditioning it is ||b - A x_j||_2 (exactly so in
    exact arithmetic, the Arnoldi vectors being orthonormal), which bounds
    the inf-norm.  Each step keeps its preconditioned direction
    ``z = precond(v)`` (flexible GMRES: a low-precision preconditioner is
    not one fixed linear operator) and x moves along Z y.  The Arnoldi
    basis lives in static (restart + 1, n) buffers (the reference's
    rotation loop, gesv_mixed_gmres.cc, in XLA form); orthogonalization is
    classical Gram-Schmidt applied twice.  Phases: ``residual`` (products
    with A), ``precond`` (the low-precision solves), ``arnoldi`` (basis,
    rotations and the small triangular solve)."""
    from ..parallel.comm import phase_scope

    n, dtype, m = b.shape[0], b.dtype, restart
    rdt = jnp.real(b).dtype
    rows = jnp.arange(m + 1)

    def measure(x):
        with phase_scope("residual"):
            r = b - matvec(x)
            rn = jnp.max(jnp.abs(r))
        return r, rn, rn <= cte * jnp.max(jnp.abs(x))

    def cycle(c):
        x, r, _, steps, _ = c
        with phase_scope("arnoldi"):
            beta = jnp.linalg.norm(r)
            v = jnp.zeros((m + 1, n), dtype).at[0].set(r / jnp.where(beta == 0, 1, beta))
            g = jnp.zeros(m + 1, dtype).at[0].set(beta.astype(dtype))
            tol = cte * jnp.max(jnp.abs(x))
        arn0 = (jnp.int32(0), v, jnp.zeros((m, n), dtype), jnp.zeros((m + 1, m), dtype),
                jnp.zeros(m, rdt), jnp.zeros(m, dtype), g, beta)

        def more(s):
            j, *_, est = s
            return (j < m) & (steps + j < max_steps) & ~(est <= tol)

        def arnoldi(s):
            j, v, z, hm, cs, sn, g, _ = s
            with phase_scope("precond"):
                zj = precond(v[j])
            with phase_scope("residual"):
                w = matvec(zj)
            with phase_scope("arnoldi"):
                keep = (rows <= j).astype(dtype)
                h = jnp.sum(jnp.conj(v) * w, axis=-1) * keep
                w = w - jnp.sum(h[:, None] * v, axis=0)
                h2 = jnp.sum(jnp.conj(v) * w, axis=-1) * keep
                w = w - jnp.sum(h2[:, None] * v, axis=0)
                hn = jnp.linalg.norm(w)
                h = (h + h2).at[j + 1].set(hn.astype(dtype))
                v = v.at[j + 1].set(w / jnp.where(hn == 0, 1, hn))
                col, cs, sn, g = _givens_step(j, h, cs, sn, g)
            return (j + 1, v, z.at[j].set(zj), hm.at[:, j].set(col), cs, sn, g,
                    jnp.abs(g[j + 1]))

        j, _, z, hm, _, _, g, _ = jax.lax.while_loop(more, arnoldi, arn0)
        with phase_scope("arnoldi"):
            x = x + jnp.sum(_upper_solve(hm, g, j)[:, None] * z, axis=0)
        r, rn, done = measure(x)
        return x, r, rn, steps + j, done

    with phase_scope("precond"):
        x0 = precond(b)
    r0, rn0, done0 = measure(x0)
    # while_loop, not fori_loop + cond: under the multi-RHS vmap a
    # batched-predicate cond lowers to both-branches-execute + select, and
    # a while_loop's batched predicate stops at the slowest column
    x, _, rn, steps, done = jax.lax.while_loop(
        lambda c: ~c[4] & (c[3] < max_steps), cycle,
        (x0, r0, rn0, jnp.int32(0), done0))
    return x, rn, steps, done


def _gmres_multi_rhs(hi, lo, b, precond, restart, max_restarts):
    """GMRES-IR on each column of ``b`` ((n,) or (n, k)) for A = hi + lo
    (``_split_pair``), stopping on HPL's test (``_gmres``).  Returns (x
    like b, worst ||b - A x||_inf, most GMRES steps of any column, every
    column converged).

    The columns are independent Krylov solves with identical static
    shapes, so the single-RHS solver is ``vmap``ped over them — ONE
    compiled program for any B width.  At most ``restart *
    max_restarts`` steps per column."""
    from ..parallel.comm import phase_scope

    n, dtype = b.shape[0], b.dtype
    unit = jnp.finfo(dtype).eps / 2  # HPL's eps, LAPACK dlamch('E')
    with phase_scope("residual"):
        anorm = jnp.max(_row_blocks(hi, lo, dtype, lambda m: jnp.sum(jnp.abs(m), axis=-1)))
    cte = 16 * unit * n * anorm

    def one(bv):
        matvec = lambda v: _row_blocks(hi, lo, dtype, lambda m: jnp.sum(m * v, axis=-1))
        return _gmres(matvec, precond, bv, restart, restart * max_restarts, cte)

    if b.ndim == 1:
        return one(b)
    x, rn, steps, done = jax.vmap(one, in_axes=1, out_axes=(1, 0, 0, 0))(b)
    return x, jnp.max(rn), jnp.max(steps), jnp.all(done)


def _lu_factor_lo(a_lo: Array, opts: Optional[Options]):
    """The low-precision LU that preconditions GMRES-IR: Option.MethodLU
    (partial pivoting by default, CALU, or NoPiv); the no-pivot factor's
    trailing updates run at Option.Precision (``lu._schur_product``)."""
    from ..blas3.blas3 import _mul_prec
    from .lu import getrf_array, getrf_nopiv_array, getrf_tntpiv_array

    method = MethodLU(get_option(opts, Option.MethodLU, MethodLU.PartialPiv))
    precision = _mul_prec(opts)
    if method == MethodLU.NoPiv:
        return getrf_nopiv_array(a_lo, precision)
    if precision != Precision.Highest:
        raise ValueError(f"Option.Precision {precision.value!r} needs MethodLU.NoPiv; "
                         f"the {method.value} factor runs at full precision")
    if method == MethodLU.PartialPiv:
        return getrf_array(a_lo)
    if method == MethodLU.CALU:
        return getrf_tntpiv_array(a_lo)
    raise ValueError(f"GMRES-IR has no {method.value} factor")


@instrument("gesv_mixed_gmres")
def _gesv_gmres(a: Array, b: Array, opts: Optional[Options], restart: int):
    """The LU GMRES-IR core: (RefineResult, worst ||b - A x||_inf)."""
    from .lu import getrs_array

    lo_dtype = jnp.complex64 if jnp.issubdtype(a.dtype, jnp.complexfloating) else jnp.float32
    hi, lo = _split_pair(a, lo_dtype)
    with jax.named_scope("getrf"):
        f = _lu_factor_lo(hi, opts)
    with jax.named_scope("gmres"):
        precond = lambda v: getrs_array(f, v.astype(lo_dtype)[:, None])[:, 0].astype(a.dtype)
        x, resid, steps, done = _gmres_multi_rhs(
            hi, lo, b, precond, restart, get_option(opts, Option.MaxIterations, 30))
    return RefineResult(x, steps, done, f.info), resid


def gesv_mixed_gmres_array(
    a: Array, b: Array, opts: Optional[Options] = None, restart: int = 30
) -> Tuple[Array, Array]:
    """GMRES-IR: low-precision LU as preconditioner for high-precision GMRES
    (src/gesv_mixed_gmres.cc). b may be (n,) or (n, 1).  The factor follows
    Option.MethodLU / Option.Precision (``_lu_factor_lo``); GMRES stops on
    HPL's test (``_gmres``).  Returns (x, ||b - A x||_inf), the worst over
    the columns; ``api.lu_solve_mixed`` returns the step count too."""
    res, resid = _gesv_gmres(a, b, opts, restart)
    return res.x, resid


def posv_mixed_gmres_array(
    a: Array, b: Array, uplo: Uplo = Uplo.Lower, opts: Optional[Options] = None, restart: int = 30
) -> Tuple[Array, Array]:
    """src/posv_mixed_gmres.cc analogue.  Returns (x, ||b - A x||_inf)."""
    from .chol import potrf_array, potrs_array

    lo_dtype = jnp.complex64 if jnp.issubdtype(a.dtype, jnp.complexfloating) else jnp.float32
    conj = jnp.issubdtype(a.dtype, jnp.complexfloating)
    a_full = symmetrize(a, uplo, conj=conj)
    f, _ = potrf_array(a.astype(lo_dtype), uplo)
    precond = lambda v: potrs_array(f, v.astype(lo_dtype)[:, None], uplo)[:, 0].astype(a.dtype)
    x, resid, _, _ = _gmres_multi_rhs(
        *_split_pair(a_full, lo_dtype), b, precond, restart,
        get_option(opts, Option.MaxIterations, 30))
    return x, resid
