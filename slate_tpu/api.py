"""Simplified verb-named API.

Analogue of ``include/slate/simplified_api.hh`` (806 LoC, reference
simplified_api.hh:19-600): friendly verb names over the LAPACK-style
drivers.  Arrays in, arrays out; matrix-type semantics (uplo/diag/band) ride
the object layer (slate_tpu.core.matrix) when needed.
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from .blas3 import blas3
from .core.matrix import BaseMatrix, HermitianMatrix, TriangularMatrix
from .linalg import chol, eig, indefinite, lu, norms, qr, svd as svd_mod, tri
from .types import Diag, MethodLU, Norm, Op, Options, Side, Uplo, get_option

Array = jax.Array
ArrayLike = Union[Array, BaseMatrix]

# -- multiply family (simplified_api.hh: multiply / triangular_multiply ...) --


def multiply(alpha, a: ArrayLike, b: ArrayLike, beta=0.0, c: Optional[ArrayLike] = None,
             opts: Optional[Options] = None):
    """C = alpha A B + beta C (slate::multiply -> gemm).  Option.Precision
    in ``opts`` selects the accumulation tier (types.Precision);
    Option.Lookahead is accepted here and consumed by the explicitly
    sharded mesh drivers (parallel.drivers / parallel.summa) — XLA's
    partitioner schedules the single-array form on its own.
    Option.FaultTolerance (ABFT policy, types.Option) routes this
    single-array form through ft.abft.gemm_checked: the product and its
    row/column checksums are computed by independent programs and
    compared, with single-tile damage repaired under ``correct`` —
    the mesh drivers run the full checksum-carrying SUMMA instead."""
    from .ft.policy import FtPolicy, resolve_policy

    policy = resolve_policy(opts)
    if policy != FtPolicy.Off:
        from .ft.abft import gemm_checked
        from .types import Option

        nb = int(get_option(opts, Option.BlockSize, default=32))
        return gemm_checked(alpha, blas3._arr(a), blas3._arr(b), beta,
                            None if c is None else blas3._arr(c),
                            nb=nb, policy=policy)
    if c is None:
        am, bm = blas3._arr(a), blas3._arr(b)
        c = jnp.zeros((am.shape[0], bm.shape[1]), am.dtype)
    return blas3.gemm(alpha, a, b, beta, c, opts=opts)


def hermitian_multiply(side: Side, alpha, a: ArrayLike, b: ArrayLike, beta=0.0, c=None,
                       opts: Optional[Options] = None):
    if c is None:
        bm = blas3._arr(b)
        c = jnp.zeros_like(bm)
    return blas3.hemm(side, alpha, a, b, beta, c, opts=opts)


def symmetric_multiply(side: Side, alpha, a: ArrayLike, b: ArrayLike, beta=0.0, c=None,
                       opts: Optional[Options] = None):
    if c is None:
        bm = blas3._arr(b)
        c = jnp.zeros_like(bm)
    return blas3.symm(side, alpha, a, b, beta, c, opts=opts)


def triangular_multiply(side: Side, alpha, a: ArrayLike, b: ArrayLike,
                        opts: Optional[Options] = None):
    return blas3.trmm(side, alpha, a, b, opts=opts)


def rank_k_update(alpha, a: ArrayLike, beta, c: ArrayLike, uplo: Optional[Uplo] = None,
                  opts: Optional[Options] = None):
    return blas3.herk(alpha, a, beta, c, uplo, opts=opts)


def rank_2k_update(alpha, a: ArrayLike, b: ArrayLike, beta, c: ArrayLike, uplo=None,
                   opts: Optional[Options] = None):
    return blas3.her2k(alpha, a, b, beta, c, uplo, opts=opts)


def triangular_solve(side: Side, alpha, a: ArrayLike, b: ArrayLike,
                     opts: Optional[Options] = None):
    """slate::triangular_solve -> trsm.  ``opts`` rides through (e.g.
    Option.Lookahead, consumed by the mesh schedules in parallel/)."""
    return blas3.trsm(side, alpha, a, b, opts=opts)


# -- LU (lu_factor / lu_solve / lu_solve_using_factor / lu_inverse) ----------


def lu_factor(a: ArrayLike, method: MethodLU = MethodLU.PartialPiv):
    ad = blas3._arr(a)
    if method == MethodLU.CALU:
        return lu.getrf_tntpiv_array(ad)
    if method == MethodLU.NoPiv:
        return lu.getrf_nopiv_array(ad)
    return lu.getrf_array(ad)


def lu_solve(a: ArrayLike, b: ArrayLike, method: MethodLU = MethodLU.PartialPiv):
    x, _ = lu.gesv_array(blas3._arr(a), blas3._arr(b), method)
    return x


def lu_solve_mixed(a: ArrayLike, b: ArrayLike, opts: Optional[Options] = None):
    """Mixed-precision LU solve (slate::gesv_mixed_gmres): a float32 LU
    (Option.MethodLU; under MethodLU.NoPiv its trailing updates run at
    Option.Precision) preconditions GMRES-IR in a's precision, which stops
    on HPL's test ||b - A x||_inf <= 16 u n ||A||_inf ||x||_inf (HPL-MxP's
    solve).  Returns RefineResult(x, iters, converged, info): iters the
    GMRES steps of the slowest column, info the factor's LAPACK code."""
    from .linalg.refine import _gesv_gmres

    res, _ = _gesv_gmres(blas3._arr(a), blas3._arr(b), opts, restart=30)
    return res


def lu_solve_using_factor(f, b: ArrayLike, op: Op = Op.NoTrans):
    return lu.getrs_array(f, blas3._arr(b), op)


def lu_inverse(a: ArrayLike):
    return lu.getri_array(lu.getrf_array(blas3._arr(a)))


# -- Cholesky (chol_factor / chol_solve / chol_inverse) ----------------------


def chol_factor(a: ArrayLike):
    uplo = a.uplo if isinstance(a, BaseMatrix) else Uplo.Lower
    ad = a.data if isinstance(a, BaseMatrix) else jnp.asarray(a)
    return chol.potrf_array(ad, uplo)


def chol_solve(a: ArrayLike, b: ArrayLike):
    x, _, info = chol.posv_array(
        a.data if isinstance(a, BaseMatrix) else jnp.asarray(a),
        blas3._arr(b),
        a.uplo if isinstance(a, BaseMatrix) else Uplo.Lower,
    )
    return x, info


def chol_solve_using_factor(l: Array, b: ArrayLike, uplo: Uplo = Uplo.Lower):
    return chol.potrs_array(l, blas3._arr(b), uplo)


def chol_inverse(l: Array, uplo: Uplo = Uplo.Lower):
    return chol.potri_array(l, uplo)


# -- indefinite (indefinite_factor / indefinite_solve) -----------------------


def indefinite_factor(a: ArrayLike, nb: int = 32):
    return indefinite.hetrf_array(blas3._arr(a), nb)


def indefinite_solve(a: ArrayLike, b: ArrayLike, nb: int = 32):
    x, _, info = indefinite.hesv_array(blas3._arr(a), blas3._arr(b), nb)
    return x, info


# -- least squares / QR / LQ -------------------------------------------------


def least_squares_solve(a: ArrayLike, b: ArrayLike):
    """slate::least_squares_solve -> gels."""
    return qr.gels_array(blas3._arr(a), blas3._arr(b))


def qr_factor(a: ArrayLike):
    return qr.geqrf_array(blas3._arr(a))


def qr_multiply_by_q(f, c: ArrayLike, side: Side = Side.Left, op: Op = Op.NoTrans):
    return qr.unmqr_array(side, op, f, blas3._arr(c))


def lq_factor(a: ArrayLike):
    return qr.gelqf_array(blas3._arr(a))


def lq_multiply_by_q(f, c: ArrayLike, side: Side = Side.Left, op: Op = Op.NoTrans):
    return qr.unmlq_array(side, op, f, blas3._arr(c))


# -- eig / svd ---------------------------------------------------------------


def eig_vals(a: ArrayLike) -> Array:
    """slate::eig_vals (Hermitian)."""
    return eig.heev_array(blas3._arr(a), want_vectors=False)


def eig_decompose(a: ArrayLike):
    return eig.heev_array(blas3._arr(a), want_vectors=True)


def generalized_eig(a: ArrayLike, b: ArrayLike):
    return eig.hegv_array(blas3._arr(a), blas3._arr(b))


def svd_vals(a: ArrayLike) -> Array:
    return svd_mod.svd_array(blas3._arr(a), want_vectors=False)


def svd_decompose(a: ArrayLike):
    return svd_mod.svd_array(blas3._arr(a), want_vectors=True)


# -- serving (slate_tpu.serve): batched small-problem verbs ------------------
# The simplified-API face of the serving runtime: stacks of same-shaped
# small problems run as ONE compiled program (bitwise-equal per problem
# to the single verbs above); ``serve_router`` builds the full request
# path (admission via the HBM model, condest-keyed accuracy classes,
# executable cache + autotuned schedule table).


def chol_solve_batched(a: Array, b: Array):
    """Stacked chol_solve: (B, n, n) x (B, n, k) -> (x, info) stacks."""
    from .serve.batch import posv_batched

    return posv_batched(a, b)


def lu_solve_batched(a: Array, b: Array,
                     method: MethodLU = MethodLU.PartialPiv):
    """Stacked lu_solve: (B, n, n) x (B, n, k) -> (x, info) stacks."""
    from .serve.batch import gesv_batched

    return gesv_batched(a, b, method)


def multiply_batched(alpha, a: Array, b: Array, beta=0.0, c=None):
    """Stacked multiply over (B, m, k) x (B, k, n) operand stacks."""
    from .serve.batch import gemm_batched

    return gemm_batched(alpha, a, b, beta, c)


def serve_router(**kwargs):
    """A serve.Router over this API's drivers (serve/router.py)."""
    from .serve.router import Router

    return Router(**kwargs)


# -- norms / condition -------------------------------------------------------


norm = norms.norm
condest = norms.gecondest
