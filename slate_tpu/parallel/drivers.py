"""Mesh-level drivers: dense-in/dense-out distributed solves.

The user-facing layer tying DistMatrix + the shard_map kernels together —
the analogue of the reference drivers (src/posv.cc, src/gesv_nopiv path,
src/gemm.cc) run with a 2D block-cyclic distribution, with
``Matrix::fromScaLAPACK``-style construction replaced by ``from_dense``.

Note the padding contract: factorization inputs are padded with an identity
diagonal block (dist.from_dense(diag_pad_one=True)) so padded runs stay
exact — diag(A, I) factors to diag(L, I) and the pad never mixes with data.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from ..obs import instrument
from ..types import Diag, Op, Option, Options, Uplo, get_option
from .dist import DistMatrix, from_dense, to_dense
from .dist_chol import potrf_dist
from .dist_lu import (
    getrf_nopiv_dist,
    getrf_pp_dist,
    getrf_tntpiv_dist,
    permute_rows_dist,
)
from .dist_qr import geqrf_dist, unmqr_dist
from .dist_trsm import trsm_dist
from .summa import gemm_summa

_DEFAULT_NB = 256


def _la(opts: Optional[Options]):
    """Raw Option.Lookahead value from a driver ``opts`` mapping — the
    panel-prefetch / deferred-update pipeline depth every mesh k-loop
    consumes (comm.prefetch_bcast / comm.pipelined_factor_loop).  May be
    None (absent or explicitly unset): ``comm.la_depth`` inside each
    kernel is the single authority that maps None to the option default
    (1, as in the reference) and clamps to the trip count."""
    return get_option(opts, Option.Lookahead)


def _bi(opts: Optional[Options]):
    """Raw Option.BcastImpl value from a driver ``opts`` mapping — the
    tileBcast lowering every mesh k-loop consumes.  May be None:
    ``comm.resolve_bcast_impl`` inside each kernel is the single
    authority for the context/env/auto default chain."""
    return get_option(opts, Option.BcastImpl)


def _ui(opts: Optional[Options]):
    """Raw Option.UpdateImpl value from a driver ``opts`` mapping — the
    trailing-update lowering the summa/potrf/LU-nopiv k-loops consume
    (fused Pallas trailing-update kernels vs the XLA bulk einsums).  May
    be None: ``ops.pallas_ops.resolve_update_impl`` inside each kernel
    is the single authority for the context/env/auto default chain."""
    return get_option(opts, Option.UpdateImpl)


def _nm(opts: Optional[Options]):
    """Raw Option.NumMonitor value from a driver ``opts`` mapping — the
    in-carry numerics-gauge switch the factor kernels consume (growth /
    diagonal-margin monitoring, obs/numerics.py).  May be None:
    ``obs.numerics.resolve_num_monitor`` inside each kernel is the
    single authority for the context/env/auto default chain (auto = on
    iff the obs layer is enabled)."""
    return get_option(opts, Option.NumMonitor)


def _ckpt_every(opts: Optional[Options]):
    """Resolved Option.Checkpoint snapshot interval (int) or None (off).
    ``ft.ckpt.resolve_checkpoint`` is the single authority for the
    explicit > SLATE_TPU_CKPT env > off chain; off keeps the drivers on
    the fused kernels untouched (trace-identical, zero overhead)."""
    from ..ft.ckpt import resolve_checkpoint

    return resolve_checkpoint(get_option(opts, Option.Checkpoint, default=None))


def _ft_on(opts: Optional[Options]) -> bool:
    """True when Option.FaultTolerance selects an active ABFT policy.
    Off (the default) keeps this module on the plain kernels with zero
    overhead — results stay bitwise-identical; any active policy routes
    to the checksum-carrying variants in slate_tpu/ft/abft.py (also
    validates the option value, so a typo'd policy fails loudly here
    instead of silently running unprotected)."""
    from ..ft.policy import FtPolicy, resolve_policy

    return resolve_policy(opts) != FtPolicy.Off


def _resilience(opts: Optional[Options]):
    """(ft_on, checkpoint_every), each resolved ONCE per driver call.
    Arming FaultTolerance TOGETHER with Option.Checkpoint is rejected
    loudly: the ABFT kernels are not checkpointed yet, so the
    combination would silently drop snapshotting (and never consult
    kill faults) — fail instead of degrading."""
    ft_on = _ft_on(opts)
    every = _ckpt_every(opts)
    if ft_on and every is not None:
        raise ValueError(
            "Option.FaultTolerance and Option.Checkpoint cannot be "
            "combined (the ABFT kernels are not checkpointed yet); arm "
            "one of them"
        )
    return ft_on, every


@instrument("gemm_mesh")
def gemm_mesh(
    alpha, a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    beta=0.0, c: Optional[jax.Array] = None,
    opts: Optional[Options] = None,
) -> jax.Array:
    """Distributed C = alpha A B (+ beta C) via SUMMA (src/gemmC.cc).
    ``opts`` carries Option.Lookahead (panel-prefetch depth) and
    Option.FaultTolerance (ABFT policy; any active policy reroutes to
    the checksum-carrying SUMMA in ft/abft.py)."""
    if _ft_on(opts):
        from ..ft.abft import gemm_mesh_ft

        return gemm_mesh_ft(alpha, a, b, mesh, nb, beta, c, opts)
    ad = from_dense(a, mesh, nb)
    bd = from_dense(b, mesh, nb)
    cd = from_dense(c, mesh, nb) if c is not None else None
    return to_dense(gemm_summa(alpha, ad, bd, beta, cd, lookahead=_la(opts),
                               bcast_impl=_bi(opts), update_impl=_ui(opts)))


@instrument("potrf_mesh")
def potrf_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[DistMatrix, jax.Array]:
    """Distributed lower Cholesky; input is the full/lower Hermitian
    array.  Option.FaultTolerance reroutes to the checksum-carrying
    mesh loop (ft/abft.py)."""
    ft_on, every = _resilience(opts)
    if ft_on:
        from ..ft.abft import potrf_mesh_ft

        return potrf_mesh_ft(a, mesh, nb, opts)
    if every is not None:
        from ..ft.ckpt import potrf_ckpt

        return potrf_ckpt(
            from_dense(a, mesh, nb, diag_pad_one=True), every=every,
            bcast_impl=_bi(opts), num_monitor=_nm(opts),
        )
    return potrf_dist(
        from_dense(a, mesh, nb, diag_pad_one=True), lookahead=_la(opts),
        bcast_impl=_bi(opts), update_impl=_ui(opts),
        num_monitor=_nm(opts),
    )


def _posv_mesh_plain(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The direct factor-at-data-dtype SPD solve: potrf + two trsm
    sweeps.  This is the whole solve under Option.MixedPrecision=off
    (trace-identical to the pre-mixed driver) and the fallback tier of
    the mixed ladder."""
    la, bi = _la(opts), _bi(opts)
    l, info = potrf_mesh(a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    y = trsm_dist(l, bd, Uplo.Lower, Op.NoTrans, lookahead=la, bcast_impl=bi)
    x = trsm_dist(l, y, Uplo.Lower, Op.ConjTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


@instrument("posv_mesh")
def posv_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed SPD solve (src/posv.cc).  f64 inputs route through the
    mixed-precision ladder by default (Option.MixedPrecision, default
    auto: f32 mesh factor + fused f64 refinement, GMRES-IR escalation,
    full-f64 fallback — dist_refine.py; the f32 factor consumes every
    opt the direct path would: Lookahead, BcastImpl, FaultTolerance).  ``off`` (or any non-f64 dtype) runs the direct
    potrf + two-trsm path, trace-identical to the pre-mixed driver."""
    from .dist_refine import mixed_mesh_route

    routed = mixed_mesh_route(
        "posv", a, b, mesh, nb, opts,
        lambda: _posv_mesh_plain(a, b, mesh, nb, opts),
    )
    if routed is not None:
        return routed
    return _posv_mesh_plain(a, b, mesh, nb, opts)


@instrument("getrf_nopiv_mesh")
def getrf_nopiv_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[DistMatrix, jax.Array]:
    """Option.FaultTolerance reroutes to the checksum-carrying LU-nopiv
    mesh loop (ft/abft.py)."""
    ft_on, every = _resilience(opts)
    if ft_on:
        from ..ft.abft import getrf_nopiv_mesh_ft

        return getrf_nopiv_mesh_ft(a, mesh, nb, opts)
    if every is not None:
        from ..ft.ckpt import getrf_nopiv_ckpt

        return getrf_nopiv_ckpt(
            from_dense(a, mesh, nb, diag_pad_one=True), every=every,
            bcast_impl=_bi(opts), num_monitor=_nm(opts),
        )
    return getrf_nopiv_dist(
        from_dense(a, mesh, nb, diag_pad_one=True), lookahead=_la(opts),
        bcast_impl=_bi(opts), update_impl=_ui(opts),
        num_monitor=_nm(opts),
    )


@instrument("gesv_nopiv_mesh")
def gesv_nopiv_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed LU solve without pivoting (src/gesv_nopiv path). For
    general matrices use gesv_tntpiv_mesh (tournament pivoting), the RBT
    preconditioner (linalg.rbt), or the single-chip partial-pivot getrf.
    Option.FaultTolerance protects the factorization (via
    getrf_nopiv_mesh); the trsm sweeps run unprotected."""
    la, bi = _la(opts), _bi(opts)
    lu, info = getrf_nopiv_mesh(a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    y = trsm_dist(lu, bd, Uplo.Lower, Op.NoTrans, Diag.Unit, lookahead=la,
                  bcast_impl=bi)
    x = trsm_dist(lu, y, Uplo.Upper, Op.NoTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


@instrument("geqrf_mesh")
def geqrf_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
):
    """Distributed CAQR factorization (src/geqrf.cc). Returns DistQR.
    ``opts`` carries Option.BcastImpl (panel-broadcast lowering),
    Option.Checkpoint (ISSUE 13: the multi-array carry — tile stack +
    T_loc stack + tree V/T stacks — snapshots every K panel steps; off
    keeps the fused kernel untouched, trace-identical) and
    Option.NumMonitor (the in-carry reflector/τ orthogonality-loss
    gauge -> num.qr_orth_margin, through the FUSED loop and the
    checkpointed chain alike since ISSUE 15 — bitwise-equal gauges;
    off keeps the plain kernels/segment jits)."""
    every = _ckpt_every(opts)
    if every is not None:
        from ..ft.ckpt import geqrf_ckpt

        return geqrf_ckpt(from_dense(a, mesh, nb), every=every,
                          bcast_impl=_bi(opts), num_monitor=_nm(opts))
    return geqrf_dist(from_dense(a, mesh, nb), bcast_impl=_bi(opts),
                      num_monitor=_nm(opts))


@instrument("gels_mesh")
def gels_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed least squares min ||A X - B|| for m >= n via CAQR
    (src/gels_qr.cc): X = R^-1 (Q^H B)[:n].  Returns (X, R diag info).

    The R top-square re-distribution goes through one dense round trip —
    the tile-level redistribute is the scalable path (redistribute()).
    """
    m, n = a.shape
    bi = _bi(opts)
    f = geqrf_mesh(a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    qb = to_dense(unmqr_dist(f, bd, Op.ConjTrans, bcast_impl=bi))[:n]
    r = jnp.triu(to_dense(f.fact)[:n, :n])
    rd = from_dense(r, mesh, nb, diag_pad_one=True)
    xd = trsm_dist(rd, from_dense(qb, mesh, nb), Uplo.Upper, Op.NoTrans,
                   bcast_impl=bi)
    rdiag = jnp.diagonal(r)
    info = jnp.where(
        jnp.any(rdiag == 0), jnp.argmax(rdiag == 0) + 1, 0
    ).astype(jnp.int32)
    return to_dense(xd), info


@instrument("heev_mesh")
def heev_mesh(
    a: jax.Array, mesh: Mesh, nb: int = 64, want_vectors: bool = True,
    distributed_solver: bool = True, opts: Optional[Options] = None,
):
    """Distributed Hermitian eigensolver (src/heev.cc with a grid): stage 1
    (he2hb, the O(n^3) reduction) and the stage-1 back-transform run on the
    mesh; the band travels as O(n nb) diagonal storage (gather_diagband,
    the analogue of he2hbGather); the band-to-tridiagonal chase runs as a
    wavefront kernel on that O(n nb) frame; the tridiagonal divide &
    conquer runs with its merge tree SHARDED over the mesh (dist_stedc —
    the reference's distributed stedc.cc/stedc_merge.cc); and the stage-2
    back-transform streams the SHARDED bulge-chase reflector family over
    Z's column shards (chase_apply_dist, reference unmtr_hb2st.cc:1-80).
    stedc_dist hands Z over ALREADY in chase_apply_dist's column-shard
    layout (dist_stedc._stedc_finale_jit), so no O(n^2) object is
    replicated anywhere in the stage-2 chain — including the driver-level
    handoffs (VERDICT r3 item 4 / r4 item 6; asserted by
    test_chase_apply_dist_memory and test_stedc_finale_memory)."""
    from ..linalg.eig import hb2st
    from ..linalg.tridiag import stedc, sterf
    from .dist_stedc import stedc_dist
    from .dist_twostage import (
        chase_apply_dist,
        gather_diagband,
        he2hb_dist,
        unmtr_he2hb_dist,
    )

    n = a.shape[0]
    cplx = jnp.issubdtype(a.dtype, jnp.complexfloating)
    every = _ckpt_every(opts)
    if every is not None:
        # Option.Checkpoint covers the O(n^3) stage-1 reduction — the
        # eig chain's preemption exposure; the later stages are O(n^2 nb)
        # or run on an O(n nb) frame (ISSUE 13)
        from ..ft.ckpt import he2hb_ckpt

        f = he2hb_ckpt(from_dense(a, mesh, nb), every=every,
                       bcast_impl=_bi(opts), num_monitor=_nm(opts))
    else:
        f = he2hb_dist(from_dense(a, mesh, nb), bcast_impl=_bi(opts),
                       num_monitor=_nm(opts))
    bandd = gather_diagband(f.band, nb)  # (n, 4nb) replicated, O(n nb)
    # the distributed two-sided update is Hermitian in exact arithmetic;
    # shave the O(eps * nsteps) rounding asymmetry before the band chase
    from ..linalg.eig import symmetrize_diagband

    bandd = symmetrize_diagband(bandd, nb)
    d, e, f2, phases = hb2st(bandd, nb, diag_storage=True)
    if not want_vectors:
        return sterf(d, e)
    if distributed_solver:
        w, ztri = stedc_dist(d, e, mesh, bcast_impl=_bi(opts))
    else:
        w, ztri = stedc(d, e)
    z = ztri.astype(a.dtype)
    if cplx:
        z = phases[:, None] * z
    z = chase_apply_dist(f2.vs, f2.taus, z, n, nb, mesh, bcast_impl=_bi(opts))
    zd = unmtr_he2hb_dist(f, from_dense(z, mesh, nb))
    return w, to_dense(zd)


@instrument("svd_mesh")
def svd_mesh(
    a: jax.Array, mesh: Mesh, nb: int = 64, want_vectors: bool = True
):
    """Distributed SVD (src/svd.cc with a grid): ge2tb and both stage-1
    back-transforms on the mesh; the band travels as O(n nb) diagonals and
    both stage-2 reflector families stream SHARDED over the eigenvector
    column shards (chase_apply_dist), as in heev_mesh."""
    from ..linalg.svd import bdsqr, tb2bd
    from .dist_twostage import (
        chase_apply_dist,
        gather_diagband,
        ge2tb_dist,
        unmbr_ge2tb_u_dist,
        unmbr_ge2tb_v_dist,
    )

    m, n = a.shape
    dtype = a.dtype
    if m < n:
        if not want_vectors:
            return svd_mesh(jnp.conj(a).T, mesh, nb, False)
        u, s, vh = svd_mesh(jnp.conj(a).T, mesh, nb, True)
        return jnp.conj(vh).T, s, jnp.conj(u).T
    f = ge2tb_dist(from_dense(a, mesh, nb))
    bandd = gather_diagband(f.band, nb)[:n]  # (n, 4nb), O(n nb) replicated
    d, e, f2, pu, pv = tb2bd(bandd, nb, diag_storage=True)
    if not want_vectors:
        return bdsqr(d, e, want_vectors=False)
    s, ub, vb = bdsqr(d, e, want_vectors=True)
    u = chase_apply_dist(f2.lvs, f2.ltaus, pu[:, None] * ub.astype(dtype), n, nb, mesh)
    u_full = jnp.zeros((m, n), dtype).at[:n].set(u)
    ud = unmbr_ge2tb_u_dist(f, from_dense(u_full, mesh, nb))
    v = chase_apply_dist(f2.rvs, f2.rtaus, pv[:, None] * vb.astype(dtype), n, nb, mesh)
    vd = unmbr_ge2tb_v_dist(f, from_dense(v, mesh, nb))
    return to_dense(ud), s, jnp.conj(to_dense(vd)).T


@instrument("her2k_mesh")
def her2k_mesh(
    alpha, a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    beta=0.0, c: Optional[jax.Array] = None, conj: bool = True,
    opts: Optional[Options] = None,
) -> jax.Array:
    """Distributed rank-2k update C = alpha A op(B) + op(alpha) B op(A)
    + beta C (conj=True: her2k, src/her2k.cc; conj=False: syr2k),
    returned FULL (both triangles).  Option.FaultTolerance reroutes to
    the checksum-carrying her2k (ft/abft.py, ISSUE 13) — the eig
    chain's dominant trailing-update op gains the same inject→detect→
    repair coverage as gemm/potrf/LU/trsm."""
    from .dist_blas3 import her2k_dist

    if _ft_on(opts):
        from ..ft.abft import her2k_mesh_ft

        return her2k_mesh_ft(alpha, a, b, mesh, nb, beta, c, conj, opts)
    ad = from_dense(a, mesh, nb)
    bd = from_dense(b, mesh, nb)
    cd = from_dense(c, mesh, nb) if c is not None else None
    out = her2k_dist(alpha, ad, bd, beta, cd, conj=conj, full=True,
                     lookahead=_la(opts), bcast_impl=_bi(opts))
    return to_dense(out)[: a.shape[0], : a.shape[0]]


@instrument("getrf_tntpiv_mesh")
def getrf_tntpiv_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[DistMatrix, jax.Array, jax.Array]:
    """Distributed tournament-pivoted LU (src/getrf_tntpiv.cc): P A = L U.
    Returns (LU, perm over the padded row space, info)."""
    return getrf_tntpiv_dist(
        from_dense(a, mesh, nb, diag_pad_one=True), lookahead=_la(opts),
        bcast_impl=_bi(opts), num_monitor=_nm(opts),
    )


@instrument("gesv_tntpiv_mesh")
def gesv_tntpiv_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed general solve with tournament pivoting
    (src/gesv.cc with MethodLU::CALU): factor, permute B, two trsm sweeps."""
    la, bi = _la(opts), _bi(opts)
    lu, perm, info = getrf_tntpiv_mesh(a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    pb = permute_rows_dist(bd, perm)
    y = trsm_dist(lu, pb, Uplo.Lower, Op.NoTrans, Diag.Unit, lookahead=la,
                  bcast_impl=bi)
    x = trsm_dist(lu, y, Uplo.Upper, Op.NoTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


# ---------------------------------------------------------------------------
# Mixed-precision mesh solvers (src/gesv_mixed.cc:16-44, posv_mixed.cc) and
# distributed inverses (src/getri.cc, src/potri.cc).  The mixed engine —
# the fused on-device refinement loop, the Ozaki residual SUMMA, the
# distributed GMRES-IR escalation tier, and the Option.MixedPrecision
# routing behind gesv_mesh/posv_mesh — lives in dist_refine.py; the
# drivers are re-exported here so `parallel.gesv_mixed_mesh` keeps
# working.
# ---------------------------------------------------------------------------

from .dist_refine import (  # noqa: E402  (re-export; see module docstring)
    gesv_mixed_gmres_mesh,
    gesv_mixed_mesh,
    mixed_mesh_route,
    posv_mixed_gmres_mesh,
    posv_mixed_mesh,
)


@instrument("getri_mesh")
def getri_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB
) -> Tuple[jax.Array, jax.Array]:
    """Distributed inverse (src/getri.cc capability): partial-pivot factor
    then solve A X = I entirely on the mesh — the solve-against-identity
    formulation costs the same O(n^3) as the reference's trtri+trmm chain
    and reuses the pivoted trsm sweeps."""
    n = a.shape[0]
    lu, perm, info = getrf_mesh(a, mesh, nb)
    eye = jnp.eye(n, dtype=a.dtype)
    bd = from_dense(eye, mesh, nb)
    pb = permute_rows_dist(bd, perm)
    y = trsm_dist(lu, pb, Uplo.Lower, Op.NoTrans, Diag.Unit)
    x = trsm_dist(lu, y, Uplo.Upper, Op.NoTrans)
    return to_dense(x), info


@instrument("potri_mesh")
def potri_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB
) -> Tuple[jax.Array, jax.Array]:
    """Distributed SPD inverse (src/potri.cc capability): Cholesky factor,
    then A^-1 = L^-H L^-1 via two mesh trsm sweeps on the identity."""
    n = a.shape[0]
    l, info = potrf_mesh(a, mesh, nb)
    eye = jnp.eye(n, dtype=a.dtype)
    y = trsm_dist(l, from_dense(eye, mesh, nb), Uplo.Lower, Op.NoTrans)
    x = trsm_dist(l, y, Uplo.Lower, Op.ConjTrans)
    return to_dense(x), info


# ---------------------------------------------------------------------------
# Band drivers on the mesh (src/gbmm.cc, hbmm.cc, tbsm.cc, gbsv/gbtrf,
# pbsv/pbtrf on distributed band matrices).  Band storage rides the dense
# block-cyclic tile stack with the zero pattern enforced by (kl, ku)
# projection — structurally-zero tiles cost flops but not correctness; the
# bandwidth-aware k-loop skip is the scale-out refinement.
# ---------------------------------------------------------------------------


@instrument("gbmm_mesh")
def gbmm_mesh(
    alpha, a: jax.Array, kl: int, ku: int, b: jax.Array, mesh: Mesh,
    nb: int = _DEFAULT_NB, beta=0.0, c: Optional[jax.Array] = None,
    opts: Optional[Options] = None,
) -> jax.Array:
    """Distributed general-band x dense multiply (src/gbmm.cc)."""
    from ..core.matrix import band_project

    return gemm_mesh(alpha, band_project(a, kl, ku), b, mesh, nb, beta, c, opts)


@instrument("hbmm_mesh")
def hbmm_mesh(
    side, alpha, a: jax.Array, kd: int, b: jax.Array, mesh: Mesh,
    nb: int = _DEFAULT_NB, beta=0.0, c: Optional[jax.Array] = None,
    uplo: Uplo = Uplo.Lower, opts: Optional[Options] = None,
) -> jax.Array:
    """Distributed Hermitian-band x dense multiply (src/hbmm.cc)."""
    from ..core.matrix import band_project
    from .dist_blas3 import hemm_summa

    kl, ku = (kd, 0) if uplo == Uplo.Lower else (0, kd)
    ad = from_dense(band_project(a, kl, ku), mesh, nb)
    bd = from_dense(b, mesh, nb)
    cd = from_dense(c, mesh, nb) if c is not None else None
    return to_dense(hemm_summa(side, alpha, ad, bd, beta, cd, uplo=uplo,
                               lookahead=_la(opts), bcast_impl=_bi(opts)))


@instrument("tbsm_mesh")
def tbsm_mesh(
    a: jax.Array, kd: int, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    uplo: Uplo = Uplo.Lower, diag: Diag = Diag.NonUnit,
    perm: Optional[jax.Array] = None,
) -> jax.Array:
    """Distributed triangular-band solve, optionally applying LU pivots
    first (src/tbsm.cc tbsmPivots path)."""
    from ..core.matrix import band_project

    kl, ku = (kd, 0) if uplo == Uplo.Lower else (0, kd)
    ad = from_dense(band_project(a, kl, ku), mesh, nb, diag_pad_one=True)
    bd = from_dense(b, mesh, nb)
    if perm is not None:
        bd = permute_rows_dist(bd, perm)
    return to_dense(trsm_dist(ad, bd, uplo, Op.NoTrans, diag))


@instrument("pbsv_mesh")
def pbsv_mesh(
    a: jax.Array, b: jax.Array, kd: int, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed Hermitian-band solve (src/pbsv.cc/pbtrf.cc): the
    factorization k-loop only touches the tile window inside the
    bandwidth (pbtrf_band_dist) — O(n kd^2) work, tiles outside the band
    never read (Cholesky preserves the band); narrow-band inputs where
    the window equals the whole grid just degenerate to the dense
    schedule.  The triangular solves ride the dense trsm (banded L makes
    its masked flops vanish against the factor cost for skinny B)."""
    from ..core.matrix import band_project
    from .dist_chol import pbtrf_band_dist

    la, bi = _la(opts), _bi(opts)
    ab = band_project(a, kd, kd)
    ad = from_dense(ab, mesh, nb, diag_pad_one=True)
    l, info = pbtrf_band_dist(ad, kd, lookahead=la, bcast_impl=bi)
    bd = from_dense(b, mesh, nb)
    y = trsm_dist(l, bd, Uplo.Lower, Op.NoTrans, lookahead=la, bcast_impl=bi)
    x = trsm_dist(l, y, Uplo.Lower, Op.ConjTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


@instrument("gbsv_mesh")
def gbsv_mesh(
    a: jax.Array, b: jax.Array, kl: int, ku: int, mesh: Mesh,
    nb: int = _DEFAULT_NB, opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed general-band solve (src/gbsv.cc/gbtrf.cc): partial-pivot
    band LU whose panel, swaps, row solve and trailing update only touch
    the band envelope (gbtrf_band_dist, U fill-in <= kl + ku under
    pivoting) — O(n (kl + nb)(kl + ku + nb)) work instead of the dense
    O(n^3)."""
    from ..core.matrix import band_project
    from .dist_lu import gbtrf_band_dist

    la, bi = _la(opts), _bi(opts)
    ab = band_project(a, kl, ku)
    ad = from_dense(ab, mesh, nb, diag_pad_one=True)
    lu, perm, info = gbtrf_band_dist(ad, kl, ku, lookahead=la, bcast_impl=bi)
    bd = from_dense(b, mesh, nb)
    pb = permute_rows_dist(bd, perm)
    y = trsm_dist(lu, pb, Uplo.Lower, Op.NoTrans, Diag.Unit, lookahead=la,
                  bcast_impl=bi)
    x = trsm_dist(lu, y, Uplo.Upper, Op.NoTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


@instrument("getrf_mesh")
def getrf_mesh(
    a: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[DistMatrix, jax.Array, jax.Array]:
    """Distributed partial-pivot LU — the reference's default getrf
    (src/getrf.cc:23-200): P A = L U with per-column argmax pivoting.
    Returns (LU, perm over the padded row space, info)."""
    # no pp ABFT variant exists yet (ft_on is unconsumed), but the
    # FaultTolerance x Checkpoint conflict must fail loudly here too
    _ft_on_, every = _resilience(opts)
    if every is not None:
        from ..ft.ckpt import getrf_pp_ckpt

        return getrf_pp_ckpt(
            from_dense(a, mesh, nb, diag_pad_one=True), every=every,
            bcast_impl=_bi(opts), num_monitor=_nm(opts),
        )
    return getrf_pp_dist(
        from_dense(a, mesh, nb, diag_pad_one=True), lookahead=_la(opts),
        bcast_impl=_bi(opts), num_monitor=_nm(opts),
    )


def _gesv_mesh_plain(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The direct factor-at-data-dtype general solve: partial-pivot
    factor, permute B, two trsm sweeps.  The whole solve under
    Option.MixedPrecision=off (trace-identical to the pre-mixed driver)
    and the fallback tier of the mixed ladder."""
    la, bi = _la(opts), _bi(opts)
    lu, perm, info = getrf_mesh(a, mesh, nb, opts)
    bd = from_dense(b, mesh, nb)
    pb = permute_rows_dist(bd, perm)
    y = trsm_dist(lu, pb, Uplo.Lower, Op.NoTrans, Diag.Unit, lookahead=la,
                  bcast_impl=bi)
    x = trsm_dist(lu, y, Uplo.Upper, Op.NoTrans, lookahead=la, bcast_impl=bi)
    return to_dense(x), info


@instrument("gesv_mesh")
def gesv_mesh(
    a: jax.Array, b: jax.Array, mesh: Mesh, nb: int = _DEFAULT_NB,
    opts: Optional[Options] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Distributed general solve with partial pivoting (src/gesv.cc
    default MethodLU::PartialPiv).  f64 inputs route through the
    mixed-precision ladder by default — f32 partial-pivot factor + fused
    f64 refinement, GMRES-IR escalation, full-f64 fallback
    (Option.MixedPrecision; dist_refine.py) — because on TPU the f32
    factor runs ~40x the emulated-f64 rate (pre-PR-1 figure, not
    measured on this code).
    Option.MixedPrecision=off (or non-f64 dtype) runs the direct path,
    trace-identical to the pre-mixed driver."""
    from .dist_refine import mixed_mesh_route

    routed = mixed_mesh_route(
        "gesv", a, b, mesh, nb, opts,
        lambda: _gesv_mesh_plain(a, b, mesh, nb, opts),
    )
    if routed is not None:
        return routed
    return _gesv_mesh_plain(a, b, mesh, nb, opts)
