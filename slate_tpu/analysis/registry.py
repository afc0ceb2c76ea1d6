"""Driver registry: every distributed entry point slate_lint traces.

Each entry knows how to build synthetic operands on the shared 8-device
CPU mesh and return a zero-argument-closure + args pair for
``jax.make_jaxpr``.  Problem sizes are chosen so every kernel loop has a
trip count > 1 (the loop-audit check keys on scoped multiplicities) while
staying cheap to trace: n = 96, nb = 8 on a 2 x 4 grid gives a 12 x 12
tile grid, already a multiple of lcm(2, 4).

Registering a driver is the act of putting it under the invariant gate —
new distributed kernels should add themselves here.

Entries additionally DECLARE their option contracts (``contracts=``):
each ``Contract(option, klass, base)`` names an ``Option`` the variant
consumes and the machine-checkable class its docs/tests claim —
``off_jaxpr_identical`` (the entry's jaxpr equals its base's, or its own
re-trace under the option's off-forcing context), ``zero_extra_collectives``
(audited comm-record multiset equal to the base's), ``bytes_invariant``
(audited comm volume equal to the base's).  ``python -m
slate_tpu.analysis.contracts`` proves every declared cell and fails any
``*_num`` / ``*_ckpt*`` / ``*_abft*`` / ``*_flight`` / ``*_queue``
naming-convention variant whose contract is undeclared — a new driver cannot ship with a
claimed-but-unproven contract.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

from ..types import Option

N = 96
NB = 8
GRID = (2, 4)


@dataclass(frozen=True)
class Contract:
    """One auto-proven contract cell: this entry, crossed with one
    Option it consumes, claims ``klass`` against ``base`` (another
    registry entry; None compares the entry against its own re-trace
    under the option's off-forcing context — see contracts._off_context).
    ``"obs"`` as the option marks the observability layer (not an Option
    enum member: obs is ambient, forced on via obs.force_enabled)."""

    option: object
    klass: str
    base: Optional[str] = None

    def option_name(self) -> str:
        return self.option.name if isinstance(self.option, Option) else \
            str(self.option)


CONTRACT_CLASSES = (
    "off_jaxpr_identical", "zero_extra_collectives", "bytes_invariant",
)


@dataclass
class DriverSpec:
    name: str
    build: Callable  # ctx -> (fn, args)
    tags: Tuple[str, ...] = ()
    contracts: Tuple[Contract, ...] = ()


@dataclass
class DonationSpec:
    name: str
    build: Callable  # ctx -> (fn, args, donate_argnums)


REGISTRY: Dict[str, DriverSpec] = {}
DONATIONS: Dict[str, DonationSpec] = {}


def register(name: str, tags: Sequence[str] = (),
             contracts: Sequence[Contract] = ()):
    for c in contracts:
        if c.klass not in CONTRACT_CLASSES:
            raise ValueError(
                f"{name}: unknown contract class {c.klass!r}; expected "
                f"one of {CONTRACT_CLASSES}"
            )

    def deco(build):
        REGISTRY[name] = DriverSpec(name, build, tuple(tags),
                                    tuple(contracts))
        return build

    return deco


def register_donation(name: str):
    def deco(build):
        DONATIONS[name] = DonationSpec(name, build)
        return build

    return deco


@dataclass
class Ctx:
    """Shared trace context: mesh + cached operands."""

    mesh: object
    p: int
    q: int
    _cache: dict = field(default_factory=dict)

    def _get(self, key, make):
        if key not in self._cache:
            self._cache[key] = make()
        return self._cache[key]

    def dense(self, dtype="float64", kind="general"):
        import numpy as np
        import jax.numpy as jnp

        def make():
            rng = np.random.default_rng(0)
            a = rng.standard_normal((N, N))
            if kind == "spd":
                a = a @ a.T / N + 2 * np.eye(N)
            elif kind == "tril":
                a = np.tril(a) + N * np.eye(N)
            return jnp.asarray(a, dtype)

        return self._get(("dense", dtype, kind), make)

    def dist(self, dtype="float64", kind="general", diag_pad=False):
        from ..parallel.dist import from_dense

        return self._get(
            ("dist", dtype, kind, diag_pad),
            lambda: from_dense(
                self.dense(dtype, kind), self.mesh, NB, diag_pad_one=diag_pad
            ),
        )

    def dist_thin(self, dtype="float64"):
        import jax.numpy as jnp
        from ..parallel.dist import from_dense

        return self._get(
            ("thin", dtype),
            lambda: from_dense(self.dense_thin(dtype), self.mesh, NB),
        )

    def dense_thin(self, dtype="float64"):
        import numpy as np
        import jax.numpy as jnp

        def make():
            rng = np.random.default_rng(1)
            return jnp.asarray(rng.standard_normal((N, 2 * NB)), dtype)

        return self._get(("dense_thin", dtype), make)


def make_ctx() -> Ctx:
    import jax
    from ..parallel.mesh import make_mesh

    devs = jax.devices("cpu")[: GRID[0] * GRID[1]]
    mesh = make_mesh(*GRID, devices=devs)
    return Ctx(mesh=mesh, p=GRID[0], q=GRID[1])


# ---------------------------------------------------------------------------
# distributed drivers under the gate
# ---------------------------------------------------------------------------


@register("gemm_summa_c")
def _gemm_c(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return (lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC)), (a, b)


@register("gemm_summa_a")
def _gemm_a(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist_thin()
    return (lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmA)), (a, b)


@register("gemm_summa_f32", tags=("upcast-probe",))
def _gemm_f32(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist("float32"), ctx.dist("float32")
    return (lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC)), (a, b)


@register("potrf_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _potrf(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return potrf_dist, (a,)


@register("pbtrf_band_dist")
def _pbtrf(ctx):
    from ..parallel.dist_chol import pbtrf_band_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return (lambda x: pbtrf_band_dist(x, 2 * NB)), (a,)


@register("getrf_nopiv_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _getrf_nopiv(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return getrf_nopiv_dist, (a,)


@register("getrf_pp_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _getrf_pp(ctx):
    from ..parallel.dist_lu import getrf_pp_dist

    a = ctx.dist(diag_pad=True)
    return getrf_pp_dist, (a,)


@register("getrf_tntpiv_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _getrf_tnt(ctx):
    from ..parallel.dist_lu import getrf_tntpiv_dist

    a = ctx.dist(diag_pad=True)
    return getrf_tntpiv_dist, (a,)


@register("gbtrf_band_dist")
def _gbtrf(ctx):
    from ..parallel.dist_lu import gbtrf_band_dist

    a = ctx.dist(diag_pad=True)
    return (lambda x: gbtrf_band_dist(x, 2 * NB, 2 * NB)), (a,)


@register("permute_rows_dist")
def _permute(ctx):
    import jax.numpy as jnp
    from ..parallel.dist_lu import permute_rows_dist

    b = ctx.dist()
    nrows = b.mt * b.nb
    perm = jnp.arange(nrows)[::-1]
    return permute_rows_dist, (b, perm)


@register("trsm_dist_lower")
def _trsm(ctx):
    from ..parallel.dist_trsm import trsm_dist
    from ..types import Op, Uplo

    a = ctx.dist(kind="tril", diag_pad=True)
    b = ctx.dist_thin()
    return (lambda x, y: trsm_dist(x, y, Uplo.Lower, Op.NoTrans)), (a, b)


@register("trsm_dist_trans")
def _trsm_t(ctx):
    from ..parallel.dist_trsm import trsm_dist
    from ..types import Op, Uplo

    a = ctx.dist(kind="tril", diag_pad=True)
    b = ctx.dist_thin()
    return (lambda x, y: trsm_dist(x, y, Uplo.Lower, Op.Trans)), (a, b)


@register("hemm_summa")
def _hemm(ctx):
    from ..parallel.dist_blas3 import hemm_summa
    from ..types import MethodHemm, Side, Uplo

    a, b = ctx.dist(kind="spd"), ctx.dist()
    return (
        lambda x, y: hemm_summa(
            Side.Left, 1.0, x, y, uplo=Uplo.Lower, method=MethodHemm.HemmC
        )
    ), (a, b)


@register("hemm_summa_a")
def _hemm_a(ctx):
    from ..parallel.dist_blas3 import hemm_summa
    from ..types import MethodHemm, Side, Uplo

    a, b = ctx.dist(kind="spd"), ctx.dist_thin()
    return (
        lambda x, y: hemm_summa(
            Side.Left, 1.0, x, y, uplo=Uplo.Lower, method=MethodHemm.HemmA
        )
    ), (a, b)


@register("trmm_dist")
def _trmm(ctx):
    from ..parallel.dist_blas3 import trmm_dist
    from ..types import Diag, Op, Side, Uplo

    a = ctx.dist(kind="tril", diag_pad=True)
    b = ctx.dist()
    return (
        lambda x, y: trmm_dist(
            Side.Left, Uplo.Lower, Op.NoTrans, Diag.NonUnit, 1.0, x, y
        )
    ), (a, b)


@register("her2k_dist")
def _her2k(ctx):
    from ..parallel.dist_blas3 import her2k_dist

    a, b = ctx.dist(), ctx.dist()
    return (lambda x, y: her2k_dist(1.0, x, y)), (a, b)


@register("transpose_dist")
def _transpose(ctx):
    from ..parallel.dist_blas3 import transpose_dist

    a = ctx.dist()
    return transpose_dist, (a,)


@register("herk_dist")
def _herk(ctx):
    from ..parallel.dist_aux import herk_dist

    a = ctx.dist()
    return (lambda x: herk_dist(1.0, x)), (a,)


@register("norm_dist_one")
def _norm(ctx):
    from ..parallel.dist_aux import norm_dist
    from ..types import Norm

    a = ctx.dist()
    return (lambda x: norm_dist(Norm.One, x)), (a,)


@register("geqrf_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _geqrf(ctx):
    from ..parallel.dist_qr import geqrf_dist

    a = ctx.dist()
    return geqrf_dist, (a,)


@register("unmqr_dist")
def _unmqr(ctx):
    from ..parallel.dist_qr import geqrf_dist, unmqr_dist

    a = ctx.dist()
    f = geqrf_dist(a)  # concrete factor once; the trace covers unmqr
    b = ctx.dist_thin()
    return unmqr_dist, (f, b)


@register("he2hb_dist", contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _he2hb(ctx):
    from ..parallel.dist_twostage import he2hb_dist

    a = ctx.dist(kind="spd")
    return he2hb_dist, (a,)


@register("ge2tb_dist")
def _ge2tb(ctx):
    from ..parallel.dist_twostage import ge2tb_dist

    a = ctx.dist()
    return ge2tb_dist, (a,)


@register("stedc_dist")
def _stedc(ctx):
    import numpy as np
    import jax.numpy as jnp
    from ..parallel.dist_stedc import stedc_dist

    rng = np.random.default_rng(2)
    d = jnp.asarray(rng.standard_normal(256))
    e = jnp.asarray(rng.standard_normal(255))
    return (lambda dd, ee: stedc_dist(dd, ee, ctx.mesh)), (d, e)


# ---------------------------------------------------------------------------
# donation contracts (invariant 3)
# ---------------------------------------------------------------------------


@register_donation("potrf_ll_staged_step")
def _don_step(ctx):
    import numpy as np
    import jax.numpy as jnp
    from ..linalg.chol import _potrf_ll_panel_step

    rng = np.random.default_rng(3)
    n = 256
    a = rng.standard_normal((n, n))
    ap = jnp.asarray(a @ a.T + n * np.eye(n))
    return (lambda x: _potrf_ll_panel_step(x, 64, 64)), (ap,), (0,)


@register_donation("potrf_ll_staged_finale")
def _don_finale(ctx):
    import numpy as np
    import jax.numpy as jnp
    from ..linalg.chol import _potrf_ll_finale_jit

    # the staged driver only donates the finale when the padded shape
    # equals the true shape (chol.potrf_left_looking_staged); lint checks
    # that exact-shape contract against the REAL jitted stage, so a future
    # change to its outputs re-enters the gate
    n = 256
    ap = jnp.asarray(np.random.default_rng(4).standard_normal((n, n)))
    return (lambda x: _potrf_ll_finale_jit(x, n=n)), (ap,), (0,)


# ---------------------------------------------------------------------------
# lookahead variants (ISSUE 3): the pipelined schedules under the gate.
# The default entries above already trace depth 1 (the Option.Lookahead
# default); these pin the strict depth-0 schedule and a deeper prefetch so
# both ends of the pipeline stay lint-green (axis names, audit coverage,
# HIGHEST dots on the narrow/bulk einsum splits).
# ---------------------------------------------------------------------------


@register("gemm_summa_la0", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "gemm_summa_c"),
))
def _gemm_la0(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return (
        lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC, lookahead=0)
    ), (a, b)


@register("gemm_summa_la2", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "gemm_summa_c"),
))
def _gemm_la2(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return (
        lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC, lookahead=2)
    ), (a, b)


@register("potrf_dist_la0", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "potrf_dist"),
))
def _potrf_la0(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return (lambda x: potrf_dist(x, lookahead=0)), (a,)


@register("trsm_dist_la2", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "trsm_dist_lower"),
))
def _trsm_la2(ctx):
    from ..parallel.dist_trsm import trsm_dist
    from ..types import Op, Uplo

    a = ctx.dist(kind="tril", diag_pad=True)
    b = ctx.dist_thin()
    return (
        lambda x, y: trsm_dist(x, y, Uplo.Lower, Op.NoTrans, lookahead=2)
    ), (a, b)


@register("getrf_nopiv_dist_la0", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "getrf_nopiv_dist"),
))
def _getrf_nopiv_la0(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return (lambda x: getrf_nopiv_dist(x, lookahead=0)), (a,)


@register("getrf_pp_dist_la0", tags=("lookahead",), contracts=(
    Contract(Option.Lookahead, "bytes_invariant", "getrf_pp_dist"),
))
def _getrf_pp_la0(ctx):
    from ..parallel.dist_lu import getrf_pp_dist

    a = ctx.dist(diag_pad=True)
    return (lambda x: getrf_pp_dist(x, lookahead=0)), (a,)


# ---------------------------------------------------------------------------
# broadcast-engine variants (ISSUE 5): the default entries above already
# trace the engine lowering (Option.BcastImpl defaults to auto → doubling
# on the power-of-two 2 x 4 grid), so every driver's ppermute schedule is
# under the gate by default.  These pin the OTHER lowerings — the legacy
# masked-psum fallback and the explicit ring pipeline — so all three stay
# lint-green (declared axis names on the ppermute hops, audit_scope
# coverage with the cond-aware loop counting, HIGHEST dots).
# ---------------------------------------------------------------------------


def _with_impl(impl, call):
    from ..parallel.comm import use_bcast_impl

    def fn(*args):
        with use_bcast_impl(impl):
            return call(*args)

    return fn


@register("gemm_summa_psum", tags=("bcast",))
def _gemm_psum(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return _with_impl(
        "psum", lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC)
    ), (a, b)


@register("gemm_summa_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "gemm_summa_c"),
))
def _gemm_ring(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return _with_impl(
        "ring", lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC)
    ), (a, b)


@register("potrf_dist_psum", tags=("bcast",))
def _potrf_psum(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return _with_impl("psum", potrf_dist), (a,)


@register("potrf_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "potrf_dist"),
))
def _potrf_ring(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return _with_impl("ring", potrf_dist), (a,)


@register("getrf_nopiv_dist_psum", tags=("bcast",))
def _getrf_nopiv_psum(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return _with_impl("psum", getrf_nopiv_dist), (a,)


@register("getrf_nopiv_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "getrf_nopiv_dist"),
))
def _getrf_nopiv_ring(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return _with_impl("ring", getrf_nopiv_dist), (a,)


@register("geqrf_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "geqrf_dist"),
))
def _geqrf_ring(ctx):
    """CAQR under the explicit ring lowering (ISSUE 6 satellite: the
    formerly-unthreaded collectives now consume the engine)."""
    from ..parallel.dist_qr import geqrf_dist

    a = ctx.dist()
    return (lambda x: geqrf_dist(x, bcast_impl="ring")), (a,)


@register("stedc_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "stedc_dist"),
))
def _stedc_ring(ctx):
    import numpy as np
    import jax.numpy as jnp
    from ..parallel.dist_stedc import stedc_dist

    rng = np.random.default_rng(2)
    d = jnp.asarray(rng.standard_normal(256))
    e = jnp.asarray(rng.standard_normal(255))
    return (lambda dd, ee: stedc_dist(dd, ee, ctx.mesh, bcast_impl="ring")), (d, e)


@register("herk_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "herk_dist"),
))
def _herk_ring(ctx):
    from ..parallel.dist_aux import herk_dist

    a = ctx.dist()
    return (lambda x: herk_dist(1.0, x, bcast_impl="ring")), (a,)


@register("trsm_dist_psum", tags=("bcast",))
def _trsm_psum(ctx):
    from ..parallel.dist_trsm import trsm_dist
    from ..types import Op, Uplo

    a = ctx.dist(kind="tril", diag_pad=True)
    b = ctx.dist_thin()
    return _with_impl(
        "psum", lambda x, y: trsm_dist(x, y, Uplo.Lower, Op.NoTrans)
    ), (a, b)


def _chase_operands(ctx):
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    n, w = N, NB
    nsweeps, hops = n - 2, -(-(n - 1) // w)
    vs = jnp.asarray(rng.standard_normal((nsweeps, hops, w)))
    taus = jnp.asarray(rng.standard_normal((nsweeps, hops)))
    z = jnp.asarray(rng.standard_normal((n, n)))
    return vs, taus, z, n, w


@register("chase_apply_dist", tags=("bcast",))
def _chase_apply(ctx):
    """The stage-2 back-transform's block broadcast (ISSUE 9 satellite):
    formerly the last waived tuple-axis masked psum, now a two-hop
    rooted broadcast through the engine — under the gate at the default
    lowering (auto → doubling on the 2x4 grid)."""
    from ..parallel.dist_twostage import chase_apply_dist

    vs, taus, z, n, w = _chase_operands(ctx)
    return (lambda v, t, zz: chase_apply_dist(v, t, zz, n, w, ctx.mesh)), \
        (vs, taus, z)


@register("chase_apply_dist_psum", tags=("bcast",))
def _chase_apply_psum(ctx):
    from ..parallel.dist_twostage import chase_apply_dist

    vs, taus, z, n, w = _chase_operands(ctx)
    return (lambda v, t, zz: chase_apply_dist(
        v, t, zz, n, w, ctx.mesh, bcast_impl="psum")), (vs, taus, z)


@register("chase_apply_dist_ring", tags=("bcast",), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "chase_apply_dist"),
))
def _chase_apply_ring(ctx):
    from ..parallel.dist_twostage import chase_apply_dist

    vs, taus, z, n, w = _chase_operands(ctx)
    return (lambda v, t, zz: chase_apply_dist(
        v, t, zz, n, w, ctx.mesh, bcast_impl="ring")), (vs, taus, z)


# ---------------------------------------------------------------------------
# observability wrappers (ISSUE 2): the same kernels traced WITH obs on
# ---------------------------------------------------------------------------


@register("potrf_dist_obs", tags=("obs",), contracts=(
    Contract("obs", "zero_extra_collectives", "potrf_dist"),
))
def _potrf_obs(ctx):
    """potrf_dist traced with observability enabled: proves the obs layer
    (driver spans, TraceAnnotation bridge, comm-audit absorption with
    propagate=True) neither changes the kernel jaxpr invariants nor hides
    audit records from the loop-audit check."""
    from .. import obs
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)

    def fn(x):
        with obs.force_enabled():
            with obs.driver_span("lint_obs_probe"):
                return potrf_dist(x)

    return fn, (a,)


@register("gemm_summa_obs", tags=("obs",), contracts=(
    Contract("obs", "zero_extra_collectives", "gemm_summa_c"),
))
def _gemm_obs(ctx):
    from .. import obs
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()

    def fn(x, y):
        with obs.force_enabled():
            return gemm_summa(1.0, x, y, method=MethodGemm.GemmC)

    return fn, (a, b)


# ---------------------------------------------------------------------------
# ABFT variants (ISSUE 4): the checksum-carrying kernels under the gate.
# Each traces encode -> augmented kernel -> checksum-residual verify on
# the shared mesh; the *_detect entries run a disarmed fault spec, the
# *_correct entries an ARMED one, so both halves of the injection masks
# (and the extra checksum-tile broadcasts) stay lint-green: declared
# axis names, audit_scope loop coverage, Precision.HIGHEST dots.
# ---------------------------------------------------------------------------


def _ft_spec(armed: bool, op: str):
    """Fault spec arrays for a registry trace: disarmed zeros, or one
    deterministic armed fault (the spec is a DYNAMIC kernel operand, so
    both trace the same jaxpr paths — armed pins the full hit masks with
    concrete in-range targets)."""
    import jax.numpy as jnp
    from ..ft import inject

    ints, vals = inject.spec_arrays(op)  # no active plan: zeros
    if armed:
        f = inject.seeded_fault(7, op, nt=N // NB, grid=GRID,
                                phase="trailing" if op == "gemm" else "panel")
        ints[0] = (1, f.k, f.phase_id(), f.ti, f.tj, f.r, f.c, f.mode)
        vals[0] = f.value
    return jnp.asarray(ints), jnp.asarray(vals)


def _ft_gemm_build(ctx, armed):
    from ..ft import abft
    from ..parallel.comm import resolve_bcast_impl
    from ..parallel.dist import DistMatrix, from_dense, to_dense

    a, b = ctx.dense(), ctx.dense()
    fi, fv = _ft_spec(armed, "gemm")

    def fn(x, y):
        a_aug, b_aug, c_aug, mt, kt, nt = abft._encode_gemm(x, y, None, NB, ctx.mesh)
        ad = from_dense(a_aug, ctx.mesh, NB)
        bd = from_dense(b_aug, ctx.mesh, NB)
        cd = from_dense(c_aug, ctx.mesh, NB)
        out = abft._ft_summa_jit(
            ad.tiles, bd.tiles, cd.tiles, 1.0, 0.0,
            ctx.mesh, ctx.p, ctx.q, kt, 1, resolve_bcast_impl(), fi, fv,
        )
        dense = to_dense(DistMatrix(
            tiles=out, m=a_aug.shape[0], n=b_aug.shape[1], nb=NB, mesh=ctx.mesh,
        ))
        return abft._gemm_residual(dense, NB, mt, nt)

    return fn, (a, b)


def _ft_factor_build(ctx, op, armed):
    from ..ft import abft
    from ..parallel.comm import resolve_bcast_impl
    from ..parallel.dist import DistMatrix, from_dense, to_dense

    is_lu = op == "getrf_nopiv"
    a = ctx.dense(kind="tril" if is_lu else "spd")
    fi, fv = _ft_spec(armed, op)
    kern = abft._ft_lu_jit if is_lu else abft._ft_potrf_jit

    def fn(x):
        aug, mt, _ = abft._encode_factor(x, NB, ctx.mesh, with_cols=is_lu)
        d = from_dense(aug, ctx.mesh, NB)
        out_t, info = kern(
            d.tiles, ctx.mesh, ctx.p, ctx.q, mt, 1, resolve_bcast_impl(), fi, fv,
        )
        dense = to_dense(DistMatrix(
            tiles=out_t, m=aug.shape[0], n=aug.shape[1], nb=NB, mesh=ctx.mesh,
        ))
        resid = (abft._lu_residual if is_lu else abft._potrf_residual)(dense, NB, mt)
        return resid, info

    return fn, (a,)


@register("gemm_abft_detect", tags=("ft",))
def _ft_gemm_detect(ctx):
    return _ft_gemm_build(ctx, armed=False)


@register("gemm_abft_correct", tags=("ft",), contracts=(
    Contract(Option.FaultTolerance, "zero_extra_collectives",
             "gemm_abft_detect"),
))
def _ft_gemm_correct(ctx):
    return _ft_gemm_build(ctx, armed=True)


@register("potrf_abft_detect", tags=("ft",))
def _ft_potrf_detect(ctx):
    return _ft_factor_build(ctx, "potrf", armed=False)


@register("potrf_abft_correct", tags=("ft",), contracts=(
    Contract(Option.FaultTolerance, "zero_extra_collectives",
             "potrf_abft_detect"),
))
def _ft_potrf_correct(ctx):
    return _ft_factor_build(ctx, "potrf", armed=True)


@register("getrf_nopiv_abft_detect", tags=("ft",))
def _ft_lu_detect(ctx):
    return _ft_factor_build(ctx, "getrf_nopiv", armed=False)


@register("getrf_nopiv_abft_correct", tags=("ft",), contracts=(
    Contract(Option.FaultTolerance, "zero_extra_collectives",
             "getrf_nopiv_abft_detect"),
))
def _ft_lu_correct(ctx):
    return _ft_factor_build(ctx, "getrf_nopiv", armed=True)


# ---------------------------------------------------------------------------
# fused trailing-update variants (PR 20): the Option.UpdateImpl lowerings
# under the gate for the three ops the option scopes (SUMMA consume,
# potrf trailing herk-gemm, LU-nopiv trailing gemm).  Per op and per
# broadcast engine (psum AND ring) the ``*_upd_xla`` entry proves the
# explicit xla pole is trace-IDENTICAL to the base entry's default chain
# (auto resolves to xla on the CPU trace mesh), and the ``*_upd_pallas``
# entry proves the fused one-dispatch kernel moves exactly the bytes of
# its xla twin (the ScheduleModel/comm-audit invariance the option
# promises by construction).
# ---------------------------------------------------------------------------


def _upd_entry(call, impl, bcast):
    from ..parallel.comm import use_bcast_impl
    from ..ops.pallas_ops import use_update_impl

    def fn(*args):
        with use_bcast_impl(bcast), use_update_impl(impl):
            return call(*args)

    return fn


def _register_upd_cells(stem, base_psum, base_ring, build):
    """One psum + one ring (xla off-identity, pallas bytes-invariant)
    quadruple for a driver under Option.UpdateImpl."""
    for bcast, base in (("psum", base_psum), ("ring", base_ring)):
        sfx = "" if bcast == "psum" else "_ring"
        xla_name = f"{stem}_upd_xla{sfx}"

        def _mk(impl, bcast=bcast):
            def _build(ctx, impl=impl, bcast=bcast):
                call, args = build(ctx)
                return _upd_entry(call, impl, bcast), args

            return _build

        register(xla_name, tags=("update",), contracts=(
            Contract(Option.UpdateImpl, "off_jaxpr_identical", base),
        ))(_mk("xla"))
        register(f"{stem}_upd_pallas{sfx}", tags=("update",), contracts=(
            Contract(Option.UpdateImpl, "bytes_invariant", xla_name),
        ))(_mk("pallas"))


def _upd_gemm_build(ctx):
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    return (
        lambda x, y: gemm_summa(1.0, x, y, method=MethodGemm.GemmC)
    ), (a, b)


def _upd_potrf_build(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return potrf_dist, (a,)


def _upd_getrf_build(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return getrf_nopiv_dist, (a,)


_register_upd_cells(
    "gemm_summa", "gemm_summa_psum", "gemm_summa_ring", _upd_gemm_build
)
_register_upd_cells(
    "potrf_dist", "potrf_dist_psum", "potrf_dist_ring", _upd_potrf_build
)
_register_upd_cells(
    "getrf_nopiv_dist", "getrf_nopiv_dist_psum", "getrf_nopiv_dist_ring",
    _upd_getrf_build,
)


# ---------------------------------------------------------------------------
# Mixed-precision mesh programs (ISSUE 8): the f32-factor + fused f64
# refinement solvers and the distributed GMRES-IR escalation tier under
# the gate.  Each traces factor -> fused while_loop refinement (f32 trsm
# sweeps, residual SUMMA, Inf-norm reductions, mesh-reduced norms in the
# carry) end to end; the *_ring variants pin the explicit ring lowering
# through the whole mixed program (factor panel broadcasts AND the
# refinement loop's residual broadcasts), and the *_ozaki variant traces
# the int8 digit-plane residual SUMMA (integer dots are exempt from the
# HIGHEST-precision rule by construction — see jaxpr_checks).
# ---------------------------------------------------------------------------


def _mixed_build(ctx, kind, ring=False, residual=None, gmres=False):
    from ..parallel import dist_refine

    a = ctx.dense(kind="spd" if kind == "posv" else "general")
    if kind == "gesv":
        import jax.numpy as jnp

        a = a + N * jnp.eye(N, dtype=a.dtype)  # keep the f32 factor sane
    b = ctx.dense_thin()
    opts = {}
    if ring:
        from ..types import Option

        opts[Option.BcastImpl] = "ring"
    if residual:
        from ..types import Option

        opts[Option.ResidualImpl] = residual
    if gmres:
        drv = (dist_refine.posv_mixed_gmres_mesh if kind == "posv"
               else dist_refine.gesv_mixed_gmres_mesh)
        # ONE RHS column: the driver's per-column loop reuses one compiled
        # program, so extra columns would be jit-cache-hit call sites —
        # counted loop eqns with no audit records (the loop-audit check
        # keys on records; the per-column volume rides audit_scope(ncols))
        b1 = b[:, :1]
        return (lambda x, y: drv(x, y, ctx.mesh, NB, opts=opts, restart=8)), (a, b1)
    drv = (dist_refine.posv_mixed_mesh if kind == "posv"
           else dist_refine.gesv_mixed_mesh)
    return (lambda x, y: drv(x, y, ctx.mesh, NB, opts=opts)), (a, b)


@register("gesv_mixed_mesh", tags=("mixed",))
def _gesv_mixed(ctx):
    return _mixed_build(ctx, "gesv")


@register("posv_mixed_mesh", tags=("mixed",), contracts=(
    Contract(Option.NumMonitor, "off_jaxpr_identical"),
))
def _posv_mixed(ctx):
    return _mixed_build(ctx, "posv")


@register("gesv_mixed_mesh_ring", tags=("mixed", "bcast"), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "gesv_mixed_mesh"),
))
def _gesv_mixed_ring(ctx):
    return _mixed_build(ctx, "gesv", ring=True)


@register("posv_mixed_mesh_ring", tags=("mixed", "bcast"), contracts=(
    Contract(Option.BcastImpl, "bytes_invariant", "posv_mixed_mesh"),
))
def _posv_mixed_ring(ctx):
    return _mixed_build(ctx, "posv", ring=True)


@register("gesv_mixed_mesh_ozaki", tags=("mixed",))
def _gesv_mixed_ozaki(ctx):
    return _mixed_build(ctx, "gesv", residual="ozaki")


@register("gesv_mixed_gmres_mesh", tags=("mixed",))
def _gesv_mixed_gmres(ctx):
    return _mixed_build(ctx, "gesv", gmres=True)


@register("posv_mixed_gmres_mesh", tags=("mixed",))
def _posv_mixed_gmres(ctx):
    return _mixed_build(ctx, "posv", gmres=True)


@register_donation("ir_refine_rhs")
def _don_ir_rhs(ctx):
    """The fused refinement program donates the RHS tile stack: the final
    solution (and residual) tiles share its aval, so XLA can alias the
    buffer once the last residual consumes b — checked against the REAL
    jitted program so an output change re-enters the gate."""
    from ..parallel import dist_refine
    from ..parallel.dist import from_dense
    from ..parallel.dist_chol import potrf_dist

    import jax.numpy as jnp

    ad = ctx.dist(kind="spd", diag_pad=True)
    a32 = dist_refine._astype_dist(ad, jnp.float32)
    l, info = potrf_dist(a32)
    bd = from_dense(ctx.dense_thin(), ctx.mesh, NB)

    def fn(bt):
        return dist_refine._ir_posv_jit(
            ad.tiles, bt, l.tiles, info, ctx.mesh, ctx.p, ctx.q, N, 2 * NB,
            NB, 30, None, "auto", "f64",
        )

    return fn, (bd.tiles,), (0,)


# ---------------------------------------------------------------------------
# Flight-recorder variants (ISSUE 7): the step-dispatch phase programs
# under the gate.  Each traces one full flight k-step (panel -> bcast ->
# narrow/bulk composition via obs.flight.step_traceable) with k a RUNTIME
# scalar, so the per-step jits' actual jaxpr surface — rooted broadcasts
# through the engine's lax.switch dispatch, HIGHEST-precision update
# einsums, audited collectives with declared axis names — stays
# lint-green alongside the fused kernels.
# ---------------------------------------------------------------------------


def _flight_build(ctx, op, kind):
    import jax.numpy as jnp

    from ..obs.flight import step_traceable

    a = ctx.dist(kind=kind, diag_pad=(op != "summa"))
    mtl, ntl = a.tiles.shape[0] // ctx.p, a.tiles.shape[1] // ctx.q
    fn = step_traceable(op, ctx.mesh, ctx.p, ctx.q, a.nt, mtl, ntl, a.nb)
    k = jnp.asarray(1)  # default int dtype (x64-aware): matches the literal
    # indices inside bcast_diag_tile's dynamic_slice
    if op == "summa":
        b = ctx.dist()
        return fn, (a.tiles, b.tiles, k)
    return fn, (a.tiles, k)


@register("gemm_summa_flight", tags=("flight",), contracts=(
    Contract("obs", "off_jaxpr_identical"),
))
def _gemm_flight(ctx):
    return _flight_build(ctx, "summa", "general")


@register("potrf_dist_flight", tags=("flight",), contracts=(
    Contract("obs", "off_jaxpr_identical"),
))
def _potrf_flight(ctx):
    return _flight_build(ctx, "potrf", "spd")


@register("getrf_nopiv_dist_flight", tags=("flight",), contracts=(
    Contract("obs", "off_jaxpr_identical"),
))
def _getrf_nopiv_flight(ctx):
    return _flight_build(ctx, "getrf_nopiv", "tril")


@register("geqrf_dist_flight", tags=("flight",), contracts=(
    Contract("obs", "off_jaxpr_identical"),
))
def _geqrf_flight(ctx):
    """One full CAQR flight k-step over the MULTI-ARRAY carry (ISSUE 15):
    panel -> three rooted column broadcasts -> trailing update + tree
    merge, composed through obs.flight.step_traceable with k a runtime
    scalar — proving the recorder's per-step programs add zero audited
    collectives beyond the fused kernel's schedule (the PR 10/14
    contract's flight sibling).  Carry shapes come from ckpt._multi_init,
    the one authority the drivers themselves use."""
    import jax.numpy as jnp

    from ..ft import ckpt
    from ..obs.flight import step_traceable

    a = ctx.dist()
    st = {}
    ckpt._multi_init("geqrf", a, st, a.nt)
    mtl, ntl = a.tiles.shape[0] // ctx.p, a.tiles.shape[1] // ctx.q
    fn = step_traceable("geqrf", ctx.mesh, ctx.p, ctx.q, a.nt, mtl, ntl,
                        a.nb)
    k = jnp.asarray(1)
    return fn, (a.tiles, st["tls"], st["tvs"], st["tts"], k)


@register("he2hb_flight", tags=("flight",), contracts=(
    Contract("obs", "off_jaxpr_identical"),
))
def _he2hb_flight(ctx):
    """One full he2hb flight k-step (rooted panel-column broadcast + row
    gather -> replicated panel QR -> distributed two-sided update) over
    the reflector/WY carry, k a runtime scalar (ISSUE 15)."""
    import jax.numpy as jnp

    from ..ft import ckpt
    from ..linalg.eig import _he2hb_panel_count
    from ..obs.flight import step_traceable

    a = ctx.dist(kind="spd")
    nsteps = _he2hb_panel_count(a.n, a.nb)
    st = {}
    ckpt._multi_init("he2hb", a, st, nsteps)
    mtl, ntl = a.tiles.shape[0] // ctx.p, a.tiles.shape[1] // ctx.q
    fn = step_traceable("he2hb", ctx.mesh, ctx.p, ctx.q, a.nt, mtl, ntl,
                        a.nb)
    k = jnp.asarray(1)
    return fn, (a.tiles, st["vqs"], st["tqs"], k)


# ---------------------------------------------------------------------------
# Numerics-monitored variants (ISSUE 10): the Option.NumMonitor=on
# lowerings under the gate.  The default entries above trace nm=off
# (jaxpr-identical to the pre-monitoring kernels); these pin the
# monitored k-loops — the gauge carries ride the same audited loops, the
# exit reductions are unaudited pmin/pmax with declared axis names (the
# _lu_info_dist class), so collective-axis, audit_scope coverage and
# HIGHEST-dot checks all see the monitored jaxpr surface.  The condest
# drivers trace the distributed Hager-Higham probe loop (a Python loop
# of mesh trsm solve pairs over a concrete factor, the unmqr pattern).
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Serving-runtime drivers (ISSUE 11): the stacked batch programs the
# executable cache pins (lax.map over the single-chip kernels — no
# collectives, but the HIGHEST-dot / donation / kwarg passes still apply
# to the mapped bodies), the block-diagonal packed mesh solve (a full
# distributed posv over a packed operand), and the presplit Ozaki SUMMA
# (A's digit planes entering as operands instead of being sliced
# in-kernel — the broadcast schedule must stay lint-identical).
# ---------------------------------------------------------------------------


def _serve_stack(ctx, kind="spd", B=2):
    import numpy as np
    import jax.numpy as jnp

    def make():
        rng = np.random.default_rng(11)
        g = rng.standard_normal((B, 4 * NB, 4 * NB))
        if kind == "spd":
            g = np.einsum("bij,bkj->bik", g, g) / (4 * NB) \
                + 2 * np.eye(4 * NB)[None]
        else:
            g = g + 4 * NB * np.eye(4 * NB)[None]
        return jnp.asarray(g)

    return ctx._get(("serve_stack", kind, B), make)


def _serve_rhs(ctx, B=2):
    import numpy as np
    import jax.numpy as jnp

    return ctx._get(("serve_rhs", B), lambda: jnp.asarray(
        np.random.default_rng(12).standard_normal((B, 4 * NB, 2))))


@register("posv_batched", tags=("serve",))
def _posv_batched(ctx):
    from ..serve.batch import posv_batched

    return posv_batched, (_serve_stack(ctx, "spd"), _serve_rhs(ctx))


@register("gesv_batched", tags=("serve",))
def _gesv_batched(ctx):
    from ..serve.batch import gesv_batched

    return gesv_batched, (_serve_stack(ctx, "general"), _serve_rhs(ctx))


@register("potrf_batched", tags=("serve",))
def _potrf_batched(ctx):
    from ..serve.batch import potrf_batched

    return potrf_batched, (_serve_stack(ctx, "spd"),)


@register("gemm_batched", tags=("serve",))
def _gemm_batched(ctx):
    from ..serve.batch import gemm_batched

    a = _serve_stack(ctx, "general")
    return (lambda x, y: gemm_batched(1.0, x, y)), (a, a)


@register("posv_packed_mesh", tags=("serve",))
def _posv_packed(ctx):
    """The block-diagonal packed mesh solve: two ragged problems through
    ONE distributed posv (mixed off keeps the trace the direct driver's
    — the packed path's own identity, not the refinement ladder's)."""
    import jax.numpy as jnp
    from ..parallel.drivers import posv_mesh
    from ..serve.batch import pack_block_diag
    from ..types import Option

    a1 = ctx.dense(kind="spd")
    a2 = jnp.eye(N, dtype="float64") * 2.0
    opts = {Option.MixedPrecision: "off"}

    def fn(x1, x2):
        a, _ = pack_block_diag([x1, x2], N)
        b = jnp.ones((2 * N, 2), x1.dtype)
        return posv_mesh(a, b, ctx.mesh, NB, opts)

    return fn, (a1, a2)


@register("posv_batched_traced", tags=("serve",), contracts=(
    Contract("obs", "off_jaxpr_identical", "posv_batched"),
    Contract("obs", "zero_extra_collectives", "posv_batched"),
))
def _posv_batched_traced(ctx):
    """The Router's stacked dispatch under an ARMED RequestTrace (ISSUE
    14): the request tracer is host-side only — phase spans, outcome
    accounting and the latency histogram live outside the jaxpr — so
    the traced program must be the plain batched driver with NO new
    collectives (the NumMonitor zero-extra-bytes contract's serving
    sibling; tests/test_serve.py additionally asserts jaxpr identity
    traced-vs-untraced)."""
    from .. import obs
    from ..serve import trace as serve_trace
    from ..serve.batch import posv_batched

    a, b = _serve_stack(ctx, "spd"), _serve_rhs(ctx)

    def fn(x, y):
        with obs.force_enabled():
            tr = serve_trace.new_trace("posv", x.shape[1], NB, str(x.dtype))
            with serve_trace.phase(tr, "solve"):
                out = posv_batched(x, y)
            serve_trace.finish(tr, "served")
        return out

    return fn, (a, b)


@register("posv_batched_queue", tags=("serve",), contracts=(
    Contract("serve_queue", "off_jaxpr_identical", "posv_batched"),
    Contract("serve_queue", "zero_extra_collectives", "posv_batched"),
))
def _posv_batched_queue(ctx):
    """The BatchQueue's stacked window dispatch (ISSUE 19): a closed
    window's program is ``queue.stacked_body`` — by construction the
    Router's own ``_build_batched`` body — so with the service layer off
    the dispatch is byte-identical to the direct batched driver.  The
    queue itself (windows, DRR, budgets) is host-side scheduling and
    must never reach the jaxpr."""
    from ..serve.queue import stacked_body

    return stacked_body("posv", "friendly"), (_serve_stack(ctx, "spd"),
                                              _serve_rhs(ctx))


@register("posv_packed_queue", tags=("serve",), contracts=(
    Contract("serve_queue", "off_jaxpr_identical", "posv_packed_mesh"),
    Contract("serve_queue", "zero_extra_collectives", "posv_packed_mesh"),
))
def _posv_packed_queue(ctx):
    """The BatchQueue's packed window dispatch: ``queue.packed_mesh_body``
    over the same two-problem block-diagonal operand as the
    ``posv_packed_mesh`` base.  AutoTune is pinned off and BlockSize
    pinned to the base's nb: the tuned table's nearest-n lookup WOULD
    resolve the n=96 winners for the 2N=192 packed operand (a different
    schedule, legitimately), and this cell isolates the queue plumbing —
    same options in, same program out."""
    import jax.numpy as jnp
    from ..serve.batch import pack_block_diag
    from ..serve.queue import packed_mesh_body
    from ..types import Option

    a1 = ctx.dense(kind="spd")
    a2 = jnp.eye(N, dtype="float64") * 2.0
    body, _merged = packed_mesh_body(
        ctx.mesh, 2 * N, "float64",
        {Option.MixedPrecision: "off", Option.BlockSize: NB,
         Option.AutoTune: "off"})

    def fn(x1, x2):
        a, _ = pack_block_diag([x1, x2], N)
        b = jnp.ones((2 * N, 2), x1.dtype)
        return body(a, b)

    return fn, (a1, a2)


@register("potrf_dist_traced", tags=("serve", "obs"), contracts=(
    Contract("obs", "off_jaxpr_identical", "potrf_dist"),
    Contract("obs", "zero_extra_collectives", "potrf_dist"),
))
def _potrf_dist_traced(ctx):
    """potrf_dist under an ARMED, tenant-carrying TraceContext with obs
    forced on (ISSUE 17): the trace-context spine — trace_id/tenant
    stamping on spans, StepEvents, mem samples and the tenant tag
    dimension on every registry write — is host-side only, so the
    traced program must be byte-for-byte the plain driver's: identical
    jaxpr AND identical audited comm-record multiset.  NumMonitor is
    pinned off: obs-on resolves its ``auto`` to the gauge-carrying
    kernel (NumMonitor's OWN proven cells), which would mask what this
    cell isolates — the spine."""
    from .. import obs
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    ctx_obj = obs.TraceContext(obs.new_trace_id(), tenant="lint",
                               klass="friendly", rid=0, op="potrf")

    def fn(x):
        with obs.force_enabled(), obs.use_context(ctx_obj):
            with obs.driver_span("lint_traced_probe"):
                return potrf_dist(x, num_monitor="off")

    return fn, (a,)


@register("gemm_summa_traced", tags=("serve", "obs"), contracts=(
    Contract("obs", "off_jaxpr_identical", "gemm_summa_c"),
    Contract("obs", "zero_extra_collectives", "gemm_summa_c"),
))
def _gemm_summa_traced(ctx):
    """gemm_summa under the same armed TraceContext — the broadcast-
    engine kernel family's cell of the spine contract (the hop records
    the span absorbs into sched.link_bytes are audit-time artifacts,
    not collectives added to the program)."""
    from .. import obs
    from ..parallel.summa import gemm_summa
    from ..types import MethodGemm

    a, b = ctx.dist(), ctx.dist()
    ctx_obj = obs.TraceContext(obs.new_trace_id(), tenant="lint",
                               klass="friendly", rid=1, op="gemm")

    def fn(x, y):
        with obs.force_enabled(), obs.use_context(ctx_obj):
            return gemm_summa(1.0, x, y, method=MethodGemm.GemmC)

    return fn, (a, b)


@register("gemm_summa_ozaki_presplit", tags=("serve", "mixed"))
def _gemm_ozaki_presplit(ctx):
    """The stationary-A Ozaki SUMMA: digit planes enter as operands
    (ozaki_presplit) — same broadcast engine schedule, same audited
    bytes as the inline-split form."""
    from ..parallel.summa import gemm_summa_ozaki, ozaki_presplit

    a, b = ctx.dist(), ctx.dist()

    def fn(x, y):
        split = ozaki_presplit(x)
        return gemm_summa_ozaki(1.0, x, y, a_split=split).tiles

    return fn, (a, b)


# ---------------------------------------------------------------------------
# Elastic-reliability variants (ISSUE 12): the checkpointed segment
# kernels (the chain-of-dispatches form of the factor k-loops), the
# shard_map block-cyclic redistribution (ppermute ring all-to-all), and
# the checksum-carrying trsm — all under the gate: declared collective
# axis names, audit_scope loop coverage, HIGHEST dots on the update
# einsums, no masked-psum idiom outside comm.py.
# ---------------------------------------------------------------------------


@register("redistribute_dist", tags=("bcast",))
def _redistribute(ctx):
    """The shardmap redistribution program (2x4 -> 4x2 over the same
    devices): every hop an audited ppermute with declared axis names."""
    from ..parallel import dist
    from ..parallel.mesh import make_mesh

    a = ctx.dist()
    mesh2 = make_mesh(4, 2, devices=list(ctx.mesh.devices.flatten()))
    cmap = dist._shardmap_coord_map(ctx.mesh, mesh2)
    mt2 = dist.padded_tiles(a.m, a.nb, mesh2)
    nt2 = dist.padded_tiles(a.n, a.nb, mesh2)
    dims = (4, 2, a.tiles.shape[0], a.tiles.shape[1], mt2, nt2, a.nb)
    return (lambda t: dist._redist_shardmap_jit(
        t, ctx.mesh, ctx.p, ctx.q, dims, cmap, False)), (a.tiles,)


# The Checkpoint OFF contracts (PR 16): every public checkpointed driver
# with Option.Checkpoint unresolved-to-off must route to the plain fused
# kernel with an IDENTICAL jaxpr — checkpointing off is free, in the
# strongest sense the analyzer can state.  Each entry below calls the
# real ft.ckpt driver with every=None (the registry process sets no
# SLATE_TPU_CHECKPOINT, so the env chain resolves off) and is proved
# jaxpr-equal to the corresponding plain entry by analysis.contracts.


@register("potrf_ckpt_off", tags=("ckpt",), contracts=(
    Contract(Option.Checkpoint, "off_jaxpr_identical", "potrf_dist"),
))
def _potrf_ckpt_off(ctx):
    from ..ft.ckpt import potrf_ckpt

    a = ctx.dist(kind="spd", diag_pad=True)
    return potrf_ckpt, (a,)


@register("getrf_nopiv_ckpt_off", tags=("ckpt",), contracts=(
    Contract(Option.Checkpoint, "off_jaxpr_identical", "getrf_nopiv_dist"),
))
def _getrf_nopiv_ckpt_off(ctx):
    from ..ft.ckpt import getrf_nopiv_ckpt

    a = ctx.dist(kind="tril", diag_pad=True)
    return getrf_nopiv_ckpt, (a,)


@register("getrf_pp_ckpt_off", tags=("ckpt",), contracts=(
    Contract(Option.Checkpoint, "off_jaxpr_identical", "getrf_pp_dist"),
))
def _getrf_pp_ckpt_off(ctx):
    from ..ft.ckpt import getrf_pp_ckpt

    a = ctx.dist(diag_pad=True)
    return getrf_pp_ckpt, (a,)


@register("geqrf_ckpt_off", tags=("ckpt",), contracts=(
    Contract(Option.Checkpoint, "off_jaxpr_identical", "geqrf_dist"),
))
def _geqrf_ckpt_off(ctx):
    from ..ft.ckpt import geqrf_ckpt

    a = ctx.dist()
    return geqrf_ckpt, (a,)


@register("he2hb_ckpt_off", tags=("ckpt",), contracts=(
    Contract(Option.Checkpoint, "off_jaxpr_identical", "he2hb_dist"),
))
def _he2hb_ckpt_off(ctx):
    from ..ft.ckpt import he2hb_ckpt

    a = ctx.dist(kind="spd")
    return he2hb_ckpt, (a,)


@register("potrf_ckpt_seg", tags=("ckpt",))
def _potrf_ckpt_seg(ctx):
    """One interior checkpoint segment of the mesh Cholesky (steps
    [1, nt) of the strict schedule on the full view)."""
    from ..ft import ckpt

    a = ctx.dist(kind="spd", diag_pad=True)
    return (lambda t: ckpt._potrf_seg_jit(
        t, 0.0, ctx.mesh, ctx.p, ctx.q, a.nt, N, 1, a.nt, "auto",
        False)), (a.tiles,)


@register("getrf_nopiv_ckpt_seg", tags=("ckpt",))
def _getrf_nopiv_ckpt_seg(ctx):
    from ..ft import ckpt

    a = ctx.dist(kind="tril", diag_pad=True)
    return (lambda t: ckpt._lu_seg_jit(
        t, 0.0, ctx.mesh, ctx.p, ctx.q, a.nt, N, 1, a.nt, "auto",
        False)), (a.tiles,)


@register("getrf_pp_ckpt_seg", tags=("ckpt",))
def _getrf_pp_ckpt_seg(ctx):
    import jax.numpy as jnp

    from ..ft import ckpt

    a = ctx.dist(diag_pad=True)
    perm = jnp.arange(a.nt * a.nb)
    return (lambda t, pm: ckpt._pp_seg_jit(
        t, pm, 0.0, ctx.mesh, ctx.p, ctx.q, a.nt, N, 1, a.nt, "auto",
        False)), (a.tiles, perm)


@register("geqrf_ckpt_seg", tags=("ckpt",))
def _geqrf_ckpt_seg(ctx):
    """One interior checkpoint segment of the distributed CAQR (steps
    [1, nt) over the MULTI-ARRAY carry: tile stack + T_loc stack + tree
    V/T stacks — ISSUE 13).  Carry shapes come from ckpt._multi_init,
    the one authority the drivers themselves use."""
    from ..ft import ckpt

    a = ctx.dist()
    st = {}
    ckpt._multi_init("geqrf", a, st, a.nt)
    return (lambda t, x, y, z: ckpt._qr_seg_jit(
        t, x, y, z, ctx.mesh, ctx.p, ctx.q, N, 1, a.nt, "auto")), \
        (a.tiles, st["tls"], st["tvs"], st["tts"])


@register("geqrf_ckpt_seg_num", tags=("ckpt", "num"), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "geqrf_ckpt_seg"),
))
def _geqrf_ckpt_seg_num(ctx):
    """The MONITORED CAQR segment (ISSUE 14 satellite): the same panel
    steps with the in-carry reflector/τ orthogonality-loss gauge —
    results bitwise, the only reduction the unaudited exit pmax (the
    _lu_info_dist class), so the audited wire bytes match the plain
    ``geqrf_ckpt_seg`` exactly."""
    import jax.numpy as jnp

    from ..ft import ckpt
    from ..parallel.comm import num_gauge_dtype

    a = ctx.dist()
    st = {}
    ckpt._multi_init("geqrf", a, st, a.nt)
    g0 = jnp.zeros((), num_gauge_dtype(a.dtype))
    return (lambda t, x, y, z, g: ckpt._qr_seg_nm_jit(
        t, x, y, z, g, ctx.mesh, ctx.p, ctx.q, N, 1, a.nt, "auto")), \
        (a.tiles, st["tls"], st["tvs"], st["tts"], g0)


@register("he2hb_ckpt_seg", tags=("ckpt",))
def _he2hb_ckpt_seg(ctx):
    """One interior checkpoint segment of the two-stage eig stage-1
    reduction (he2hb) over its multi-array carry (ISSUE 13)."""
    from ..ft import ckpt
    from ..linalg.eig import _he2hb_panel_count

    a = ctx.dist(kind="spd")
    nsteps = _he2hb_panel_count(a.n, a.nb)
    st = {}
    ckpt._multi_init("he2hb", a, st, nsteps)
    return (lambda t, v, s: ckpt._he2hb_seg_jit(
        t, v, s, ctx.mesh, ctx.p, ctx.q, a.n, a.nb, 1, max(nsteps, 2),
        "auto")), (a.tiles, st["vqs"], st["tqs"])


@register("he2hb_ckpt_seg_num", tags=("ckpt", "num"), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "he2hb_ckpt_seg"),
))
def _he2hb_ckpt_seg_num(ctx):
    """The MONITORED he2hb segment (ISSUE 15): the same panel steps with
    the in-carry orthogonality-loss gauge — results bitwise, the gauge
    replicated (no reduction at all), audited wire bytes matching the
    plain ``he2hb_ckpt_seg`` exactly."""
    import jax.numpy as jnp

    from ..ft import ckpt
    from ..linalg.eig import _he2hb_panel_count
    from ..parallel.comm import num_gauge_dtype

    a = ctx.dist(kind="spd")
    nsteps = _he2hb_panel_count(a.n, a.nb)
    st = {}
    ckpt._multi_init("he2hb", a, st, nsteps)
    g0 = jnp.zeros((), num_gauge_dtype(a.dtype))
    return (lambda t, v, s, g: ckpt._he2hb_seg_nm_jit(
        t, v, s, g, ctx.mesh, ctx.p, ctx.q, a.n, a.nb, 1, max(nsteps, 2),
        "auto")), (a.tiles, st["vqs"], st["tqs"], g0)


def _ft_her2k_build(ctx, armed):
    """The checksum-carrying her2k under the gate: encode -> augmented
    rank-2k kernel (the shared dist_blas3 panel schedule) -> checksum
    residual — disarmed and armed fault specs, like the gemm pair."""
    import jax.numpy as jnp

    from ..ft import abft, inject
    from ..parallel.comm import resolve_bcast_impl
    from ..parallel.dist import DistMatrix, from_dense, to_dense

    a, b = ctx.dense(), ctx.dense()
    ints, vals = inject.spec_arrays("her2k")
    if armed:
        ints[0] = (1, N // NB - 1, 3, 3, 1, 3 % GRID[0], 1 % GRID[1], 2)
        vals[0] = 3.0
    fi, fv = jnp.asarray(ints), jnp.asarray(vals)

    def fn(x, y):
        a_aug, b_aug, _c, mt, kt = abft._encode_her2k(x, y, None, NB,
                                                      ctx.mesh)
        ad = from_dense(a_aug, ctx.mesh, NB)
        bd = from_dense(b_aug, ctx.mesh, NB)
        out = abft._ft_her2k_jit(
            ad.tiles, bd.tiles, None, 1.0, 0.0, ctx.mesh, ctx.p, ctx.q,
            kt, N, True, 1, resolve_bcast_impl(), fi, fv,
        )
        dense = to_dense(DistMatrix(
            tiles=out, m=a_aug.shape[0], n=a_aug.shape[0], nb=NB,
            mesh=ctx.mesh,
        ))
        return abft._gemm_residual(dense, NB, mt, mt)

    return fn, (a, b)


@register("her2k_abft_detect", tags=("ft",))
def _ft_her2k_detect(ctx):
    return _ft_her2k_build(ctx, armed=False)


@register("her2k_abft_correct", tags=("ft",), contracts=(
    Contract(Option.FaultTolerance, "zero_extra_collectives",
             "her2k_abft_detect"),
))
def _ft_her2k_correct(ctx):
    return _ft_her2k_build(ctx, armed=True)


def _ft_trsm_build(ctx, armed):
    import jax.numpy as jnp

    from ..ft import abft, inject
    from ..parallel.comm import resolve_bcast_impl
    from ..parallel.dist import DistMatrix, from_dense, to_dense

    a = ctx.dense(kind="tril")
    b = ctx.dense_thin()
    ints, vals = inject.spec_arrays("trsm")
    if armed:
        ints[0] = (1, N // NB - 1, 3, 1, 0, 1 % GRID[0], 0, 2)
        vals[0] = 3.0
    fi, fv = jnp.asarray(ints), jnp.asarray(vals)

    def fn(x, y):
        b_aug, mt, ntb = abft._encode_trsm_rhs(x, y, NB, ctx.mesh)
        ad = from_dense(x, ctx.mesh, NB, diag_pad_one=True)
        bd = from_dense(b_aug, ctx.mesh, NB)
        out = abft._ft_trsm_jit(
            ad.tiles, bd.tiles, ctx.mesh, ctx.p, ctx.q, mt, True, False,
            False, 1, resolve_bcast_impl(), fi, fv,
        )
        dense = to_dense(DistMatrix(
            tiles=out, m=b_aug.shape[0], n=b_aug.shape[1], nb=NB,
            mesh=ctx.mesh,
        ))
        return abft._trsm_residual(dense, NB, mt * NB, ntb * NB)

    return fn, (a, b)


@register("trsm_abft_detect", tags=("ft",))
def _ft_trsm_detect(ctx):
    return _ft_trsm_build(ctx, armed=False)


@register("trsm_abft_correct", tags=("ft",), contracts=(
    Contract(Option.FaultTolerance, "zero_extra_collectives",
             "trsm_abft_detect"),
))
def _ft_trsm_correct(ctx):
    return _ft_trsm_build(ctx, armed=True)


@register("potrf_dist_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives", "potrf_dist"),
))
def _potrf_num(ctx):
    from ..parallel.dist_chol import potrf_dist

    a = ctx.dist(kind="spd", diag_pad=True)
    return (lambda x: potrf_dist(x, num_monitor="on")), (a,)


@register("getrf_nopiv_dist_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "getrf_nopiv_dist"),
))
def _getrf_nopiv_num(ctx):
    from ..parallel.dist_lu import getrf_nopiv_dist

    a = ctx.dist(kind="tril", diag_pad=True)
    return (lambda x: getrf_nopiv_dist(x, num_monitor="on")), (a,)


@register("getrf_pp_dist_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "getrf_pp_dist"),
))
def _getrf_pp_num(ctx):
    from ..parallel.dist_lu import getrf_pp_dist

    a = ctx.dist(diag_pad=True)
    return (lambda x: getrf_pp_dist(x, num_monitor="on")), (a,)


@register("getrf_tntpiv_dist_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "getrf_tntpiv_dist"),
))
def _getrf_tnt_num(ctx):
    from ..parallel.dist_lu import getrf_tntpiv_dist

    a = ctx.dist(diag_pad=True)
    return (lambda x: getrf_tntpiv_dist(x, num_monitor="on")), (a,)


@register("geqrf_dist_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives", "geqrf_dist"),
))
def _geqrf_num(ctx):
    """The FUSED monitored CAQR loop (ISSUE 15): the per-panel
    reflector/τ orthogonality-loss gauge riding the fori_loop carry —
    the only reduction the unaudited exit pmax (the _lu_info_dist
    class), so audited wire bytes match the unmonitored trace."""
    from ..parallel.dist_qr import geqrf_dist

    a = ctx.dist()
    return (lambda x: geqrf_dist(x, num_monitor="on")), (a,)


@register("he2hb_num", tags=("num",), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives", "he2hb_dist"),
))
def _he2hb_num(ctx):
    """The FUSED monitored two-stage eig stage-1 loop (ISSUE 15): the
    first eig-chain gauge — the replicated panel QR's loss proxy in the
    carry, collective-free by replication."""
    from ..parallel.dist_twostage import he2hb_dist

    a = ctx.dist(kind="spd")
    return (lambda x: he2hb_dist(x, num_monitor="on")), (a,)


@register("posv_mixed_mesh_num", tags=("num", "mixed"), contracts=(
    Contract(Option.NumMonitor, "zero_extra_collectives",
             "posv_mixed_mesh"),
))
def _posv_mixed_num(ctx):
    """The fused refinement program with the (||r||, ||x||) history
    buffer riding the while_loop carry (Option.NumMonitor=on)."""
    from ..parallel import dist_refine
    from ..types import Option

    a = ctx.dense(kind="spd")
    b = ctx.dense_thin()
    opts = {Option.NumMonitor: "on"}
    return (lambda x, y: dist_refine.posv_mixed_mesh(
        x, y, ctx.mesh, NB, opts=opts)), (a, b)


@register("gecondest_dist", tags=("num",))
def _gecondest(ctx):
    import jax.numpy as jnp

    from ..parallel.dist_aux import gecondest_dist, norm_dist
    from ..parallel.dist_lu import getrf_pp_dist
    from ..types import Norm

    a = ctx.dist(diag_pad=True)
    lu, perm, _info = getrf_pp_dist(a)  # concrete factor once; the trace
    anorm = norm_dist(Norm.One, ctx.dist())  # covers the probe loop
    return (lambda l, p_: gecondest_dist(
        DistLike(l, lu), p_, anorm)), (lu.tiles, perm)


@register("pocondest_dist", tags=("num",))
def _pocondest(ctx):
    from ..parallel.dist_aux import norm_dist, pocondest_dist
    from ..parallel.dist_chol import potrf_dist
    from ..types import Norm

    a = ctx.dist(kind="spd", diag_pad=True)
    l, _info = potrf_dist(a)
    anorm = norm_dist(Norm.One, ctx.dist(kind="spd"))
    return (lambda lt: pocondest_dist(DistLike(lt, l), anorm)), (l.tiles,)


def DistLike(tiles, like):
    """Rewrap a traced tile stack in ``like``'s DistMatrix layout (the
    condest traces take the raw tile stack so make_jaxpr sees it as an
    input rather than a constant)."""
    from ..parallel.dist import DistMatrix

    return DistMatrix(tiles=tiles, m=like.m, n=like.n, nb=like.nb,
                      mesh=like.mesh, diag_pad=like.diag_pad)
