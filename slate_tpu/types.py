"""Core enums, options, and types.

TPU-native analogue of the reference's ``include/slate/enums.hh`` and
``include/slate/types.hh`` (reference: enums.hh:33-143, types.hh:32-64).
Enums that only exist to drive the reference's CPU/GPU runtime (MOSI states,
TileKind, queue indices) are intentionally absent: under XLA/SPMD there is no
coherency protocol and no stream scheduler to configure.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Any, Mapping, Optional, Union


class Uplo(enum.Enum):
    """Which triangle of a matrix is stored/referenced (enums.hh analog)."""

    Upper = "U"
    Lower = "L"
    General = "G"


class Op(enum.Enum):
    """Logical transposition applied to a matrix view (Tile.hh op_)."""

    NoTrans = "N"
    Trans = "T"
    ConjTrans = "C"


class Diag(enum.Enum):
    Unit = "U"
    NonUnit = "N"


class Side(enum.Enum):
    Left = "L"
    Right = "R"


class Norm(enum.Enum):
    """Matrix norms (lapack convention; reference enums.hh Norm)."""

    One = "1"
    Inf = "I"
    Max = "M"
    Fro = "F"


class NormScope(enum.Enum):
    """Whole-matrix norm vs per-row / per-column norms (enums.hh:120)."""

    Matrix = "M"
    Columns = "C"
    Rows = "R"


class Target(enum.Enum):
    """Execution target.

    The reference dispatches HostTask/HostNest/HostBatch/Devices
    (enums.hh:33).  Here the only compute substrate is XLA, so targets
    select *where XLA runs*, not a hand-written scheduler:

    - ``TPU``: jit on the default accelerator backend.
    - ``Host``: jit on the CPU backend (reference Host* targets collapse to
      one — XLA:CPU already does the task/nest/batch scheduling internally).
    """

    TPU = "tpu"
    Host = "host"


class GridOrder(enum.Enum):
    """Process-grid ordering for 2D block-cyclic distributions (enums.hh:130)."""

    Col = "C"
    Row = "R"


class Layout(enum.Enum):
    """Tile storage layout. XLA manages physical layout; kept for API parity."""

    ColMajor = "C"
    RowMajor = "R"


# ---------------------------------------------------------------------------
# Method selection (reference include/slate/method.hh:25-319)
# ---------------------------------------------------------------------------


class MethodGemm(enum.Enum):
    Auto = "auto"
    GemmA = "A"  # stationary-A
    GemmC = "C"  # stationary-C (SUMMA-like)


class MethodTrsm(enum.Enum):
    Auto = "auto"
    TrsmA = "A"
    TrsmB = "B"


class MethodHemm(enum.Enum):
    Auto = "auto"
    HemmA = "A"
    HemmC = "C"


class MethodLU(enum.Enum):
    PartialPiv = "PPLU"
    CALU = "CALU"  # tournament pivoting (getrf_tntpiv analog)
    NoPiv = "NoPiv"
    RBT = "RBT"  # random butterfly transform + no-pivot LU


class MethodGels(enum.Enum):
    QR = "QR"
    CholQR = "CholQR"


class MethodEig(enum.Enum):
    QR = "QR"  # steqr: tridiagonal QR iteration
    DC = "DC"  # stedc: divide and conquer


class MethodSVD(enum.Enum):
    QR = "QR"  # bdsqr
    DC = "DC"


class Precision(enum.Enum):
    """Accumulation-precision tier for BLAS-3 (Option.Precision).

    The reference always runs vendor-native full-precision BLAS
    (internal_gemm.cc:634); on TPU the MXU offers a speed/accuracy ladder,
    so the tier is a first-class option.  Measured on v5e, n=1024 N(0,1)
    operands, max relative error vs f64:

    - ``Fast``: native MXU rate — single-pass bf16 for f32 data (~2^-8,
      78-103 TF/s), 6-slice Ozaki for f64 (~2^-33, 1.5x Highest's rate).
    - ``High``: 3-pass bf16x3 for f32 (~2^-16, ~43 TF/s); f64 unchanged
      (full Ozaki — there is no meaningful middle tier on the int8 path).
    - ``Highest``: full precision for the dtype — 6-pass bf16x9 for f32
      (~2^-22.5, ~25 TF/s), 9-slice int8 Ozaki for f64 (true f64, ~3e-15).
    - ``Emulated``: opt out of the int8 Ozaki f64 path entirely and use
      XLA's f32-pair f64 emulation (~1.3 TF/s; debugging escape hatch).

    Every driver defaults to Highest — matching the reference's
    always-full-precision vendor BLAS — and the reduced tiers are
    explicit opt-ins via Option.Precision.
    """

    Fast = "fast"
    High = "high"
    Highest = "highest"
    Emulated = "emulated"


def select_gemm_method(m: int, n: int, k: int) -> MethodGemm:
    """Heuristic from method.hh:35-45: tiny output panel -> stationary-A."""
    if n <= max(m, k) // 4:
        return MethodGemm.GemmA
    return MethodGemm.GemmC


def select_trsm_method(side: Side, m: int, n: int) -> MethodTrsm:
    """method.hh:88-99: solve-side-dominant shapes favour TrsmA."""
    if (side == Side.Left and n <= m // 4) or (side == Side.Right and m <= n // 4):
        return MethodTrsm.TrsmA
    return MethodTrsm.TrsmB


def select_hemm_method(m: int, n: int) -> MethodHemm:
    """Shape heuristic in the SPIRIT of method.hh MethodHemm::select_algo
    (a thin B/C panel next to a big Hermitian A favours the stationary-A
    schedule, hemmA.cc) but NOT its exact rule: the reference switches on
    ``n < 2 * nb`` (panel thinner than two tiles); here the threshold is
    the TPU-tuned aspect ratio n <= m / 4, where hemmA's |B|-replication
    + p|C|-reduction ICI volume undercuts the k-loop's row-panel gathers
    on the meshes we measure.  Callers pinning the reference's exact
    dispatch should pass Option.MethodHemm explicitly."""
    if n <= m // 4:
        return MethodHemm.HemmA
    return MethodHemm.HemmC


# ---------------------------------------------------------------------------
# Options (reference types.hh:60 Options = map<Option, OptionValue>)
# ---------------------------------------------------------------------------


class Option(enum.Enum):
    ChunkSize = "chunk_size"
    Lookahead = "lookahead"
    BlockSize = "block_size"  # nb (reference Option::TileSize analog)
    InnerBlocking = "inner_blocking"  # ib
    # Reference: threads cooperating on one LU panel (internal_getrf.cc),
    # a parallelism-only knob there.  TPU analogue: the CALU tournament
    # panel is ib * MaxPanelThreads columns wide, trading per-step latency
    # against update size as panel threads do — but with a NUMERICAL side
    # effect the reference doesn't have: the tournament width changes
    # which pivots win, so pivot quality varies with this option
    # (linalg/lu.py getrf, MethodLU.CALU, has the full note).
    MaxPanelThreads = "max_panel_threads"
    Tolerance = "tolerance"
    Target = "target"
    MaxIterations = "max_iterations"
    UseFallbackSolver = "use_fallback_solver"
    PivotThreshold = "pivot_threshold"
    MethodCholQR = "method_cholqr"
    MethodEig = "method_eig"
    MethodGels = "method_gels"
    MethodGemm = "method_gemm"
    MethodHemm = "method_hemm"
    MethodLU = "method_lu"
    MethodTrsm = "method_trsm"
    MethodSVD = "method_svd"
    PrintVerbose = "print_verbose"
    PrintPrecision = "print_precision"
    Depth = "depth"  # RBT butterfly depth
    Precision = "precision"  # BLAS-3 accumulation tier (Precision enum)
    # ABFT policy for the distributed kernels (ft.FtPolicy: off | detect |
    # correct | recompute).  Off (the default) routes to the plain kernels
    # untouched; any other value runs the checksum-carrying variants in
    # slate_tpu/ft/abft.py.  No reference analogue: SLATE delegates
    # resilience to the MPI/ULFM layer, while under XLA/SPMD the natural
    # unit of protection is the tile algebra itself.
    FaultTolerance = "fault_tolerance"
    # Broadcast lowering for the mesh k-loops' tileBcast verbs
    # (parallel/comm.py engine): "psum" (legacy masked all-reduce, ~2x the
    # bytes a broadcast needs), "ring" (pipelined collective_permute ring,
    # (s-1)/s * B per link), "doubling" (log2(s)-hop recursive doubling on
    # power-of-two axes), or "auto" (the default: doubling on power-of-two
    # axes, ring otherwise).  All lowerings are bitwise-identical in
    # results; they differ only in wire bytes and hop latency.  Resolution
    # order: explicit option > comm.use_bcast_impl context >
    # SLATE_TPU_BCAST_IMPL environment > auto.
    BcastImpl = "bcast_impl"
    # Trailing-update lowering for the mesh k-loops' bulk phase
    # (ops/pallas_ops.py, ISSUE 20): "xla" (the reference semantics —
    # today's einsum bulk chains, jaxpr-IDENTICAL by construction),
    # "pallas" (one fused grid dispatch over the local trailing tile
    # stack per k-step — summa_update_pallas / chol_trailing_update_pallas
    # / lu_trailing_update_pallas, with the broadcast panels riding VMEM
    # blocks; bitwise vs the xla bulk in float64 under interpret mode,
    # within a length-nb dot's rounding bound in float32), or "auto"
    # (the default: pallas on a real TPU backend for MXU dtypes, xla
    # elsewhere).  Fusion changes compute scheduling, never comm — the
    # broadcast schedule and comm-audit wire bytes are invariant across
    # lowerings (asserted).  Resolution order: explicit option >
    # pallas_ops.use_update_impl context > SLATE_TPU_UPDATE_IMPL
    # environment > auto (the Option.BcastImpl pattern).  Scope: the
    # summa / potrf / LU-nopiv bulk phases; the pivoted/band LU kernels
    # pin xla (their trailing sweeps interleave with pivot application).
    UpdateImpl = "update_impl"
    # Mixed-precision routing for the distributed f64 solves
    # (parallel/dist_refine.py): "off" (factor at the data dtype — trace-
    # identical to the direct gesv_mesh/posv_mesh path), "ir" (f32 mesh
    # factor + fused on-device f64 iterative refinement, then the full-f64
    # fallback on non-convergence), "gmres" (f32 factor preconditioning
    # distributed restarted GMRES, then fallback), or "auto" (the default:
    # the escalation ladder IR -> GMRES-IR -> full-f64 fallback for real
    # f64 inputs — the reference's gesv_mixed/posv_mixed stance made the
    # DEFAULT because on TPU the f32:f64 factor gap is ~40x, not ~2x).
    # Resolution order: explicit option > dist_refine.use_mixed context >
    # SLATE_TPU_MIXED environment > auto.
    MixedPrecision = "mixed_precision"
    # Numerical-health monitoring for the mesh factorization k-loops and
    # the mixed-precision refinement loop (obs/numerics.py): "off" (the
    # plain kernels, jaxpr-IDENTICAL — the UpdateImpl/MixedPrecision
    # pattern), "on" (the loop carry accumulates running element-growth /
    # diagonal-margin gauges and the refinement while_loop keeps a
    # fixed-size (||r||, ||x||) history buffer — zero extra collectives:
    # the gauges ride the carry and reduce once at loop exit through the
    # same unaudited pmax the info computation already uses, so comm-audit
    # wire bytes are unchanged), or "auto" (the default: on when the obs
    # layer is enabled — SLATE_TPU_OBS=1 / obs.enable() — off otherwise).
    # Resolution order: explicit option > numerics.use_num_monitor
    # context > SLATE_TPU_NUM environment > auto.  When monitoring is on,
    # Option.MixedPrecision=auto additionally consults the measured
    # f32-factor growth and a Hager-Higham condition estimate to pick its
    # ladder entry tier (pathological inputs skip straight to GMRES-IR).
    NumMonitor = "num_monitor"
    # Tuned-schedule-table consultation for the serving request path
    # (serve/table.py): "on" (unset schedule options — BcastImpl,
    # Lookahead, BlockSize, MethodGemm — resolve through the committed
    # autotuned table, artifacts/serve/tuned.json, BEFORE falling back
    # to auto; the resolution chain becomes explicit > context > env >
    # tuned > auto) or "off" (the pre-serve chain, tuned tier skipped).
    # Resolution order for the switch itself: explicit option >
    # SLATE_TPU_AUTOTUNE environment > on (serving exists to consume its
    # own measurements).  Only the serve dispatch path consults this —
    # direct driver calls never read the table.
    AutoTune = "auto_tune"
    # Checkpoint interval for the mesh factorization k-loops (ft/ckpt.py):
    # an int K snapshots the k-loop carry to host every K steps, so a
    # preempted multi-minute factorization resumes from the last
    # snapshot instead of restarting from zero.  Covered loops: potrf /
    # LU-nopiv / partial-pivot LU (single tile-stack carry + NumMonitor
    # gauges + pivot permutation; resume bitwise on the SAME mesh or a
    # RESHAPED p' x q' mesh via block-cyclic redistribution,
    # ft/elastic.py) and — ISSUE 13 — the distributed CAQR (geqrf) and
    # two-stage eig stage-1 reduction (he2hb), whose MULTI-ARRAY carries
    # (tile stack + T-factor / reflector / tree stacks) resume bitwise
    # on the same (p, q) grid shape only: the auxiliary arrays are
    # grid-locked and a reshaped resume is refused with a structured
    # error.  Snapshots are sync by default; SLATE_TPU_CKPT_ASYNC=1 (or
    # the drivers' async_snapshots=True) overlaps the device->host carry
    # copy with the next segment's dispatch, bitwise-equal either way.
    # Off / absent / 0 (the default) routes to the plain fused kernels
    # untouched: trace-identical, zero overhead.  Resolution order:
    # explicit option > SLATE_TPU_CKPT environment > off.  No reference
    # analogue: SLATE delegates preemption survival to the MPI
    # checkpoint layer; under XLA/SPMD the natural snapshot unit is the
    # k-loop carry itself.
    Checkpoint = "checkpoint"
    # Residual lowering for the mixed-precision refinement loop: "f64"
    # (plain SUMMA at the data dtype — XLA's emulated-f64 pairs on TPU),
    # "ozaki" (the int8 split-integer SUMMA: digit planes of A and X ride
    # the unchanged broadcast schedule at slice_count/8 x the f64 panel
    # bytes and the MXU integer rate), or "auto" (ozaki on a real TPU
    # backend, f64 elsewhere).  Both are f64-grade accurate; ozaki is
    # bitwise-reproducible across mesh shapes (fixed split + summation
    # order).  Resolution order: explicit option >
    # SLATE_TPU_RESIDUAL_IMPL environment > auto.
    ResidualImpl = "residual_impl"


Options = Mapping[Union[Option, str], Any]

_DEFAULTS = {
    Option.Lookahead: 1,
    Option.BlockSize: 256,
    Option.InnerBlocking: 32,
    Option.Tolerance: None,
    Option.Target: Target.TPU,
    Option.MaxIterations: 30,
    Option.UseFallbackSolver: True,
    Option.PivotThreshold: 1.0,
    Option.Depth: 2,
}


def get_option(opts: Optional[Options], key: Option, default: Any = None) -> Any:
    """Typed option lookup (types.hh get_option analog)."""
    if opts:
        if key in opts:
            return opts[key]
        if key.value in opts:
            return opts[key.value]
    if default is not None:
        return default
    return _DEFAULTS.get(key)


@dataclass(frozen=True)
class Pivot:
    """One pivot entry: which tile row / element within it (types.hh:64)."""

    tile_index: int
    element_offset: int


class SlateError(Exception):
    """slate::Exception analog (include/slate/Exception.hh)."""


def slate_assert(cond: bool, msg: str) -> None:
    if not cond:
        raise SlateError(msg)
