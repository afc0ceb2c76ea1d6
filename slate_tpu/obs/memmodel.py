"""Analytic HBM memory model: closed-form per-device peak bytes for the
registered mesh kernels, plus the single-chip f64 Cholesky residency
models that drive ``linalg.chol``'s fused/staged/ozaki-cache routing.

The memory sibling of ``obs.schedule.ScheduleModel``: where the schedule
model answers "how many bytes move, when", this answers "how many bytes
are LIVE, at peak" — the number that decides whether a problem fits
before any pod time is burned (``predict_max_n``), and the number the
``mem.*`` regression gate pins so the lost-donation/extra-copy bug class
(PR 1's unusable-donation fix, PR 3's staged-potrf OOM fix — both found
by crashing a v5e) is caught at compile-analysis time instead.

Model structure (per device, one mesh kernel):

- **exact terms** — the local tile-stack shards (arguments/outputs), the
  panel-broadcast payloads the lookahead schedule pins live at once
  (``comm.la_live_buffers``: a (1 + d)-deep FIFO for the SUMMA-class
  prefetch loops, 1 + 2·min(d, 1) payload pairs for the deferred-update
  factor loops), and the bucketed kernels' statically-shrinking trailing
  views (``comm.bucket_plan``).  These are tile-count arithmetic times
  ``nb² · itemsize`` — machine-independent at fixed shape.
- **calibrated terms** — XLA's buffer assignment overlaps the bucket
  views and einsum temporaries in ways no simple sum reproduces, so the
  view sum carries a per-op liveness coefficient, and each (op, impl)
  carries a small constant for loop-carry/index scaffolding.  The
  coefficients below were calibrated against
  ``jitted.lower(...).compile().memory_analysis()`` temp bytes across
  10 (n, nb, depth, impl) configurations per op on the 8-device tier-1
  mesh (XLA CPU, JAX 0.4.37) and hold within ~8% everywhere measured;
  ``tests/test_mem.py`` re-validates model-vs-measured at two
  (n, nb, depth) points per BcastImpl on every run, so coefficient drift
  with an XLA upgrade fails loudly.

Everything here is plain arithmetic — no jax import at module load, so
the model is usable from tooling that never builds a mesh (feasibility
checks, the OOM-forensics report).
"""

from __future__ import annotations

import math
import os
from typing import Dict, Optional, Tuple

import numpy as np

# mesh kernels the model covers.  "summa" / "trsm" are prefetch-class
# (read-only panel FIFO); "potrf" / "getrf_nopiv" are deferred-update
# factor loops over bucketed trailing views; "geqrf" / "he2hb" (ISSUE
# 15) are the strict-schedule QR/eig panel chains whose workspace is
# dominated by the full flat-view working copies plus the replicated
# panel/tree buffers of dist_qr._qr_panel_* / dist_twostage._he2hb_*.
MODEL_OPS = ("summa", "potrf", "getrf_nopiv", "trsm", "geqrf", "he2hb")
_FACTOR_OPS = ("potrf", "getrf_nopiv")
_PANEL_CHAIN_OPS = ("geqrf", "he2hb")

# XLA buffer-assignment calibration (see module docstring).  The
# constants are index/loop-carry scaffolding (size-independent: measured
# identical from n = 96 to n = 384); _VIEW_COEF is the fraction of the
# bucket-view byte sum XLA keeps live at peak (views overlap the stack
# copy and each other in assignment).
_CONST_BYTES = {"summa": 256, "potrf": 1504, "getrf_nopiv": 1808,
                "trsm": 617, "geqrf": 753, "he2hb": 4059}
_ENGINE_CONST_BYTES = {"summa": 212, "potrf": 1568, "getrf_nopiv": 2144,
                       "trsm": 512, "geqrf": 384, "he2hb": 128}
_VIEW_COEF = {"potrf": 0.53, "getrf_nopiv": 0.55}

# trsm exact-class calibration (ISSUE 15 satellite — formerly the
# estimate-class op): the RHS carry plus one full-stack trailing-update
# einsum buffer (the ~2.0x stack term XLA keeps live at peak), the
# A-panel prefetch FIFO at its measured overlapped liveness, and the
# diag-tile slot.  Fitted by least squares over 10 (n, nb, depth)
# configurations (n = 96..384, nb = 8..32, depths 0/1) on the tier-1
# mesh; max residual 2.2%, within the 10% gate at every point.
_TRSM_STACK_COEF = 1.996
_TRSM_PCOL_COEF = 0.400
_TRSM_TILE_COEF = 0.067
_TRSM_LIVEPAY_COEF = 0.228

# geqrf / he2hb calibration (same 8-configuration least-squares fit;
# max residuals 6.9% / 6.6%).  Terms: "stack" — the flat-view working
# copies (cflat / a) the panel chain rewrites per step; "panel" — the
# (mfl, nb)-class local panel buffers (r_a / V / packed) plus the
# gathered (p, nb, w) tree-top slices; "gpan" — he2hb's replicated
# global panel column + the W~/Y algebra riding it; "tree" — the
# per-panel T/tree accumulator slices XLA holds next to the update.
_QR_COEF = {"stack": 1.659, "panel": 0.769, "tree": 1.537}
_HE2HB_COEF = {"stack": 1.542, "gpan": 1.236, "pcol": 0.618, "tree": 1.236}

# measured output-assignment slack beyond the exact shard arithmetic
# (the factor ops' info scalar analogue) per multi-array op
_MULTI_OUT_SLOT = {"geqrf": 32, "he2hb": 24}


def _he2hb_steps(n: int, nb: int) -> int:
    """linalg.eig._he2hb_panel_count without the jax import (the model
    must stay importable from pure tooling): panels while the next
    column block still has rows below the band."""
    k = 0
    while (k + 1) * nb < n - 1:
        k += 1
    return k

# the replicated info scalar's buffer slot in the factor kernels' output
# assignment (measured: output − tile shard = 20 B on the tier-1 mesh)
_INFO_SLOT_BYTES = 20


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _itemsize(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


class MemoryModel:
    """Closed-form per-device peak HBM bytes of one mesh kernel at
    (n, nb, mesh grid, dtype, lookahead depth, BcastImpl, FT).

    ``peak_bytes = arg_bytes + out_bytes + workspace_bytes`` — the same
    decomposition ``compile().memory_analysis()`` reports (arguments +
    outputs + temps), so model-vs-measured comparison is term-by-term.
    ``ft=True`` grows the tile grid by the Huang-Abraham checksum
    augmentation (two weighted checksum tile rows/cols + lcm re-pad —
    ft/abft._encode_* geometry).
    """

    def __init__(self, op: str, n: int, nb: int, grid: Tuple[int, int],
                 dtype="float32", lookahead: int = 1,
                 bcast_impl: str = "auto", ft: bool = False,
                 k: Optional[int] = None):
        if op not in MODEL_OPS:
            raise ValueError(f"unknown model op {op!r}; expected {MODEL_OPS}")
        self.op = op
        self.n = int(n)
        self.nb = int(nb)
        self.p, self.q = int(grid[0]), int(grid[1])
        self.dtype = np.dtype(dtype)
        self.isz = _itemsize(dtype)
        self.ft = bool(ft)
        self.bcast_impl = bcast_impl

        lcm = math.lcm(self.p, self.q)
        base = max(1, -(-self.n // self.nb))
        if self.ft:
            # Huang-Abraham augmentation: +2 checksum tile rows (unit +
            # ramp weights), +2 checksum tile cols for the ops that carry
            # column checksums (LU's dual row+col, SUMMA's C), then the
            # lcm re-pad (ft/abft._encode_gemm/_encode_factor geometry)
            base = base + 2
        self.nt = _round_up(base, lcm)
        self.mt = self.nt  # square tile grids throughout the k-loops
        self.mtl = self.mt // self.p
        self.ntl = self.nt // self.q
        self.depth = max(0, min(int(lookahead), self.nt))
        # contraction trip count (SUMMA's kt); square by default
        self.kt = self.nt if k is None else int(k)

        tile = self.nb * self.nb * self.isz
        self.tile_bytes = tile
        self.stack_bytes = self.mtl * self.ntl * tile  # one local shard
        self.panel_col_bytes = self.mtl * tile  # (mtl, nb, nb) payload
        self.panel_row_bytes = self.ntl * tile  # (ntl, nb, nb) payload

    # -- exact terms ---------------------------------------------------

    @property
    def engine(self) -> bool:
        return self.bcast_impl != "psum"

    @property
    def arg_bytes(self) -> int:
        if self.op == "summa":
            return 2 * self.stack_bytes  # A and B shards (C optional)
        if self.op == "trsm":
            return 2 * self.stack_bytes  # A and B shards
        return self.stack_bytes

    @property
    def aux_out_bytes(self) -> int:
        """The multi-array ops' per-device auxiliary outputs beyond the
        tile-stack shard — EXACT tile arithmetic (the ft/ckpt carry
        layout): geqrf's T_loc + replicated tree V/T stacks, he2hb's
        sharded reflector stack + replicated compact-WY accumulators."""
        tile = self.tile_bytes
        if self.op == "geqrf":
            nmerge = max(1, self.p)
            tls = self.nt * tile  # (nt, nb, nb) per mesh row
            tvs = self.nt * nmerge * 2 * tile  # replicated (2nb, nb) slots
            tts = self.nt * nmerge * tile
            return tls + tvs + tts
        if self.op == "he2hb":
            nsteps = max(1, _he2hb_steps(self.n, self.nb))
            vqs = nsteps * self.mtl * self.nb * self.nb * self.isz
            tqs = nsteps * tile  # replicated
            return vqs + tqs
        return 0

    @property
    def out_bytes(self) -> int:
        if self.op in _FACTOR_OPS:
            return self.stack_bytes + _INFO_SLOT_BYTES
        if self.op in _PANEL_CHAIN_OPS:
            return (self.stack_bytes + self.aux_out_bytes
                    + _MULTI_OUT_SLOT[self.op])
        return self.stack_bytes

    @property
    def live_payloads(self) -> int:
        """Panel-broadcast payload pairs the lookahead schedule pins live
        at once (comm.la_live_buffers: single source with the kernels)."""
        from ..parallel.comm import la_live_buffers

        return la_live_buffers(self.depth, factor_loop=self.op in _FACTOR_OPS)

    @property
    def payload_bytes(self) -> int:
        """One panel payload pair: the column panel plus the row-indexed
        transpose/row payload every k-step broadcasts."""
        if self.op == "trsm":
            # A-panel prefetch + the diag tile (the solved-row broadcast
            # is transient within the panel phase)
            return self.panel_col_bytes + self.tile_bytes
        return self.panel_col_bytes + self.panel_row_bytes

    def _bucket_view_bytes(self) -> int:
        """Byte sum of the bucketed factor kernels' trailing-view buffers
        (comm.bucket_plan: the statically-shrinking per-bucket views)."""
        from ..parallel.comm import bucket_plan

        total = 0
        for _k0, _k1, s0r, s0c in bucket_plan(self.nt, self.p, self.q):
            total += (self.mtl - s0r) * (self.ntl - s0c) * self.tile_bytes
        return total

    # -- modeled workspace (the memory_analysis temp twin) -------------

    @property
    def workspace_bytes(self) -> float:
        """Per-device transient bytes at peak — the model twin of
        ``memory_analysis().temp_size_in_bytes``.  Exact payload/stack
        terms plus the calibrated bucket-view liveness (module
        docstring)."""
        const = _CONST_BYTES[self.op]
        if self.engine:
            const += _ENGINE_CONST_BYTES[self.op]
        tile = self.tile_bytes
        if self.op == "trsm":
            # exact-class (ISSUE 15): RHS carry + one full-stack trailing
            # einsum buffer, the prefetch FIFO at measured overlapped
            # liveness, and the diag-tile slot — fitted coefficients, max
            # residual 2.2% over the 10-configuration calibration sweep
            return (_TRSM_STACK_COEF * self.stack_bytes
                    + _TRSM_PCOL_COEF * self.panel_col_bytes
                    + _TRSM_TILE_COEF * tile
                    + _TRSM_LIVEPAY_COEF * self.live_payloads
                    * self.payload_bytes
                    + const)
        if self.op == "geqrf":
            pcol = self.panel_col_bytes  # (mfl, nb) local panel buffers
            tops = self.p * self.panel_row_bytes  # gathered (p, nb, w)
            tree = self.nt * tile  # per-panel T/tree slices
            return (_QR_COEF["stack"] * self.stack_bytes
                    + _QR_COEF["panel"] * (pcol + tops)
                    + _QR_COEF["tree"] * tree + const)
        if self.op == "he2hb":
            pcol = self.panel_col_bytes
            gpan = self.p * pcol  # replicated global panel column
            tree = self.nt * tile
            return (_HE2HB_COEF["stack"] * self.stack_bytes
                    + _HE2HB_COEF["gpan"] * gpan
                    + _HE2HB_COEF["pcol"] * pcol
                    + _HE2HB_COEF["tree"] * tree + const)
        if self.op == "summa":
            # accumulator carry + the (1 + d)-deep payload FIFO
            return (self.stack_bytes + self.live_payloads * self.payload_bytes
                    + const)
        # factor loops: factored stack copy + live payload pairs
        # (1 + 2·min(d,1): the deferred payload is carried next to the
        # fresh one) + the bucketed trailing views at calibrated liveness
        return (self.stack_bytes
                + self.live_payloads * self.payload_bytes
                + _VIEW_COEF[self.op] * self._bucket_view_bytes()
                + const)

    @property
    def peak_bytes(self) -> float:
        return self.arg_bytes + self.out_bytes + self.workspace_bytes

    def breakdown(self) -> Dict[str, float]:
        return {
            "arg_bytes": float(self.arg_bytes),
            "out_bytes": float(self.out_bytes),
            "workspace_bytes": float(self.workspace_bytes),
            "peak_bytes": float(self.peak_bytes),
            "payload_bytes": float(self.payload_bytes),
            "live_payloads": float(self.live_payloads),
            "stack_bytes": float(self.stack_bytes),
        }


def predict_max_n(budget_bytes: float, op: str = "potrf", nb: int = 256,
                  grid: Tuple[int, int] = (2, 4), dtype="float32",
                  lookahead: int = 1, bcast_impl: str = "auto",
                  ft: bool = False) -> int:
    """Largest n whose modeled per-device peak fits ``budget_bytes`` —
    the "will it fit?" answer for a planned run, searched over tile-grid
    multiples (the model is step-wise constant between them)."""
    step = nb * math.lcm(int(grid[0]), int(grid[1]))

    def fits(n):
        if n <= 0:
            return True
        m = MemoryModel(op, n, nb, grid, dtype, lookahead, bcast_impl, ft)
        return m.peak_bytes <= budget_bytes

    if not fits(step):
        return 0
    lo, hi = step, step
    while fits(hi * 2):
        hi *= 2
        if hi > (1 << 40):
            break
    lo = hi
    hi = hi * 2
    while lo + step < hi:
        mid = ((lo + hi) // 2) // step * step
        if mid <= lo:
            break
        if fits(mid):
            lo = mid
        else:
            hi = mid
    return lo


# ---------------------------------------------------------------------------
# Single-chip f64 Cholesky residency (linalg/chol.py routing).  These are
# the model-derived versions of the peak-HBM numbers chol.py used to
# carry as hand-computed docstring constants.
# ---------------------------------------------------------------------------

# v5e HBM per chip (the BASELINE_v5e.md target machine)
V5E_HBM_BYTES = int(15.75 * 2**30)
# fraction of HBM the planner budgets for one factorization (the rest
# covers the runtime, caller-held operands, and allocator slack)
HBM_SAFETY = 0.90
HBM_ENV = "SLATE_TPU_HBM_BYTES"

# Fused left-looking f64 peak, in matrix copies: XLA's buffer assignment
# across the unrolled panel chain keeps ~7.2 live copies of the matrix
# (MEASURED on v5e: 14.4 GB peak for the 2.0 GB n = 16384 problem,
# ADVICE r5 — the calibration point for this coefficient; it OOMed the
# chip at n = 32768).
FUSED_LL_COPIES = 7.2
# Staged dispatch: one donated matrix + one panel's transients (the
# update gemm's (n, nb_panel) operands/output) — ~3 panel strips.
STAGED_PANEL_STRIPS = 3
# Ozaki digit-cache f64 working set next to the S n^2 int8 cache:
# ~4 full f64 buffers (matrix + symmetrize/update transients), i.e.
# 32 n^2 bytes (chol._potrf_ll_ozaki; validated on chip at n = 16384:
# (10 + 32) n^2 = 11.3 GB of 15.75).
OZAKI_F64_BUFFERS = 4


def hbm_budget(default: int = V5E_HBM_BYTES) -> int:
    """Per-device HBM budget for routing decisions: the SLATE_TPU_HBM_BYTES
    env override, else the default backend device's reported bytes_limit.
    Devices that report no memory statistics (CPU) get ``default``; a TPU
    that reports no ``bytes_limit`` raises — guessing its size would
    route real work against a made-up budget."""
    env = os.environ.get(HBM_ENV)
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    import jax

    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform == "tpu":
        raise RuntimeError(
            f"{dev.device_kind} reports no bytes_limit in memory_stats(); "
            f"set {HBM_ENV} to its HBM size"
        )
    return default


def _ll_nb(n: int) -> int:
    """chol.py's left-looking panel width heuristic."""
    return 4096 if n >= 16384 else 2048


def potrf_fused_ll_peak(n: int, itemsize: int = 8) -> float:
    """Peak HBM of the fused (single-program) left-looking f64 Cholesky:
    FUSED_LL_COPIES live matrix copies (measured calibration above)."""
    return FUSED_LL_COPIES * float(n) * n * itemsize


def potrf_staged_peak(n: int, itemsize: int = 8,
                      nb: Optional[int] = None) -> float:
    """Peak HBM of chol.potrf_left_looking_staged: one donated matrix
    plus one panel step's transients (~STAGED_PANEL_STRIPS (n, nb)
    strips)."""
    nbp = _ll_nb(n) if nb is None else nb
    return float(n) * n * itemsize + STAGED_PANEL_STRIPS * float(n) * nbp * itemsize


def potrf_ozaki_cache_peak(n: int, n_slices: Optional[int] = None) -> float:
    """Peak HBM of the digit-cached Ozaki f64 Cholesky: the S n^2 int8
    plane cache next to ~OZAKI_F64_BUFFERS full f64 buffers."""
    s = (10 if n > 8192 else 9) if n_slices is None else int(n_slices)
    return (s + OZAKI_F64_BUFFERS * 8) * float(n) * n


def potrf_fused_fits(n: int, budget: Optional[int] = None,
                     itemsize: int = 8) -> bool:
    b = hbm_budget() if budget is None else budget
    return potrf_fused_ll_peak(n, itemsize) <= HBM_SAFETY * b


def potrf_ozaki_cache_max_n(budget: Optional[int] = None) -> int:
    """Digit-cache ceiling: the largest n whose cache + f64 working set
    fits the safety-scaled budget (the model-derived replacement for
    chol.py's hand-computed 16384 constant — which this reproduces at
    the v5e default: 16384 fits at 11.3 GB, 20480 does not at 17.6)."""
    b = HBM_SAFETY * (hbm_budget() if budget is None else budget)
    # peak is monotone with a piecewise S; solve both pieces
    n_hi = int(math.sqrt(b / (10 + OZAKI_F64_BUFFERS * 8)))
    if n_hi > 8192:
        return n_hi
    return min(8192, int(math.sqrt(b / (9 + OZAKI_F64_BUFFERS * 8))))


def potrf_f64_form(n: int, concrete: bool, ozaki_dispatch: bool,
                   budget: Optional[int] = None, itemsize: int = 8) -> str:
    """Routing decision for the big-f64 potrf_array dispatch:

    - ``"ozaki"``  — the digit-cached left-looking form, when the int8
      dispatch is live and cache + matrix fit the budget (f64 only: the
      caller gates ``ozaki_dispatch`` on the real dtype);
    - ``"staged"`` — one donated XLA program per panel (peak = one
      matrix + panel transients), when the fused form's ~7.2 live copies
      would not fit AND the call is concrete (staged dispatch is eager
      only: under an outer jit the stages inline and the fused-liveness
      problem returns);
    - ``"fused"``  — the single-program left-looking form otherwise.

    ``itemsize`` covers the whole dtype class the dispatch admits: 8 for
    float64, 16 for complex128 (whose fused peak is twice the f64 one).
    """
    b = hbm_budget() if budget is None else budget
    if ozaki_dispatch and itemsize == 8 and n <= potrf_ozaki_cache_max_n(b):
        return "ozaki"
    if concrete and not potrf_fused_fits(n, b, itemsize):
        return "staged"
    return "fused"


def mixed_ladder_residency(n: int, nb: int, grid: Tuple[int, int],
                           nrhs: int = 1) -> float:
    """Per-device residency estimate of the mixed-precision IR ladder
    (dist_refine): the f64 A tile stack + its f32 copy (half) + the f32
    factor (half) + two RHS-shaped f64 stacks (the donated B carry and
    the residual) — the buffers the fused refinement while_loop keeps
    live across iterations.  The serving-runtime per-request budget
    hook; an estimate, not memory_analysis-validated like the kernel
    model (tests pin its arithmetic only)."""
    p, q = int(grid[0]), int(grid[1])
    m64 = MemoryModel("potrf", n, nb, grid, "float64")
    rhs_nt = _round_up(max(1, -(-int(nrhs) // nb)), math.lcm(p, q))
    rhs_stack = m64.mtl * (rhs_nt // q) * nb * nb * 8
    return 2.0 * m64.stack_bytes + 2.0 * rhs_stack
