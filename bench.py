"""Headline benchmark: DGEMM (f64) GFLOP/s per chip, Ozaki-split int8 path.

Mirrors the reference tester's gemm benchmark (test/test_gemm.cc:217-245,
gflop formula blas::Gflop<double>::gemm = 2mnk / time) on the driver's
north-star config (BASELINE.json: DGEMM FP64 GFLOPS/chip).  The f64 product
runs on the int8 MXU via the Ozaki error-free split scheme
(slate_tpu/ops/ozaki.py) — TPU v5e has no native f64 path, and XLA's
f32-pair emulation measures ~1.3 TF/s; the split scheme reaches ~4.7 TF/s
at true f64 accuracy (residual-gated below).

Prints the driver-facing JSON line {"metric", "value", "unit",
"vs_baseline", "extras"} INCREMENTALLY: a complete line is re-emitted
after the headline and after every finished extra (the last parsable line
wins, which is what the driver's tail-parser and obs.report's legacy
loader read), and the same line is atomically rewritten to
``bench_partial.json`` next to this file (override:
``SLATE_TPU_BENCH_PARTIAL``) — so a timeout kill (rc=124, the failure
mode of a pre-PR-1 run) never loses already-measured numbers.
An atexit hook re-emits the last complete line on EVERY exit path
(SIGTERM handler, unhandled exception, SystemExit), so only an outright
SIGKILL can end stdout without a parseable line — and the partial file
covers that (unit-tested: tests/test_bench_kill.py).
``SLATE_TPU_BENCH_TIMEOUT`` (seconds; unset = 600, an explicit 0 = off)
is a wall-clock budget: extras that would start past it are skipped with a
reason, and a SIGALRM guard aborts a mid-flight extra at the deadline
instead of letting it eat the whole run.  Extras run cheapest-first, so
the f64 n=8192 factorizations (the pre-PR-1 rc=124 culprits: unrolled
f64 programs with O(10 min) cold compiles) land LAST — a budget kill
costs the expensive tail, never an already-cheap middle.  A SIGTERM
(what ``timeout`` sends before SIGKILL) re-emits the current full result
line on the way out, so the driver's tail parser sees a complete line
even on the kill path.

vs_baseline: ratio to 19,500 GFLOP/s — the FP64 tensor-core peak of the
A100 GPUs SLATE-CUDA runs on (its large-n DGEMM approaches peak), since the
reference repo publishes no numbers (BASELINE.md).

Ceiling analysis (the honest cross-ISA story): v5e int8 peak is 394 TOPS
(measured dense attainable: ~278 TOPS).  Full-f64 accuracy needs 9 digit
slices = 45 unit-GEMMs per product, so the hardware ceiling for f64-via-
int8 on this chip is 394/45 = 8.8 TF/s (attainable ~6.2); the headline
number is ~76% of attainable ceiling.  A100 FP64 TC peak (19.5 TF/s) is a
dedicated-f64-silicon number — "extras" records the native-precision MFU
story (bf16/int8/f32) where this chip actually competes.

Timing notes: iterations run inside one jitted lax.fori_loop with per-iter
input perturbation, full-size accumulators, and a forced host transfer at
the end — XLA DCEs any result that is only partially consumed, and the
host transfer is what proves the work finished.
"""

import json
import os as _os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)
# Persistent XLA compilation cache: the unrolled f64 factorizations take
# minutes to compile; the on-disk cache makes re-runs start in seconds.
from slate_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(min_compile_secs=10.0)

_T0 = time.time()


def _progress(msg):
    """Progress to stderr; stdout stays the single driver-facing JSON line."""
    print(f"[bench {time.time() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)

BASELINE_GFLOPS = 19500.0  # A100 FP64 TC peak ~ SLATE-CUDA DGEMM/device
N = 8192  # v5e: 16G HBM; the Ozaki digit planes cap the size
V5E_BF16_PEAK = 197_000.0  # GFLOP/s, published v5e peak
V5E_INT8_PEAK = 394_000.0  # GOP/s


def _timeit(fn, *args, reps=3):
    """Best wall time over reps; forces a scalar host transfer."""
    float(fn(*args))  # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_dgemm_ozaki(a64, b64, iters=4):
    from slate_tpu.ops.ozaki import matmul_f64

    @jax.jit
    def run(a, b):
        # b must come in as an argument — closing over the device array
        # would embed a 512MB constant in the program and stall compile
        def body(i, carry):
            acc, aa = carry
            return acc + matmul_f64(aa, b), aa + 1e-6

        acc, _ = jax.lax.fori_loop(0, iters, body, (jnp.zeros((N, N), jnp.float64), a))
        return jnp.sum(acc[:1])

    t = _timeit(run, a64, b64)
    return 2.0 * N**3 * iters / t / 1e9


def bench_gemm(dtype, iters, pet=None):
    a = jax.random.normal(jax.random.PRNGKey(0), (N, N)).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, N)).astype(dtype)
    acc_dt = pet or dtype

    @jax.jit
    def run(a, b):
        def body(i, carry):
            acc, aa = carry
            c = jax.lax.dot_general(
                aa, b, (((1,), (0,)), ((), ())), preferred_element_type=pet
            )
            return acc + c, aa + jnp.ones((), dtype)

        acc, _ = jax.lax.fori_loop(0, iters, body, (jnp.zeros((N, N), acc_dt), a))
        return jnp.sum(acc[:1].astype(jnp.float32))

    t = _timeit(run, a, b)
    return 2.0 * N**3 * iters / t / 1e9


def bench_potrf():
    # recursive path, single call (the scanned variant pays ~3x masked
    # flops and only wins above the recursion's program-size ceiling;
    # tools/northstar_sweep.py times the scanned 16384/32768 forms)
    from slate_tpu.linalg.chol import potrf_array

    g = jax.random.normal(jax.random.PRNGKey(0), (N, N), jnp.float32)
    a = (g @ g.T) / N + 2 * jnp.eye(N, dtype=jnp.float32)
    run = jax.jit(lambda x: jnp.sum(jnp.abs(jnp.diagonal(potrf_array(x)[0]))))
    t = _timeit(run, a)
    return N**3 / 3.0 / t / 1e9


def bench_getrf():
    # recursive path: fastest at n=8192 (the scanned form trades ~2.25x
    # flops for O(1) compile and only wins beyond the recursion's
    # program-size ceiling)
    from slate_tpu.linalg.lu import getrf_array

    m = jax.random.normal(jax.random.PRNGKey(1), (N, N), jnp.float32) / 64
    run = jax.jit(lambda x: jnp.sum(jnp.abs(jnp.diagonal(getrf_array(x).lu))))
    t = _timeit(run, m)
    return 2.0 * N**3 / 3.0 / t / 1e9


# ---------------------------------------------------------------------------
# panel microbenches (ISSUE 6): the XLA panel chains at the mesh kernels'
# panel shape (nb = 256, 63 below tiles = one n = 16384 panel column).
# These isolate the panel latency story — SURVEY "Hard parts": potrf f32
# runs at ~2.4 TF/s while gemm f32 hits ~101 TF/s because the panel phase
# is nb tiny dispatches.
# ---------------------------------------------------------------------------

NB_PANEL = 256
L_PANEL = 63


def _panel_operands(kind):
    rng = np.random.default_rng(7)
    d = rng.standard_normal((NB_PANEL, NB_PANEL)).astype(np.float32)
    if kind == "potrf":
        d = d @ d.T / NB_PANEL + 2 * np.eye(NB_PANEL, dtype=np.float32)
    else:
        d = d + NB_PANEL * np.eye(NB_PANEL, dtype=np.float32)
    tiles = rng.standard_normal((L_PANEL, NB_PANEL, NB_PANEL)).astype(np.float32)
    return jnp.asarray(d), jnp.asarray(tiles)


def bench_panel_potrf():
    """One potrf panel phase: the cholesky + batched-trsm chain over the
    diag tile and 63 tile solves."""
    d, tiles = _panel_operands("potrf")

    @jax.jit
    def run(d, t):
        lkk = jax.lax.linalg.cholesky(d)
        solved = jax.lax.linalg.triangular_solve(
            jnp.broadcast_to(lkk.T, t.shape), t,
            left_side=False, lower=False, transpose_a=False,
        )
        return jnp.sum(jnp.abs(lkk)) + jnp.sum(solved[:, :1, :1])

    t = _timeit(run, d, tiles)
    flops = NB_PANEL**3 / 3.0 + L_PANEL * NB_PANEL**3
    return flops / t / 1e9


def bench_panel_getrf():
    """One LU-nopiv panel-column phase (diag L\\U + 63 right-solves)."""
    from slate_tpu.linalg.lu import _getrf_nopiv_rec

    d, tiles = _panel_operands("getrf")

    @jax.jit
    def run(d, t):
        lu = _getrf_nopiv_rec(d)
        solved = jax.lax.linalg.triangular_solve(
            jnp.broadcast_to(jnp.triu(lu), t.shape), t,
            left_side=False, lower=False, transpose_a=False,
        )
        return jnp.sum(jnp.abs(lu)) + jnp.sum(solved[:, :1, :1])

    t = _timeit(run, d, tiles)
    flops = 2.0 * NB_PANEL**3 / 3.0 + L_PANEL * NB_PANEL**3
    return flops / t / 1e9


# ---------------------------------------------------------------------------
# trailing-update microbenches (PR 20): the fused one-dispatch Pallas
# trailing-update kernels vs the XLA einsum bulk forms, at a mesh
# kernel's local trailing shape (an 8 x 8 local tile grid of nb = 256
# tiles — one device's share of a step's trailing update).  The panel
# benches above isolate the panel phase's dispatch latency; these
# isolate the OTHER side of every k-step — the grid-wide consume — where
# the fused kernel keeps the broadcast panels VMEM-resident across the
# whole tile stack instead of re-streaming them per XLA fusion.
# ---------------------------------------------------------------------------

MTL_UPD = NTL_UPD = 8
NB_UPD = 256


def _update_operands(masked):
    rng = np.random.default_rng(9)
    acc = rng.standard_normal(
        (MTL_UPD, NTL_UPD, NB_UPD, NB_UPD)).astype(np.float32)
    pan = rng.standard_normal((MTL_UPD, NB_UPD, NB_UPD)).astype(np.float32)
    urow = rng.standard_normal((NTL_UPD, NB_UPD, NB_UPD)).astype(np.float32)
    mask = (np.arange(MTL_UPD)[:, None] >= np.arange(NTL_UPD)[None, :]
            if masked else np.ones((MTL_UPD, NTL_UPD), bool))
    return (jnp.asarray(acc), jnp.asarray(pan), jnp.asarray(urow),
            jnp.asarray(mask))


def bench_update_summa(impl):
    """One SUMMA stationary-C consume over the local tile grid: xla =
    today's einsum + add; pallas = the fused one-dispatch grid kernel
    (summa_update_pallas, panels broadcast in VMEM)."""
    from slate_tpu.ops.pallas_ops import summa_update_pallas

    acc, pan, urow, _ = _update_operands(masked=False)
    if impl == "pallas":

        @jax.jit
        def run(acc, p, u):
            out = summa_update_pallas(acc, p, u)
            return jnp.sum(out[:, :, :1, :1])

    else:

        @jax.jit
        def run(acc, p, u):
            upd = jnp.einsum("iab,jbc->ijac", p, u,
                             precision=jax.lax.Precision.HIGHEST)
            return jnp.sum((acc + upd.astype(acc.dtype))[:, :, :1, :1])

    t = _timeit(run, acc, pan, urow)
    return 2.0 * MTL_UPD * NTL_UPD * NB_UPD**3 / t / 1e9


def bench_update_potrf(impl):
    """One potrf trailing herk (lower-masked rank-nb update of the local
    trailing stack) — dist_chol._chol_bulk's two lowerings."""
    from slate_tpu.ops.pallas_ops import chol_trailing_update_pallas

    view, pan, _, mask = _update_operands(masked=True)
    pan_t = pan  # the mesh kernel broadcasts the panel twice (row + col)
    if impl == "pallas":

        @jax.jit
        def run(v, p, pt, m):
            out = chol_trailing_update_pallas(v, p, pt, m)
            return jnp.sum(out[:, :, :1, :1])

    else:

        @jax.jit
        def run(v, p, pt, m):
            upd = jnp.einsum("iab,jcb->ijac", p, pt,
                             precision=jax.lax.Precision.HIGHEST
                             ).astype(v.dtype)
            out = v - jnp.where(m[:, :, None, None], upd, 0)
            return jnp.sum(out[:, :, :1, :1])

    t = _timeit(run, view, pan, pan_t, mask)
    flops = 2.0 * int(mask.sum()) * NB_UPD**3
    return flops / t / 1e9


def bench_update_getrf(impl):
    """One LU trailing gemm (full local stack, the strict-schedule
    _nopiv_bulk) — einsum + subtract vs the fused kernel."""
    from slate_tpu.ops.pallas_ops import lu_trailing_update_pallas

    t_loc, pan, urow, mask = _update_operands(masked=False)
    if impl == "pallas":

        @jax.jit
        def run(t, p, u, m):
            out = lu_trailing_update_pallas(t, p, u, m)
            return jnp.sum(out[:, :, :1, :1])

    else:

        @jax.jit
        def run(t, p, u, m):
            upd = jnp.einsum("iab,jbc->ijac", p, u,
                             precision=jax.lax.Precision.HIGHEST)
            return jnp.sum((t - upd.astype(t.dtype))[:, :, :1, :1])

    t = _timeit(run, t_loc, pan, urow, mask)
    return 2.0 * MTL_UPD * NTL_UPD * NB_UPD**3 / t / 1e9


def bench_panel_qr():
    """One tall-skinny Householder panel (m = 16384, w = 64) WITH the
    compact-WY T accumulation — the CAQR / two-stage building block."""
    from slate_tpu.linalg.qr import _larft, _panel_qr

    m, w = L_PANEL * NB_PANEL + NB_PANEL, 64
    a = jnp.asarray(
        np.random.default_rng(8).standard_normal((m, w)).astype(np.float32)
    )

    @jax.jit
    def run(a):
        vr, tau = _panel_qr(a)
        t = _larft(vr, tau)
        return jnp.sum(jnp.abs(tau)) + jnp.sum(t[:1])

    t = _timeit(run, a)
    return 2.0 * m * w * w / t / 1e9


# f64 factorizations: the shipped dispatch routes f64 (n >= 4096) to the
# LEFT-LOOKING forms (round 4) whose panel updates are large-k gemms — the
# shape where the Ozaki int8-MXU path wins — with digit-plane caching for
# potrf and f32-seeded all-gemm panels; these benches time exactly that
# dispatch (potrf_array / getrf_array), not the superseded scan paths.
N_F64 = 8192


def bench_potrf_f64():
    # the SHIPPED dispatch (potrf_array): f64 at this size routes to the
    # left-looking digit-cached Ozaki form, whose big-k panel updates ride
    # the int8 MXU (chol.py _potrf_ll_ozaki) — the path users actually get
    from slate_tpu.linalg.chol import potrf_array

    n = N_F64
    g = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.float64)
    a = (g + g.T) / (2.0 * jnp.sqrt(float(n))) + 3 * jnp.eye(n, dtype=jnp.float64)
    run = jax.jit(lambda x: jnp.sum(jnp.abs(jnp.diagonal(potrf_array(x)[0]))))
    t = _timeit_perturbed(run, a)
    return n**3 / 3.0 / t / 1e9


def bench_gemm_f64_emulated():
    # XLA f32-pair emulated DGEMM at the headline size: the denominator of
    # the honest Ozaki speedup (ozaki wins only in this huge-square
    # regime; see ops/matmul.py gate comment).  The f64_emulation context
    # ENFORCES the emulated path even if this is later switched to the
    # library matmul; outer reps perturb the input so no rep repeats an
    # identical dispatch.
    from slate_tpu.ops.matmul import f64_emulation

    a = jax.random.normal(jax.random.PRNGKey(0), (N, N), jnp.float64)
    b = jax.random.normal(jax.random.PRNGKey(1), (N, N), jnp.float64)

    with f64_emulation():

        @jax.jit
        def run(a, b):
            # b as an argument — a 512MB closure constant stalls compile
            def body(i, carry):
                acc, aa = carry
                return acc + jnp.matmul(aa, b), aa + 1e-9
            acc, _ = jax.lax.fori_loop(0, 2, body, (jnp.zeros((N, N), jnp.float64), a))
            return jnp.sum(acc[:1])

        t = _timeit_perturbed(run, a, b)
    return 2.0 * N**3 * 2 / t / 1e9


def bench_getrf_f64():
    # the SHIPPED dispatch (getrf_array): f64 at this size routes to the
    # left-looking form whose big-k Schur gemms ride the f64 dispatch
    from slate_tpu.linalg.lu import getrf_array

    n = N_F64
    m = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float64) / 64
    run = jax.jit(lambda x: jnp.sum(jnp.abs(jnp.diagonal(getrf_array(x).lu))))
    t = _timeit_perturbed(run, m)
    return 2.0 * n**3 / 3.0 / t / 1e9


# Mixed-precision mesh solve (ISSUE 8): the DEFAULT f64 gesv/posv now
# routes through the f32-factor + fused-refinement ladder
# (Option.MixedPrecision=auto, parallel/dist_refine.py).  These extras
# time the shipped driver against the same driver pinned to the direct
# f64 path — the mixed/f64 ratio IS the headline the routing change buys
# (f32 getrf runs ~40x the emulated-f64 rate, so the solve should
# approach factor-bound f32 time + a few refinement sweeps).
N_SOLVE = 4096


def _bench_mesh_solve(kind: str, mode: str):
    from slate_tpu.parallel import make_mesh
    from slate_tpu.parallel.drivers import gesv_mesh, posv_mesh
    from slate_tpu.types import Option

    n = N_SOLVE
    g = jax.random.normal(jax.random.PRNGKey(2), (n, n), jnp.float64)
    if kind == "posv":
        a = (g + g.T) / (2.0 * jnp.sqrt(float(n))) + 3 * jnp.eye(n, dtype=jnp.float64)
        drv, flops = posv_mesh, n**3 / 3.0
    else:
        # diagonally shifted so the f32 factor's condition stays well
        # inside the IR tier (no GMRES/fallback escalation in the timing)
        a = g + jnp.sqrt(float(n)) * jnp.eye(n, dtype=jnp.float64)
        drv, flops = gesv_mesh, 2.0 * n**3 / 3.0
    b = jax.random.normal(jax.random.PRNGKey(3), (n, 8), jnp.float64)
    mesh = make_mesh()  # near-square grid over every local device
    opts = {Option.MixedPrecision: mode}

    def run(a_):
        x, info = drv(a_, b, mesh, 256, opts=opts)
        jax.block_until_ready(x)
        return x

    run(a)  # compile + warm (the drivers are host-driven multi-program)
    best = float("inf")
    for i in range(2):
        ai = a + (i + 1) * 1e-9 * jnp.eye(n, dtype=jnp.float64)
        jax.block_until_ready(ai)
        t0 = time.perf_counter()
        run(ai)
        best = min(best, time.perf_counter() - t0)
    return flops / best / 1e9


# Serving runtime (ISSUE 11): solves/s of the stacked batch driver vs
# the one-at-a-time loop through the mesh driver at the canonical small
# serving shape.  The ratio IS the headline the serving layer buys —
# small problems can't fill the machine one at a time, batched ones can.
def _bench_serve_batched():
    from slate_tpu.parallel import make_mesh
    from slate_tpu.serve.smoke import measure_throughput

    thr = measure_throughput(make_mesh(), n=512, batch=8, reps=2,
                             loop_reps=1)
    if not thr["bitwise"]:
        raise RuntimeError("serve batched parity broke under bench")
    # stash both rates; the caller derives the speedup ratio
    _bench_serve_batched.last = thr
    return thr["batched_solves_per_s"]


# Service layer (ISSUE 19): end-to-end requests/s through the batch-
# window queue — submit-side binning, budget reservation and DRR dequeue
# included, so the number prices the scheduler itself, not just the
# stacked program it dispatches.
def _bench_serve_queue():
    import numpy as np
    import jax.numpy as jnp

    from slate_tpu.serve.cache import ExecutableCache
    from slate_tpu.serve.queue import BatchQueue, ManualClock
    from slate_tpu.serve.router import Router

    n, reqs, batch = 256, 32, 8
    rng = np.random.default_rng(7)
    g = rng.standard_normal((n, n))
    a = jnp.asarray(g @ g.T / n + 2 * np.eye(n))
    b = jnp.asarray(rng.standard_normal(n))
    router = Router(bins=(n,), cache=ExecutableCache())
    q = BatchQueue(router, max_batch=batch, window_s=0.001,
                   clock=ManualClock(), name="bench")
    try:
        for tenant in ("warm",):  # compile outside the timed stream
            q.submit("posv", a, b, tenant=tenant)
            q.drain()
        t0 = time.perf_counter()
        for i in range(reqs):
            q.submit("posv", a, b, tenant=("acme", "zeta")[i % 2])
        q.drain()
        dt = time.perf_counter() - t0
    finally:
        q.close()
    return reqs / dt


def _timeit_perturbed(fn, a, *rest, reps=2):
    """Best wall time with a PERTURBED first input per rep (no rep
    repeats an identical dispatch) and a queue drain per timing."""
    float(fn(a, *rest))  # compile + warm
    best = float("inf")
    for i in range(reps):
        ai = a + (i + 1) * 1e-9
        _ = float(jnp.sum(ai[:1, :4]))  # drain
        t0 = time.perf_counter()
        float(fn(ai, *rest))
        best = min(best, time.perf_counter() - t0)
    return best


import atexit
import contextlib
import signal


_PARTIAL_PATH = _os.environ.get("SLATE_TPU_BENCH_PARTIAL") or _os.path.join(
    _os.path.dirname(_os.path.abspath(__file__)), "bench_partial.json"
)

# the last complete result line, re-emitted by the atexit hook so ANY
# exit path after the headline (SIGTERM handler, an unhandled exception,
# a SystemExit from a failed extra) still ends stdout with a parseable
# line — a pre-PR-1 run died rc=124 with parsed=null because the kill landed
# where no line had been flushed.  A SIGKILL (timeout -k's second shot)
# skips atexit by definition; the atomically-rewritten partial file from
# the last _emit is the survivor there.
_LAST_LINE = [None]
_ATEXIT_ARMED = [False]


def _atexit_reemit():
    if _LAST_LINE[0]:
        print(_LAST_LINE[0], flush=True)


def _arm_atexit():
    if not _ATEXIT_ARMED[0]:
        atexit.register(_atexit_reemit)
        _ATEXIT_ARMED[0] = True


def _bench_line(gflops, extras):
    return json.dumps(
        {
            "metric": f"dgemm_f64_ozaki_int8_gflops_n{N}",
            "value": round(gflops, 1),
            "unit": "GFLOP/s",
            "vs_baseline": round(gflops / BASELINE_GFLOPS, 4),
            "extras": extras,
        }
    )


def _emit(gflops, extras):
    """Emit the CURRENT full result line: stdout (last line wins for the
    driver's tail parser) + an atomic rewrite of bench_partial.json, so
    every completed metric survives a timeout kill.  Also arms the
    atexit re-emit so any exit path flushes a final parseable line."""
    line = _bench_line(gflops, extras)
    _LAST_LINE[0] = line
    _arm_atexit()
    print(line, flush=True)
    try:
        tmp = _PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        _os.replace(tmp, _PARTIAL_PATH)
    except OSError as e:  # partial-file trouble must not kill the bench
        _progress(f"partial write failed: {e!r}")


@contextlib.contextmanager
def _alarm(seconds):
    """SIGALRM guard: abort one extra at the budget deadline (raises
    TimeoutError into the caller's except) instead of letting the driver's
    outer ``timeout`` SIGKILL the whole run mid-metric.  Best-effort:
    Python delivers the handler only at a bytecode boundary, so a single
    blocked XLA compile/execute call cannot be interrupted — the
    incremental ``_emit`` checkpoints are what actually preserve the
    already-measured numbers in that case."""
    if seconds is None or seconds <= 0 or not hasattr(signal, "SIGALRM"):
        yield
        return

    def handler(signum, frame):
        raise TimeoutError(f"extra exceeded the {seconds:.0f}s budget")

    old = signal.signal(signal.SIGALRM, handler)
    signal.alarm(max(1, int(seconds)))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


def main():
    from slate_tpu.ops.ozaki import matmul_f64

    # unset = a sane 600 s default (a pre-PR-1 run died rc=124 with the guard
    # off); an explicit SLATE_TPU_BENCH_TIMEOUT=0 still disables it
    budget = float(_os.environ.get("SLATE_TPU_BENCH_TIMEOUT", "600") or 0)
    deadline = _T0 + budget if budget > 0 else None

    # correctness gate: Ozaki f64 product vs numpy f64, 3-eps style
    m = 512
    rng = np.random.default_rng(0)
    am, bm = rng.standard_normal((m, m)), rng.standard_normal((m, m))
    chk = np.asarray(matmul_f64(jnp.asarray(am), jnp.asarray(bm)))
    ref = am @ bm
    rel = np.abs(chk - ref).max() / np.abs(ref).max()
    assert rel < 50 * m * np.finfo(np.float64).eps, f"ozaki residual {rel}"
    _progress(f"accuracy gate passed rel={rel:.2e}")

    a64 = jnp.asarray(rng.standard_normal((N, N)))
    b64 = jnp.asarray(rng.standard_normal((N, N)))
    _progress("operands transferred; timing ozaki dgemm")
    gflops = bench_dgemm_ozaki(a64, b64)
    _progress(f"headline {gflops:.0f} GFLOP/s")

    extras = {"ozaki_check_rel_err": float(rel)}
    _emit(gflops, extras)  # the headline survives even if every extra dies

    def _reemit_on_term(signum, frame):
        # timeout(1) sends SIGTERM before SIGKILL: flush the current full
        # line + partial file so the driver's tail parser wins either way
        _progress("SIGTERM: re-emitting final line and exiting")
        _emit(gflops, extras)
        raise SystemExit(124)

    if hasattr(signal, "SIGTERM"):
        signal.signal(signal.SIGTERM, _reemit_on_term)

    # cheapest-first: the f64 n=8192 factorizations (cold compiles alone
    # can eat several minutes each) run at the very end, after every
    # cheap metric has checkpointed
    for name, fn in [
        ("gemm_bf16_gflops", lambda: bench_gemm(jnp.bfloat16, 64, jnp.float32)),
        ("gemm_int8_gops", lambda: bench_gemm(jnp.int8, 64, jnp.int32)),
        ("gemm_f32_gflops", lambda: bench_gemm(jnp.float32, 32)),
        # panel story (ISSUE 6): the XLA panel phases alone
        ("panel_potrf_xla_gflops", bench_panel_potrf),
        ("panel_getrf_xla_gflops", bench_panel_getrf),
        ("panel_qr_xla_gflops", bench_panel_qr),
        # fused trailing-update story (PR 20): the k-step's OTHER side —
        # the grid-wide consume — under both Option.UpdateImpl lowerings
        ("update_summa_xla_gflops", lambda: bench_update_summa("xla")),
        ("update_summa_pallas_gflops", lambda: bench_update_summa("pallas")),
        ("update_potrf_xla_gflops", lambda: bench_update_potrf("xla")),
        ("update_potrf_pallas_gflops", lambda: bench_update_potrf("pallas")),
        ("update_getrf_xla_gflops", lambda: bench_update_getrf("xla")),
        ("update_getrf_pallas_gflops", lambda: bench_update_getrf("pallas")),
        ("potrf_f32_gflops", bench_potrf),
        ("getrf_f32_gflops", bench_getrf),
        ("gemm_f64_emulated_gflops", bench_gemm_f64_emulated),
        # mixed-precision mesh solve (ISSUE 8): the shipped auto ladder
        # vs the same driver pinned to the direct f64 path — mixed first
        # (cheap), the f64 baselines just before the n=8192 heavyweights
        # serving runtime (ISSUE 11): batched small-problem throughput
        ("serve_batched_solves_per_s", _bench_serve_batched),
        # service layer (ISSUE 19): queue-scheduled end-to-end requests/s
        ("serve_queue_reqs_per_s", _bench_serve_queue),
        ("gesv_mixed_gflops", lambda: _bench_mesh_solve("gesv", "auto")),
        ("posv_mixed_gflops", lambda: _bench_mesh_solve("posv", "auto")),
        ("gesv_f64_direct_gflops", lambda: _bench_mesh_solve("gesv", "off")),
        ("posv_f64_direct_gflops", lambda: _bench_mesh_solve("posv", "off")),
        (f"potrf_f64_gflops_n{N_F64}", bench_potrf_f64),
        (f"getrf_f64_gflops_n{N_F64}", bench_getrf_f64),
    ]:
        remaining = None if deadline is None else deadline - time.time()
        if remaining is not None and remaining <= 0:
            extras[name] = "skipped: SLATE_TPU_BENCH_TIMEOUT budget exhausted"
            _progress(f"extra: {name} skipped (budget exhausted)")
            continue
        _progress(f"extra: {name}")
        try:
            with _alarm(remaining):
                extras[name] = round(fn(), 1)
            _progress(f"extra: {name} = {extras[name]}")
        except Exception as e:  # one failed extra must not kill the headline
            extras[name] = f"failed: {type(e).__name__}"
            _progress(f"extra: {name} failed: {e!r:.200}")
        _emit(gflops, extras)  # atomic checkpoint after every metric
    for kind in ("summa", "potrf", "getrf"):
        ux = extras.get(f"update_{kind}_xla_gflops")
        up = extras.get(f"update_{kind}_pallas_gflops")
        if isinstance(ux, float) and isinstance(up, float) and ux > 0:
            extras[f"update_{kind}_pallas_speedup"] = round(up / ux, 2)
    for kind in ("gesv", "posv"):
        mx = extras.get(f"{kind}_mixed_gflops")
        fx = extras.get(f"{kind}_f64_direct_gflops")
        if isinstance(mx, float) and isinstance(fx, float) and fx > 0:
            extras[f"{kind}_mixed_vs_f64_speedup"] = round(mx / fx, 2)
    thr = getattr(_bench_serve_batched, "last", None)
    if thr is not None and thr["loop_solves_per_s"] > 0:
        extras["serve_vs_loop_speedup"] = round(thr["speedup"], 2)
    if isinstance(extras.get("gemm_bf16_gflops"), float):
        extras["bf16_mfu_vs_peak"] = round(extras["gemm_bf16_gflops"] / V5E_BF16_PEAK, 3)
    ge = extras.get("gemm_f64_emulated_gflops")
    if isinstance(ge, float) and ge > 0:
        extras["gemm_f64_ozaki_vs_emulated"] = round(gflops / ge, 2)
    if isinstance(extras.get("gemm_int8_gops"), float):
        extras["int8_mfu_vs_peak"] = round(extras["gemm_int8_gops"] / V5E_INT8_PEAK, 3)
        # f64-via-int8 hardware ceiling: int8 attainable / 45 unit-GEMMs
        extras["ozaki_frac_of_int8_ceiling"] = round(
            gflops / (extras["gemm_int8_gops"] / 45.0), 3
        )

    _emit(gflops, extras)  # final line carries the derived ratios too
    _emit_obs_report(gflops, extras)
    _emit_flight_report()
    _emit_mem_report()
    _emit_num_report()


def _emit_obs_report(gflops, extras):
    """RunReport twin of the driver-facing JSON line (slate_tpu.obs):
    written when SLATE_TPU_OBS=1 or SLATE_TPU_OBS_REPORT=<path> is set,
    diffable against any prior report (or this BENCH line itself) with
    ``python -m slate_tpu.obs.report --check``.  stdout stays untouched."""
    path = _os.environ.get("SLATE_TPU_OBS_REPORT")
    if not path and _os.environ.get("SLATE_TPU_OBS", "") in ("", "0"):
        return
    try:
        from slate_tpu.obs.report import write_report

        if not path:
            path = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                                 "artifacts", "obs", "bench_report.json")
        _os.makedirs(_os.path.dirname(_os.path.abspath(path)), exist_ok=True)
        values = {f"dgemm_f64_ozaki_int8_gflops_n{N}": float(gflops)}
        values.update({k: float(v) for k, v in extras.items()
                       if isinstance(v, (int, float))})
        write_report(path, name="bench",
                     config={"n": N, "n_f64": N_F64}, values=values)
        _progress(f"obs report written to {path}")
    except Exception as e:  # the headline line must never die on obs
        _progress(f"obs report failed: {e!r}")


def _emit_flight_report():
    """Flight-recorder twin (ISSUE 7): when SLATE_TPU_OBS_FLIGHT=<path>
    is set, run a small per-step potrf flight on the available devices
    and write the FlightReport there — the per-k-step schedule timeline
    (critical path, overlap efficiency, exposed comm) next to the
    headline numbers.  Step dispatch fences every phase, so this runs
    AFTER the headline measurements and never touches them."""
    path = _os.environ.get("SLATE_TPU_OBS_FLIGHT")
    if not path:
        return
    try:
        import jax as _jax

        from slate_tpu.obs import flight as _flight
        from slate_tpu.parallel import make_mesh as _make_mesh

        devs = _jax.devices()
        if len(devs) >= 8:
            mesh = _make_mesh(2, 4, devices=devs[:8])
        else:
            mesh = _make_mesh(1, len(devs), devices=devs)
        rep = _flight.run_flight("potrf", n=256, nb=32, depth=1, mesh=mesh)
        _flight.write_flight_report(path, rep)
        _progress(
            f"flight report written to {path} (overlap_eff "
            f"{rep['sched']['overlap_eff']:.3f}, critical_path "
            f"{rep['sched']['critical_path_s']:.4f}s)")
    except Exception as e:  # the headline line must never die on obs
        _progress(f"flight report failed: {e!r}")


def _emit_mem_report():
    """Memory-observability twin (ISSUE 9): when SLATE_TPU_OBS_MEM=<path>
    is set, run the memwatch pass (AOT memory analysis + MemoryModel
    comparison + donation-alias verification) for a small mesh potrf on
    the available devices and write the mem.* RunReport there — the
    compile-analysis keys are the machine-independent regression surface
    next to the headline numbers."""
    path = _os.environ.get("SLATE_TPU_OBS_MEM")
    if not path:
        return
    try:
        import jax as _jax

        from slate_tpu.obs import memwatch as _memwatch
        from slate_tpu.parallel import make_mesh as _make_mesh

        devs = _jax.devices()
        if len(devs) >= 8:
            mesh = _make_mesh(2, 4, devices=devs[:8])
        else:
            mesh = _make_mesh(1, len(devs), devices=devs)
        rep = _memwatch.run_memwatch("potrf", n=256, nb=32, mesh=mesh,
                                     with_donations=False)
        _memwatch.write_mem_report(path, rep)
        v = rep["values"]
        _progress(
            f"mem report written to {path} (temp "
            f"{v['mem.temp_bytes']:,.0f} B/dev, model err "
            f"{v['mem.model_err_frac']:.1%})")
    except Exception as e:  # the headline line must never die on obs
        _progress(f"mem report failed: {e!r}")


def _emit_num_report():
    """Numerics-observability twin (ISSUE 10): when SLATE_TPU_OBS_NUM=
    <path> is set, run the numwatch pass (monitored-factor growth/margin
    gauges + distributed Hager-Higham condest + mixed-ladder health
    routing on seeded adversarial inputs) and write the num.* RunReport
    there — the accuracy report shipping next to the perf numbers, so a
    bench artifact records not just how fast the kernels ran but whether
    the answers they produce are numerically healthy."""
    path = _os.environ.get("SLATE_TPU_OBS_NUM")
    if not path:
        return
    try:
        import jax as _jax

        from slate_tpu.obs import numwatch as _numwatch
        from slate_tpu.parallel import make_mesh as _make_mesh

        devs = _jax.devices()
        if len(devs) >= 8:
            mesh = _make_mesh(2, 4, devices=devs[:8])
        else:
            mesh = _make_mesh(1, len(devs), devices=devs)
        rep = _numwatch.run_numwatch("mixed", n=96, nb=16, mesh=mesh)
        _numwatch.write_num_report(path, rep)
        v = rep["values"]
        _progress(
            f"num report written to {path} (condest "
            f"{v.get('num.condest_cond', 0):.3g}, routed_gmres "
            f"{v.get('num.routed_gmres', 0):.0f}, ir_iters_well "
            f"{v.get('num.ir_iters_well', 0):.0f})")
    except Exception as e:  # the headline line must never die on obs
        _progress(f"num report failed: {e!r}")


def _selftest_kill():
    """Hidden harness for tests/test_bench_kill.py: emit a headline,
    register the SIGTERM/atexit emission machinery exactly as main()
    does, then block mid-'extra' until the test delivers SIGTERM — the
    rc=124 kill path must still end stdout with a parseable line and a
    parseable partial file."""
    gflops = 1.0
    extras = {"selftest": 1}
    _emit(gflops, extras)

    def _reemit_on_term(signum, frame):
        _progress("SIGTERM: re-emitting final line and exiting")
        _emit(gflops, extras)
        raise SystemExit(124)

    signal.signal(signal.SIGTERM, _reemit_on_term)
    print("SELFTEST_READY", file=sys.stderr, flush=True)
    while True:  # mid-extra: blocked until the kill arrives
        time.sleep(0.05)


if __name__ == "__main__":
    if "--selftest-kill" in sys.argv:
        _selftest_kill()
    else:
        main()
