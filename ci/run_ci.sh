#!/usr/bin/env bash
# One-command CI: static analysis first (fails fast, no kernels run), then
# the unit/numerical suite on the 8-device virtual CPU mesh, then the
# example smoke tests (the reference's Jenkins matrix runs
# test/run_tests.py + examples/run_tests.py the same way, Jenkinsfile:16-26).
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"

# ---- static gates -------------------------------------------------------
# slate_lint: jaxpr + AST invariants over every registered distributed
# driver (see slate_tpu/analysis/).  A lint failure is a CI failure.
python -m slate_tpu.analysis.lint

# contract-matrix autoprover (ISSUE 16): every registry entry's declared
# option contracts (off_jaxpr_identical / zero_extra_collectives /
# bytes_invariant) proved by abstract trace + comm audit, plus the
# registry-completeness and naming-convention checks.  The ring re-run
# proves the matrix holds under the non-default broadcast lowering too
# (the hop schedules move the same bytes, so every cell must re-prove).
python -m slate_tpu.analysis.contracts
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.analysis.contracts

# self-checks: each gate must actually trip on its seeded violation,
# otherwise a silent analysis regression would wave everything through.
# Exit code must be EXACTLY 1 (findings) — 2 means the seeded path
# itself crashed.  The three ISSUE 16 SPMD passes (branch-divergent
# collectives, broken ppermute pair, read-after-donate) and the two
# contract seeds (undeclared / broken declaration) gate beside the
# original donation seed.
check_seed() {  # check_seed <module> <args...>
  set +e
  python -m "$@" > /dev/null 2>&1
  seed_rc=$?
  set -e
  if [ "$seed_rc" -ne 1 ]; then
    echo "static-analysis self-check FAILED: '$*' exited $seed_rc" \
         "(want 1)" >&2
    exit 1
  fi
}
check_seed slate_tpu.analysis.lint --skip-trace --seed-violation donation
check_seed slate_tpu.analysis.lint --only seeded \
    --seed-violation branch-divergence
check_seed slate_tpu.analysis.lint --only seeded --seed-violation ppermute-pair
check_seed slate_tpu.analysis.lint --only seeded \
    --seed-violation read-after-donate
check_seed slate_tpu.analysis.contracts --only seeded \
    --seed-violation undeclared-contract
check_seed slate_tpu.analysis.contracts --only seeded \
    --seed-violation broken-contract

# obs smoke: a tiny instrumented potrf_dist on the 8-device mesh must
# emit a schema-valid RunReport (wall/compile time, flop estimate, comm
# bytes) + a Perfetto-loadable trace with nested spans, and the
# `obs.report --check` gate must pass an unchanged report while flagging
# a synthetic 2x regression (slate_tpu/obs/smoke.py validates all of it)
python -m slate_tpu.obs.smoke --out artifacts/obs

# flight smoke (ISSUE 7): the step-level flight recorder — tiny summa +
# potrf re-run as per-step fenced dispatches under BOTH broadcast
# lowerings (psum + ring).  Gates: schema-valid FlightReports, per-device
# Perfetto Gantt with broadcast hop flow events, overlap_eff == 0 at
# lookahead depth 0 and > 0 at depth 1 (the number that proves the
# Option.Lookahead overlap), results numerically correct.  The fresh ring
# reports then gate against the committed references on the
# machine-independent keys only (modeled/measured bytes, resid): the
# millisecond wall-clock keys AND overlap_eff (a ratio of measured
# durations) depend on the runner's per-dispatch host round-trip, so
# they are --ignore'd rather than gated against another machine's
# numbers — the smoke itself asserts the depth-1-vs-0 overlap contrast
# on THIS machine.
python -m slate_tpu.obs.flight --smoke --out artifacts/obs_flight
python -m slate_tpu.obs.report --check \
    artifacts/obs_flight/flight_summa.flight.json \
    artifacts/obs/flight_summa.flight.json --threshold 4 \
    --ignore 'sched.*_s' --ignore 'sched.overlap_eff'
python -m slate_tpu.obs.report --check \
    artifacts/obs_flight/flight_potrf.flight.json \
    artifacts/obs/flight_potrf.flight.json --threshold 4 \
    --ignore 'sched.*_s' --ignore 'sched.overlap_eff'
# ISSUE 15: the QR/eig chains' flights (strict schedules — the smoke
# asserts overlap_eff == 0 by construction; the byte surface gates here)
python -m slate_tpu.obs.report --check \
    artifacts/obs_flight/flight_geqrf.flight.json \
    artifacts/obs/flight_geqrf.flight.json --threshold 4 \
    --ignore 'sched.*_s' --ignore 'sched.overlap_eff'
python -m slate_tpu.obs.report --check \
    artifacts/obs_flight/flight_he2hb.flight.json \
    artifacts/obs/flight_he2hb.flight.json --threshold 4 \
    --ignore 'sched.*_s' --ignore 'sched.overlap_eff'

# memwatch smoke (ISSUE 9): the HBM memory observability layer — AOT
# compile memory analysis of summa + potrf on the 8-device mesh must
# match the analytic MemoryModel within 10%, every donation-registry
# entry must MEASURABLY alias in its compiled executable, and the mem
# gate must trip on a seeded donation loss.  The fresh reports then gate
# against the committed references on the compile-analysis keys only
# (arg/out/temp/alias bytes + model + donation fracs are
# machine-independent at fixed shape); the runtime live/allocator keys
# depend on what else the runner holds live, so they are --ignore'd —
# as is model_err_frac, a near-zero ratio the smoke already bounds at
# 10% absolute (ratio-gating 0.008 vs 0.015 would flake on benign XLA
# buffer-assignment shifts while the byte keys catch any real change).
python -m slate_tpu.obs.memwatch --smoke --out artifacts/obs_mem
python -m slate_tpu.obs.report --check \
    artifacts/obs_mem/mem_summa.report.json \
    artifacts/obs/mem_summa.report.json \
    --ignore 'mem.*_runtime_*' --ignore 'mem.model_err_frac'
python -m slate_tpu.obs.report --check \
    artifacts/obs_mem/mem_potrf.report.json \
    artifacts/obs/mem_potrf.report.json \
    --ignore 'mem.*_runtime_*' --ignore 'mem.model_err_frac'

# numwatch smoke (ISSUE 10): the numerics observability layer — seeded
# adversarial inputs (Wilkinson growth, prescribed-spectrum
# ill-conditioned, near-singular-diagonal SPD) through the monitored
# kernels must trip the num.* gauges exactly (the Wilkinson growth is
# the CLOSED-FORM 2^{n-1}), the distributed Hager-Higham condest must
# match the single-chip estimators to rtol, the mixed ladder must
# health-route the pathological input to the GMRES tier, and every
# non-runtime gauge must be BITWISE-invariant across psum/ring (asserted
# inside the smoke).  The fresh reports then gate against the committed
# references: growth factors, condition estimates and iteration counts
# are bitwise-reproducible at fixed shape, so only the wall-clock keys
# are --ignore'd — the accuracy surface gates tight.
python -m slate_tpu.obs.numwatch --smoke --out artifacts/obs_num
for op in lu potrf mixed qr; do
  python -m slate_tpu.obs.report --check \
      "artifacts/obs_num/num_${op}.report.json" \
      "artifacts/obs/num_${op}.report.json" \
      --ignore 'num.*_runtime_*'
done
# the acceptance bound "gate green under both psum and ring": the smoke
# artifacts above ran ring; re-derive the lu gauges under the explicit
# legacy psum lowering and gate them against the SAME committed ring
# reference — they pass because the values are equal, not merely close
python -m slate_tpu.obs.numwatch lu --impl psum \
    --out artifacts/obs_num/num_lu_psum.report.json
python -m slate_tpu.obs.report --check \
    artifacts/obs_num/num_lu_psum.report.json \
    artifacts/obs/num_lu.report.json \
    --ignore 'num.*_runtime_*'

# serve smoke (ISSUE 11): the serving runtime — the stacked batch driver
# must beat the one-at-a-time mesh-dispatch loop >= 3x in solves/s at
# n = 512 with bitwise per-problem parity, the executable cache must
# perform ZERO retraces after warm-up (trace-counter asserted), ragged
# block-diagonal packing must unpack exactly (non-interaction bitwise),
# and the committed autotuned table (artifacts/serve/tuned.json, written
# by `python -m slate_tpu.serve.tune` from measured sched.* flights)
# must load and resolve with the explicit > context > env > tuned > auto
# precedence.  The ring re-run proves the env tier keeps outranking the
# tuned tier end-to-end.  The fresh report gates against the committed
# reference on the deterministic cache-hygiene keys; machine-dependent
# rates carry the _runtime_ infix and are --ignore'd.
python -m slate_tpu.serve.smoke --out artifacts/serve_ci
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.serve.smoke \
    --out artifacts/serve_ci_ring
# (the serve section now carries the SLA latency quantiles too — wall
# clock, so this gate ignores them exactly like the SLA gate below and
# keeps only the machine-independent counts tight)
python -m slate_tpu.obs.report --check \
    artifacts/serve_ci/serve.report.json \
    artifacts/obs/serve.report.json \
    --ignore 'serve.*_runtime_*' --ignore '*latency*_s'

# request-level SLA gate (ISSUE 14): the smoke's SLA phase drove a
# deterministic meshless request stream through the Router; its
# serve_sla.report.json carries the latency histogram reductions +
# outcome-attribution totals/rates.  The quantiles are wall clock
# (--ignore '*latency*_s'); the shape/count/rate keys — per-class
# histogram counts, outcome counts, outcome rates — are
# machine-independent under the fixed stream and gate tight against the
# committed reference under BOTH lowerings (the stream is meshless, so
# ring must reproduce the counts exactly).  serve.stats then formats
# the fresh artifact as Prometheus text — the export-surface smoke.
python -m slate_tpu.obs.report --check \
    artifacts/serve_ci/serve_sla.report.json \
    artifacts/obs/serve_sla.report.json \
    --ignore '*latency*_s'
python -m slate_tpu.obs.report --check \
    artifacts/serve_ci_ring/serve_sla.report.json \
    artifacts/obs/serve_sla.report.json \
    --ignore '*latency*_s'
python -m slate_tpu.serve.stats artifacts/serve_ci/serve_sla.report.json \
    > /dev/null

# service-layer queue smoke (ISSUE 19): the async batch-window queue —
# a deterministic 64-request two-tenant ManualClock stream must coalesce
# into <= ceil(N/B) dispatched programs with ZERO steady-state retraces
# and bitwise parity to one-at-a-time Router dispatch, the weighted-DRR
# dequeue must keep every tenant within one max-weight round (no
# starvation, FIFO within tenant), per-tenant budget overruns must
# terminate as counted reject_budget outcomes with headroom restored on
# drain, the admission memo must evaluate each MemoryModel key exactly
# once over 100 admissions, the SLA controller must trip EXACTLY once on
# a seeded p95 spike (hysteresis — no flapping), and a ragged packed
# window must dispatch as one block-diagonal program.  The stream is
# meshless, so the ring re-run must reproduce every gated count exactly;
# only the wall-clock latency quantiles are --ignore'd.
python -m slate_tpu.serve.queue_smoke --out artifacts/serve_queue_ci
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.serve.queue_smoke \
    --out artifacts/serve_queue_ci_ring
python -m slate_tpu.obs.report --check \
    artifacts/serve_queue_ci/serve_queue.report.json \
    artifacts/obs/serve_queue.report.json \
    --ignore '*latency*_s'
python -m slate_tpu.obs.report --check \
    artifacts/serve_queue_ci_ring/serve_queue.report.json \
    artifacts/obs/serve_queue.report.json \
    --ignore '*latency*_s'
# the export surface's new families (ISSUE 15): one scrape carries the
# num.* accuracy gauges and the sched.* schedule keys next to serve.* —
# format the fresh numwatch + flight artifacts and assert both appear
python -m slate_tpu.serve.stats artifacts/obs_num/num_qr.report.json \
    | grep -q 'slate_tpu_num_qr_orth_margin_fused'
python -m slate_tpu.serve.stats \
    artifacts/obs_flight/flight_geqrf.flight.json \
    | grep -q 'slate_tpu_sched_model_bytes'

# telemetry spine (ISSUE 17): start the live scrape endpoint, drive a
# tiny two-tenant Router workload (meshless rounds + one checkpointed/
# monitored mesh solve), scrape it over HTTP mid-process, and require
# validator-clean Prometheus text carrying ALL FOUR families (serve.*,
# sched.*, mem.*, num.*), a validator-clean unified Perfetto trace with
# >= 3 track types correlated by one request's trace_id, and a fresh
# ledger entry — obs.live --ci asserts all of it and exits nonzero
# otherwise.  The ring re-run proves the spine under the non-default
# broadcast lowering (the sched.link_bytes hop records come from the
# ring ppermute schedule there).  The ledger seeded from the committed
# entries then gates the fresh run against the N-run median
# (--trend); latency quantiles are wall clock and stay ignored.
python -m slate_tpu.obs.live --ci --out artifacts/obs_live
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.obs.live --ci \
    --out artifacts/obs_live_ring
python -m slate_tpu.obs.report --trend artifacts/obs_live/ledger \
    --ignore '*latency*_s'
python -m slate_tpu.obs.report --trend artifacts/obs_live_ring/ledger \
    --ignore '*latency*_s'

# scaling-curve artifact (ISSUE 7 satellite): fold the MULTICHIP round
# artifacts into one RunReport-schema curve and schema-validate it
# through the standard CLI (the committed twin lives at
# artifacts/obs/scaling.report.json)
python tools/scaling_report.py --out artifacts/obs_flight/scaling.report.json
python -m slate_tpu.obs.report artifacts/obs_flight/scaling.report.json > /dev/null

# ft smoke: the ABFT acceptance run — one injected single-tile fault per
# op class (SUMMA gemm / mesh potrf / LU-nopiv / trsm / her2k) must be detected
# and corrected on the 8-device mesh, the recompute + FtError escalations
# must fire, and the ft.* counters must land in a schema-valid RunReport
# so detection-coverage regressions gate like perf (slate_tpu/ft/smoke.py)
python -m slate_tpu.ft.smoke --out artifacts/ft

# checkpoint/restart smoke (ISSUE 12 + 13): the elastic-reliability
# acceptance run — seeded kill -> resume on the SAME mesh must be
# BITWISE-identical to the uninterrupted factorization for potrf,
# LU-nopiv, partial-pivot LU, the distributed CAQR, and the two-stage
# eig stage-1 reduction (the last two over MULTI-ARRAY carries);
# kill -> resume on a RESHAPED 4x2 mesh must land the bitwise-same
# solution for the tile-stack ops through the shard_map block-cyclic
# redistribution (itself asserted bitwise vs the eager path) while the
# grid-locked multi-array carries REFUSE the reshaped grid with a
# structured error; snapshots survive a disk round trip; an in-segment
# kill loses exactly the steps since the last snapshot; async snapshots
# are bitwise-equal to sync; and the ft.ckpt_* recovery-cost counters
# land in a schema-valid RunReport.  The ring re-run proves the segment
# chains thread Option.BcastImpl end-to-end; the fresh report gates
# against the committed reference on the deterministic keys (snapshot /
# redistribute bytes, lost steps, bitwise-diff zeros) — resume wall time
# and the async-copy overlap are machine-dependent and carry the
# *_runtime_* / *_overlap_s infixes.
python -m slate_tpu.ft.ckpt_smoke --out artifacts/ft_ckpt
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.ft.ckpt_smoke \
    --out artifacts/ft_ckpt_ring
python -m slate_tpu.obs.report --check \
    artifacts/ft_ckpt/ft_ckpt.report.json \
    artifacts/obs/ft_ckpt.report.json --ignore '*_runtime_*' \
    --ignore '*_overlap_s'

# broadcast-engine cross-impl pass (ISSUE 5): re-run both smokes under the
# explicit ring lowering so the non-default Option.BcastImpl path is
# exercised end-to-end on every commit (the default runs above already
# cover auto -> doubling on the 2x4 grid; slate_lint covers psum via the
# *_psum registry variants).  Two gates on the ring report vs the
# default-lowering report: `obs.report --check` at threshold 3 keeps the
# TIMING metrics from flaking a shared CI runner, and a dedicated exact
# comparison enforces the byte invariant the loose threshold cannot —
# ring and doubling move the SAME (s-1)-payload link bytes per rooted
# broadcast, so the absorbed comm_bytes must be equal to the byte.
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.obs.smoke --out artifacts/obs_ring
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.ft.smoke --out artifacts/ft_ring
python -m slate_tpu.obs.report --check \
    artifacts/obs_ring/smoke_report.json artifacts/obs/smoke_report.json \
    --threshold 3
python - <<'PY'
import json
ring = json.load(open("artifacts/obs_ring/smoke_report.json"))["values"]
base = json.load(open("artifacts/obs/smoke_report.json"))["values"]
if ring["comm_bytes"] != base["comm_bytes"]:
    raise SystemExit(
        f"cross-impl comm-byte gate: ring smoke absorbed "
        f"{ring['comm_bytes']:.0f} B/dev but the default lowering "
        f"{base['comm_bytes']:.0f} — the engine hop schedules must move "
        "identical link bytes"
    )
print(f"ci: cross-impl comm bytes equal ({ring['comm_bytes']:.0f} B/dev)")
PY

# fused trailing-update cross-impl pass (PR 20): re-run the smokes under
# the explicit Pallas trailing-update lowering — on this CPU harness the
# one-kernel fused updates (SUMMA stationary-C consume, potrf trailing
# herk, LU-nopiv trailing gemm) run under the Pallas interpreter, so
# Option.UpdateImpl=pallas is exercised end-to-end on every commit.  The
# default runs above cover auto -> xla (bitwise today's update loops),
# and the contracts runs at the top already prove BOTH lowerings of
# every *_upd_* matrix cell — the xla-side cells are jaxpr-identity
# proofs against the default trace, the pallas-side cells are
# bytes_invariant proofs against their xla twins, each under psum AND
# ring.  (No contracts re-run under this env: the off-pole cells
# compare pinned-xla against the ambient default, which the env itself
# would move.)  The flight re-run gates the byte surface: the fused
# update sits strictly inside the compute half of each k-step, so the
# modeled/measured bytes must equal the committed default-lowering
# references exactly (wall-clock keys and overlap_eff stay
# machine-dependent and --ignore'd, as above).
SLATE_TPU_UPDATE_IMPL=pallas python -m slate_tpu.obs.smoke --out artifacts/obs_upd
SLATE_TPU_UPDATE_IMPL=pallas python -m slate_tpu.ft.smoke --out artifacts/ft_upd
SLATE_TPU_UPDATE_IMPL=pallas python -m slate_tpu.obs.flight --smoke \
    --out artifacts/obs_flight_upd
for op in summa potrf; do
  python -m slate_tpu.obs.report --check \
      "artifacts/obs_flight_upd/flight_${op}.flight.json" \
      "artifacts/obs/flight_${op}.flight.json" --threshold 4 \
      --ignore 'sched.*_s' --ignore 'sched.overlap_eff'
done

# fused-update parity artifact: regenerate the fused trailing-update vs
# XLA-reference RunReports and gate the parity — the update kernels
# replicate the XLA op sequence exactly (contraction at HIGHEST →
# astype → select → add), so the tool requires BITWISE equality under
# the interpreter, a stronger contract than the panel threshold class.
# The obs.report --check pass re-validates the committed artifact pair
# through the standard CLI.
python tools/update_report.py --out artifacts/obs
python -m slate_tpu.obs.report --check \
    artifacts/obs/update_pallas.report.json artifacts/obs/update_xla.report.json \
    --threshold 3

# mixed-precision solve smoke (ISSUE 8): the default f64 gesv/posv route
# through the Option.MixedPrecision=auto ladder (f32 mesh factor + fused
# on-device refinement, GMRES-IR escalation, full-f64 fallback).  The
# smoke asserts the acceptance surface — off is jaxpr-identical to the
# direct path, auto and the Ozaki int8 residual meet the refine.py gate,
# the GMRES tier converges, the ir.* counters land in a schema-valid
# RunReport — then re-runs under the ring broadcast to prove opts thread
# end-to-end into the f32 factor AND the refinement loop's residual SUMMA.
python -m slate_tpu.parallel.mixed_smoke --out artifacts/mixed
SLATE_TPU_BCAST_IMPL=ring python -m slate_tpu.parallel.mixed_smoke \
    --out artifacts/mixed_ring

# mixed accuracy artifact: regenerate the off-vs-auto RunReports and gate
# the residual-gate parity (the mixed ladder may not be numerically worse
# than the direct f64 solve); the obs.report --check pass re-validates
# the COMMITTED artifact pair through the standard CLI.
python tools/mixed_report.py --out artifacts/obs --threshold 3
python -m slate_tpu.obs.report --check \
    artifacts/obs/mixed_auto.report.json artifacts/obs/mixed_off.report.json \
    --threshold 3

# ruff / mypy: configured in pyproject.toml; the container image may not
# ship them, so gate on availability rather than skipping silently
if command -v ruff > /dev/null 2>&1; then
  ruff check slate_tpu tools tests
else
  echo "ci: ruff not installed; skipping (config lives in pyproject.toml)"
fi
if command -v mypy > /dev/null 2>&1; then
  mypy --config-file pyproject.toml
else
  echo "ci: mypy not installed; skipping (config lives in pyproject.toml)"
fi

# ---- dynamic suites -----------------------------------------------------
# tests/ includes test_lookahead.py in the default tier: the Option.Lookahead
# pipelined schedules must stay BITWISE identical to the strict depth-0
# schedule on the 8-device mesh, and the comm-audit byte totals must be
# depth-invariant (lookahead moves when bytes travel, never how many).
python -m pytest tests/ -q
python examples/run_tests.py
