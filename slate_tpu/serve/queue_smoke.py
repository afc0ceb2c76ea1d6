"""Queue smoke: the CI acceptance run for the service layer (ISSUE 19).

Drives a deterministic 64-request two-tenant stream through the
batch-window queue on an injectable ManualClock — every scheduling
decision in this run is about NUMBERS, never about how fast CI ran —
and asserts:

(a) **Windowed throughput**: the stream coalesces into at most
    ceil(N/B) dispatched batch programs, with ZERO steady-state
    retraces (trace-counter asserted after the first window), and every
    served solution BITWISE equal to one-at-a-time dispatch through a
    fresh reference Router — the queue is host-side scheduling only.
(b) **Fair-share dequeue**: an oversubscribed window dequeues by
    weighted deficit round robin — no pending tenant is shut out of a
    closed window (starvation freedom), service stays within one
    max-weight round of the weight ratio, and FIFO holds within a
    tenant.  No tenant's reservation ledger ever exceeded its budget.
(c) **Budget rejections**: a tenant submitting past its HBM budget is
    refused as the ``reject_budget`` terminal (counted, exactly-one-
    terminal), other tenants are untouched, and drained windows restore
    the tenant's headroom.
(d) **Admission memo**: a steady-state 100-request admission stream
    across two Routers computes the MemoryModel closed form EXACTLY
    once per (op, nb, grid, dtype, budget) key
    (``serve.max_n_computes``).
(e) **Control loop**: a seeded p95 latency spike trips the controller's
    hysteresis latch exactly once (no flapping under a sustained
    square-wave input), the actuation moves the (B, T) window knobs,
    and the ``controller`` event lands on the telemetry bus.
(f) **Packed dispatch**: a ragged posv window in ``dispatch="packed"``
    mode runs as ONE block-diagonal program whose unpacked solutions
    match the solo kernel to factorization accuracy.

Meshless ON PURPOSE: the stream is broadcast-impl-independent, so the
``SLATE_TPU_BCAST_IMPL=ring`` CI re-run reproduces every gated count
exactly.  Emits ``serve_queue.report.json`` (RunReport schema; the
``serve`` counter section rides in automatically) gated by
``obs.report --check --ignore '*latency*_s'``.

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m slate_tpu.serve.queue_smoke [--out artifacts/serve]
"""

from __future__ import annotations

import argparse
import os
import sys


def _spd(rng, n):
    import jax.numpy as jnp
    import numpy as np

    g = rng.standard_normal((n, n))
    return jnp.asarray(g @ g.T / n + 2 * np.eye(n))


def run_stream_phase(failures: list) -> dict:
    """(a)+(b): the 64-request two-tenant stream + the oversubscribed
    DRR window."""
    import numpy as np
    import jax.numpy as jnp

    from .cache import ExecutableCache
    from .metrics import serve_counts
    from .queue import BatchQueue, ManualClock
    from .router import Router

    rng = np.random.default_rng(19)
    n, total, batch = 32, 64, 8
    clk = ManualClock()
    qcache = ExecutableCache()
    router = Router(bins=(n,), hbm_budget=1 << 30, cache=qcache)
    q = BatchQueue(router, max_batch=batch, window_s=0.005, clock=clk,
                   budgets={"acme": 1 << 30, "zeta": 1 << 30},
                   weights={"acme": 2.0, "zeta": 1.0}, name="smoke")
    probs = [(_spd(rng, n), jnp.asarray(rng.standard_normal((n,))))
             for _ in range(total)]
    tenants = ["acme" if i % 2 == 0 else "zeta" for i in range(total)]
    c0 = serve_counts()
    tickets = []
    snapshot = None
    for i, ((a, b), tenant) in enumerate(zip(probs, tenants)):
        tickets.append(q.submit("posv", a, b, tenant=tenant))
        if i == batch - 1:
            # first window just closed by B-fill: its program is the
            # steady state — everything after must be ZERO retraces
            snapshot = qcache.snapshot_traces()
    q.drain()
    c1 = serve_counts()
    windows = c1["queue_windows"] - c0["queue_windows"]
    if windows > -(-total // batch):
        failures.append(
            f"stream phase: {total} requests dispatched {windows:.0f} "
            f"windows > ceil(N/B) = {-(-total // batch)} — windows are "
            "fragmenting")
    if c1["queue_dispatched"] - c0["queue_dispatched"] != total:
        failures.append("stream phase: dispatched count != submitted count")
    try:
        qcache.assert_steady(snapshot)
    except AssertionError as e:
        failures.append(f"stream phase: steady-state retrace: {e}")
    if any(not t.done() for t in tickets):
        failures.append("stream phase: a ticket never resolved")

    # bitwise parity vs one-at-a-time dispatch through a fresh Router
    # (the service layer is host-side scheduling ONLY)
    ref = Router(bins=(n,), hbm_budget=1 << 30, cache=ExecutableCache())
    bitwise = all(
        np.array_equal(np.asarray(t.result()),
                       np.asarray(ref.solve("posv", a, b, tenant=tn)))
        for t, (a, b), tn in zip(tickets, probs, tenants))
    if not bitwise:
        failures.append("stream phase: queued solutions are not bitwise-"
                        "equal to one-at-a-time Router dispatch")

    # no tenant's ledger ever exceeded its budget
    for tenant in ("acme", "zeta"):
        acct = q.ledger.account(tenant)
        if not 0 < acct.peak <= acct.budget:
            failures.append(f"stream phase: tenant {tenant} peak "
                            f"{acct.peak} outside (0, budget]")

    # (b) oversubscribe ONE window (12 pending, B=8) and dequeue by DRR
    q.max_batch = 16  # let the window fill past the dispatch size...
    over = [(_spd(rng, n), jnp.asarray(rng.standard_normal((n,))),
             "acme" if i % 2 == 0 else "zeta") for i in range(12)]
    otk = [q.submit("posv", a, b, tenant=t) for a, b, t in over]
    q.max_batch = 8   # ...then close it at B=8: 12 pending, 8 slots
    clk.advance(0.01)
    q.pump()          # the contended close: 8 of 12 dequeue by DRR
    clk.advance(0.01)
    q.pump()          # the leftover window's fresh deadline expires
    first = q.dispatch_log[-2]  # the contended close
    sel = first["tickets"]
    if len(sel) != 8:
        failures.append(f"DRR phase: contended close selected {len(sel)} "
                        "!= 8")
    by_tenant = {t: sum(1 for _s, tt in sel if tt == t)
                 for t in ("acme", "zeta")}
    # starvation freedom: both pending tenants appear in the close
    if min(by_tenant.values()) < 1:
        failures.append(f"DRR phase: a pending tenant was starved out of "
                        f"the close ({by_tenant})")
    # one-max-weight-round fairness: with weights 2:1 over 8 slots the
    # fair split is (16/3, 8/3); within one round means zeta >= 2 and
    # acme >= 4
    if by_tenant["acme"] < 4 or by_tenant["zeta"] < 2:
        failures.append(f"DRR phase: selection {by_tenant} further than "
                        "one max-weight round from the 2:1 weight ratio")
    # FIFO within tenant, across the whole oversubscribed dispatch order
    served_order = [s for entry in q.dispatch_log[-2:]
                    for s in entry["tickets"]]
    for tenant in ("acme", "zeta"):
        seqs = [s for s, tt in served_order if tt == tenant]
        if seqs != sorted(seqs):
            failures.append(f"DRR phase: FIFO broken within {tenant}: "
                            f"{seqs}")
    if any(not t.done() for t in otk):
        failures.append("DRR phase: leftover tickets never dispatched")
    q.close()
    return {"requests": total, "windows": windows, "bitwise": bitwise,
            "drr_split": by_tenant}


def run_budget_phase(failures: list) -> dict:
    """(c): per-tenant budget rejection + headroom restoration."""
    import jax.numpy as jnp
    import numpy as np

    from ..types import SlateError
    from . import trace as serve_trace
    from .cache import ExecutableCache
    from .metrics import serve_counts
    from .queue import BatchQueue, ManualClock
    from .router import Router

    rng = np.random.default_rng(23)
    n = 32
    clk = ManualClock()
    router = Router(bins=(n,), hbm_budget=1 << 30, cache=ExecutableCache())
    # cost of one binned f64 request: 3.5 * 32 * 32 * 8 = 28_672 bytes
    # -> a 100 kB budget admits exactly 3 in flight
    q = BatchQueue(router, max_batch=8, window_s=0.005, clock=clk,
                   budgets={"burst": 100_000}, name="smoke_budget")
    c0 = serve_counts()
    t0 = len(serve_trace.finished_traces())
    accepted, rejected = 0, 0
    for _ in range(5):
        a, b = _spd(rng, n), jnp.asarray(rng.standard_normal((n,)))
        try:
            q.submit("posv", a, b, tenant="burst")
            accepted += 1
        except SlateError:
            rejected += 1
    # an unaffected tenant keeps its default (device-sized) budget
    q.submit("posv", _spd(rng, n),
             jnp.asarray(rng.standard_normal((n,))), tenant="calm")
    c1 = serve_counts()
    if (accepted, rejected) != (3, 2):
        failures.append(f"budget phase: expected 3 accepts + 2 rejects at "
                        f"a 100kB budget, got {accepted}+{rejected}")
    if c1["queue_budget_rejects"] - c0["queue_budget_rejects"] != rejected:
        failures.append("budget phase: serve.queue_budget_rejects did not "
                        "count the refusals")
    rej_traces = [t for t in serve_trace.finished_traces()[t0:]
                  if t.outcome == "reject_budget"]
    if len(rej_traces) != rejected:
        failures.append(f"budget phase: {rejected} refusals produced "
                        f"{len(rej_traces)} reject_budget terminals")
    # a submit past the bin vocabulary is the OTHER reject outcome
    try:
        q.submit("posv", _spd(rng, 64),
                 jnp.asarray(rng.standard_normal((64,))), tenant="burst")
        failures.append("budget phase: an over-bin submit was admitted")
    except SlateError:
        pass
    clk.advance(0.01)
    q.pump()
    if q.ledger.account("burst").reserved != 0:
        failures.append("budget phase: drained windows did not restore "
                        "the tenant's headroom")
    q.close()
    return {"accepted": accepted, "rejected": rejected}


def run_memo_phase(failures: list) -> dict:
    """(d): the admission memo computes each MemoryModel key once over a
    steady-state 100-request stream (across Router instances)."""
    from .metrics import serve_counts
    from .router import Router

    # a budget value no other phase uses -> a FRESH process-global key
    budget = 987_654_321
    c0 = serve_counts()
    r1 = Router(bins=(32,), hbm_budget=budget)
    r2 = Router(bins=(32,), hbm_budget=budget)
    for _ in range(50):
        r1.admit("posv", 32)
        r2.admit("posv", 32)
    computes = serve_counts()["max_n_computes"] - c0["max_n_computes"]
    if computes != 1:
        failures.append(
            f"memo phase: 100 admissions across 2 routers evaluated the "
            f"MemoryModel closed form {computes:.0f} times (want exactly "
            "1 per (op, nb, grid, dtype, budget) key)")
    return {"computes": computes}


def run_controller_phase(failures: list) -> dict:
    """(e): a seeded latency spike trips the SLA control loop exactly
    once — hysteresis + cooldown prove it cannot flap."""
    from ..obs import REGISTRY, live as obs_live
    from .cache import ExecutableCache
    from .controller import ServiceController
    from .metrics import serve_counts
    from .queue import BatchQueue, ManualClock
    from .router import Router

    router = Router(bins=(32,), hbm_budget=1 << 30,
                    cache=ExecutableCache())
    q = BatchQueue(router, max_batch=8, window_s=0.005,
                   clock=ManualClock(), name="smoke_ctrl")
    # failure latch deliberately out of reach: the earlier phases SEEDED
    # reject outcomes into the global SLA surface, and this phase is
    # about the latency latch alone
    ctrl = ServiceController(q, slo_p95_s=0.25, arm=2, cooldown=2,
                             failure_rate_hi=0.9, failure_rate_lo=0.0)
    base = (q.max_batch, q.window_s)
    c0 = serve_counts()
    # the spike: enough 2 s observations to own the pooled p95
    for _ in range(32):
        REGISTRY.observe("serve.latency_s", 2.0, op="posv",
                         klass="friendly", outcome="served")
    if ctrl.signals()["p95_s"] < 1.0:
        failures.append("controller phase: seeded spike did not surface "
                        "in the p95 signal")
    acted = []
    for _ in range(6):  # sustained square-wave input
        acted += ctrl.step()
    trips = serve_counts()["controller_actuations"] - \
        c0["controller_actuations"]
    if trips != 1:
        failures.append(f"controller phase: sustained spike produced "
                        f"{trips:.0f} actuations (hysteresis should latch "
                        "after exactly 1)")
    if not acted or acted[0]["action"] != "shrink_window":
        failures.append(f"controller phase: expected a shrink_window "
                        f"actuation, got {[a['action'] for a in acted]}")
    if (q.max_batch, q.window_s) == base or q.window_s >= base[1]:
        failures.append("controller phase: the actuation did not move "
                        "the (B, T) window knobs")
    if not any(e["kind"] == "controller"
               for e in obs_live.BUS.events()):
        failures.append("controller phase: no controller event on the "
                        "telemetry bus")
    q.close()
    return {"trips": trips,
            "actions": [a["action"] for a in acted]}


def run_packed_phase(failures: list) -> dict:
    """(f): a ragged posv window in packed mode runs as ONE
    block-diagonal program."""
    import jax.numpy as jnp
    import numpy as np

    from ..linalg.chol import posv_array
    from . import trace as serve_trace
    from .cache import ExecutableCache
    from .metrics import serve_counts
    from .queue import BatchQueue, ManualClock
    from .router import Router

    rng = np.random.default_rng(29)
    clk = ManualClock()
    router = Router(bins=(32,), hbm_budget=1 << 30,
                    cache=ExecutableCache())
    q = BatchQueue(router, max_batch=8, window_s=0.005, clock=clk,
                   dispatch="packed", name="smoke_packed")
    sizes = (20, 28, 32)
    probs = [(_spd(rng, sz), jnp.asarray(rng.standard_normal((sz, 1))))
             for sz in sizes]
    c0 = serve_counts()
    t0 = len(serve_trace.finished_traces())
    tks = [q.submit("posv", a, b) for a, b in probs]
    clk.advance(0.01)
    q.pump()
    c1 = serve_counts()
    if c1["queue_packed_dispatches"] - c0["queue_packed_dispatches"] != 1:
        failures.append("packed phase: 3 ragged requests did not dispatch "
                        "as ONE packed program")
    ok = True
    for tk, (a, b) in zip(tks, probs):
        ref, _f, info = posv_array(a, b)
        if int(info) != 0 or not np.allclose(
                np.asarray(tk.result()), np.asarray(ref),
                rtol=1e-9, atol=1e-9):
            ok = False
    if not ok:
        failures.append("packed phase: unpacked solutions drifted from "
                        "the solo kernel past factorization accuracy")
    outcomes = [t.outcome for t in serve_trace.finished_traces()[t0:]]
    if outcomes != ["served"] * len(sizes):
        failures.append(f"packed phase: outcomes {outcomes} != all served")
    q.close()
    return {"packed_ok": ok}


def run_smoke(out_dir: str) -> int:
    import jax

    jax.config.update("jax_enable_x64", True)  # f64 serving classes

    from .. import obs
    # import the bus up front: phase (e) asserts the controller event
    # reaches it (producers probe sys.modules, so it must be loaded)
    from ..obs import live as _obs_live  # noqa: F401
    from ..obs import report
    from . import metrics as serve_metrics
    from .cache import executable_cache

    obs.reset()
    obs.enable()
    serve_metrics.reset()
    executable_cache.clear()
    failures: list = []

    stream = run_stream_phase(failures)
    budget = run_budget_phase(failures)
    memo = run_memo_phase(failures)
    ctrl = run_controller_phase(failures)
    packed = run_packed_phase(failures)

    os.makedirs(out_dir, exist_ok=True)
    rep_path = os.path.join(out_dir, "serve_queue.report.json")
    # every value below is deterministic under the ManualClock workload
    # (no *_runtime_* keys needed); the wall-clock latency quantiles the
    # serve section carries are the CI gate's --ignore '*latency*_s'
    report.write_report(
        rep_path, name="serve_queue",
        config={"n": 32, "batch": 8, "window_s": 0.005,
                "driver": "batch_queue_meshless", "clock": "manual"},
        values={
            "serve.queue_stream_requests": float(stream["requests"]),
            "serve.queue_stream_windows": float(stream["windows"]),
            "serve.queue_stream_bitwise_ok": float(stream["bitwise"]),
            "serve.queue_drr_acme": float(stream["drr_split"]["acme"]),
            "serve.queue_drr_zeta": float(stream["drr_split"]["zeta"]),
            "serve.queue_budget_accepts": float(budget["accepted"]),
            "serve.queue_budget_rejections": float(budget["rejected"]),
            "serve.queue_memo_computes": float(memo["computes"]),
            "serve.queue_controller_trips": float(ctrl["trips"]),
            "serve.queue_packed_ok": float(packed["packed_ok"]),
        })
    import json

    with open(rep_path) as f:
        rep = json.load(f)
    errs = report.validate_report(rep)
    if errs:
        failures.append(f"RunReport schema: {errs}")
    serve_sec = rep.get("serve") or {}
    if serve_sec.get("queue_submitted", 0) <= 0:
        failures.append("serve section missing queue counters — "
                        "obs.report is not folding serve.queue_* in")

    if failures:
        print(f"serve.queue_smoke: FAILED with {len(failures)} problem(s):")
        for msg in failures:
            print(f"  FAIL {msg}")
        return 1
    print(f"serve.queue_smoke: OK — {stream['requests']} requests in "
          f"{stream['windows']:.0f} windows (0 retraces, bitwise parity), "
          f"DRR split {stream['drr_split']}, "
          f"{budget['rejected']} budget reject(s), 1 memo compute, "
          f"{ctrl['trips']:.0f} controller trip, packed dispatch OK, "
          f"report {rep_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m slate_tpu.serve.queue_smoke")
    ap.add_argument("--out", default=os.path.join("artifacts", "serve"))
    args = ap.parse_args(argv)
    return run_smoke(args.out)


if __name__ == "__main__":
    sys.exit(main())
