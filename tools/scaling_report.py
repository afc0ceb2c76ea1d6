#!/usr/bin/env python3
"""Fold the MULTICHIP round artifacts into ONE committed scaling artifact
(ISSUE 7 satellite; ROADMAP "publish the scaling curve" item).

Each driver round leaves a ``MULTICHIP_r0N.json`` wrapper whose ``tail``
holds the harness' incremental JSON lines (``__graft_entry__._dryrun_impl``:
one line per completed phase, the last parsable line wins — the bench.py
contract).  This tool parses every round, extracts the per-phase wall
seconds / residuals / sched metrics, attaches the documented flop models
to estimate GF/s per phase, and writes a single RunReport-schema JSON
(``artifacts/obs/scaling.report.json``) so the scaling trajectory is a
first-class, diffable artifact: ``python -m slate_tpu.obs.report`` prints
it, ``--check`` gates a new sweep against it.

Rounds whose tail is empty or unparsable (e.g. the r01 libtpu-mismatch
crash, the r02-r05 empty tails) are recorded under ``config.missing``
with their rc — absence of data is part of the trajectory, not silently
dropped.

Single-chip ``BENCH_r0N.json`` partials fold in too (ISSUE 17) where
present: a fully-parsed headline + extras, or the ``[bench ...s] extra:
k = v`` progress lines recovered from a killed run's tail — a killed
run's completed phases are data, not garbage.  No such record is in the
repo now; a glob that matches nothing yields an empty curve and exit 0.  The report's config is stamped
with the emitting trace_id (the RunReport-meta convention the obs.live
ledger uses), so this artifact is joinable against traces and ledger
entries.

Usage::

    python tools/scaling_report.py [--out artifacts/obs/scaling.report.json]
        [--glob 'MULTICHIP_r*.json'] [--bench-glob 'BENCH_r*.json']
        [--partial multichip_partial.json]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# harness problem sizes (__graft_entry__._dryrun_impl)
N, NRHS, STEDC_N = 64, 16, 96

# documented flop models per harness phase (None = no meaningful GF/s)
PHASE_FLOPS = {
    # potrf + 2 trsm + SUMMA residual gemm
    "posv_chain": N**3 / 3 + 2 * N * N * NRHS + 2 * N**3,
    "gesv_pp": 2 * N**3 / 3 + 2 * N * N * NRHS,
    "hemm_summa": 2 * N * N * NRHS,
    "stedc_dist": None,
    "heev_chain": 4 * N**3 / 3,
    "flight_timeline": None,
}


def parse_round(path: str):
    """(round_tag, phases_dict | None, rc): phases from the tail's last
    parsable JSON line carrying a ``phases`` key."""
    tag = re.sub(r"\.json$", "", os.path.basename(path))
    with open(path) as f:
        doc = json.load(f)
    rc = doc.get("rc")
    if isinstance(doc.get("phases"), dict):  # a bare harness line (partial)
        return tag, doc["phases"], rc
    tail = doc.get("tail") or ""
    for line in reversed(tail.splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            inner = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(inner, dict) and isinstance(inner.get("phases"), dict):
            return tag, inner["phases"], rc
    return tag, None, rc


# a completed incremental metric in a bench run's progress log:
# "[bench  653.7s] extra: potrf_f64_gflops_n8192 = 700.8"
_BENCH_EXTRA_RE = re.compile(
    r"\[bench\s+[\d.]+s\]\s+extra:\s+(\w+)\s*=\s*([-+\d.eE]+)")


def parse_bench_round(path: str):
    """(round_tag, values_dict, rc, recovered): the headline + extras of
    a parsed BENCH wrapper, or — when the run died before the headline
    (r05's rc=124 timeout) — every completed ``extra: k = v`` progress
    line recovered from the tail."""
    tag = re.sub(r"\.json$", "", os.path.basename(path))
    with open(path) as f:
        doc = json.load(f)
    rc = doc.get("rc")
    vals = {}
    parsed = doc.get("parsed")
    if isinstance(parsed, dict):
        metric = parsed.get("metric")
        if metric and isinstance(parsed.get("value"), (int, float)):
            vals[str(metric)] = float(parsed["value"])
        for k, v in (parsed.get("extras") or {}).items():
            if isinstance(v, (int, float)):
                vals[str(k)] = float(v)
        return tag, vals, rc, False
    for m in _BENCH_EXTRA_RE.finditer(doc.get("tail") or ""):
        try:
            vals[m.group(1)] = float(m.group(2))
        except ValueError:
            continue
    return tag, vals, rc, bool(vals)


def _rows_for(tag, phases):
    rows = []
    for name, vals in phases.items():
        if not isinstance(vals, dict):
            continue
        row = {"round": tag, "phase": name}
        if "skipped" in vals or "error" in vals:
            row["status"] = vals.get("skipped") or vals.get("error")
            rows.append(row)
            continue
        secs = vals.get("seconds")
        row["seconds"] = secs
        flops = PHASE_FLOPS.get(name)
        if flops and isinstance(secs, (int, float)) and secs > 0:
            row["gflops"] = flops / secs / 1e9
        for k, v in vals.items():
            if k != "seconds" and isinstance(v, (int, float)):
                row[k] = v
        rows.append(row)
    return rows


def build(paths, partial=None, bench_paths=()) -> dict:
    rows, missing = [], []
    for path in paths:
        tag, phases, rc = parse_round(path)
        if phases is None:
            missing.append({"round": tag, "rc": rc})
            continue
        rows.extend(_rows_for(tag, phases))
    if partial and os.path.exists(partial):
        tag, phases, _ = parse_round(partial)
        if phases is not None:
            rows.extend(_rows_for("partial", phases))

    values = {}
    for row in rows:
        key = f"{row['phase']}_{row['round'].lower()}"
        if isinstance(row.get("seconds"), (int, float)):
            values[f"{key}_seconds"] = float(row["seconds"])
        if isinstance(row.get("gflops"), (int, float)):
            values[f"{key}_gflops"] = float(row["gflops"])

    # single-chip bench partials: headline + extras per round, recovered
    # progress lines for rounds that died mid-run
    bench_rounds = []
    for path in bench_paths:
        tag, bvals, rc, recovered = parse_bench_round(path)
        if not bvals:
            missing.append({"round": tag, "rc": rc})
            continue
        bench_rounds.append({"round": tag, "rc": rc,
                             "recovered_from_tail": recovered,
                             "n_metrics": len(bvals)})
        low = tag.lower()
        for k, v in bvals.items():
            values[f"{low}_{k}"] = v

    from slate_tpu.obs.context import current as _ctx_current, new_trace_id
    from slate_tpu.obs.report import SCHEMA, VERSION, _env_info

    import time

    ctx = _ctx_current()
    return {
        "schema": SCHEMA,
        "version": VERSION,
        "name": "multichip_scaling",
        "created_unix": time.time(),
        "env": _env_info(),
        "config": {
            "n": N, "nrhs": NRHS, "harness": "__graft_entry__.dryrun_multichip",
            "rounds": sorted({r["round"] for r in rows}),
            "bench_rounds": bench_rounds,
            "missing": missing,
            # the emitting trace_id (RunReport-meta convention, ISSUE
            # 17): joinable against the obs.live ledger and traces
            "trace_id": ctx.trace_id if ctx is not None else new_trace_id(),
        },
        "values": values,
        # the curve proper: phase x n_devices x GF/s (every harness round
        # so far runs the 8-device virtual mesh; real-pod rounds will add
        # more n_devices points to the same artifact)
        "curve": rows,
        "metrics": {"counters": [], "gauges": [], "histograms": []},
        "spans": [],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python tools/scaling_report.py",
                                 description=__doc__)
    ap.add_argument("--out",
                    default=os.path.join(REPO, "artifacts", "obs",
                                         "scaling.report.json"))
    ap.add_argument("--glob", default=os.path.join(REPO, "MULTICHIP_r*.json"))
    ap.add_argument("--bench-glob", default=os.path.join(REPO,
                                                         "BENCH_r*.json"))
    ap.add_argument("--partial",
                    default=os.path.join(REPO, "multichip_partial.json"))
    args = ap.parse_args(argv)

    paths = sorted(glob.glob(args.glob))
    if not paths:
        print(f"scaling_report: no artifacts match {args.glob}; "
              "writing an empty curve")
    bench_paths = sorted(glob.glob(args.bench_glob)) if args.bench_glob else []
    rep = build(paths, args.partial, bench_paths)

    from slate_tpu.obs.report import validate_report

    errs = validate_report(rep)
    if errs:
        print(f"scaling_report: built report fails schema: {errs}")
        return 1
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rep, f, indent=1)
    n_rows = len(rep["curve"])
    n_missing = len(rep["config"]["missing"])
    n_bench = len(rep["config"]["bench_rounds"])
    print(f"scaling_report: {len(paths)} round artifact(s) -> {n_rows} "
          f"phase row(s), {n_bench} bench round(s) folded, "
          f"{n_missing} round(s) without data; wrote {args.out}")
    for br in rep["config"]["bench_rounds"]:
        how = ("recovered from rc=%s tail" % br["rc"]
               if br["recovered_from_tail"] else "parsed headline")
        print(f"  {br['round']}: {br['n_metrics']} metric(s), {how}")
    for row in rep["curve"]:
        bits = [f"{row['phase']:<16} {row['round']}"]
        if "seconds" in row:
            bits.append(f"{row['seconds']:.3f}s")
        if "gflops" in row:
            bits.append(f"{row['gflops']:.3f} GF/s")
        if "status" in row:
            bits.append(f"[{row['status']}]")
        print("  " + "  ".join(bits))
    return 0


if __name__ == "__main__":
    sys.exit(main())
