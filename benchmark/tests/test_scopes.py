"""The scope reduction: op scopes from the XSpace's event metadata, phase
and stage attribution, gaps named by the innermost span, and the readers
of the scope metrics."""

import os
import types

import pytest

from benchmark import flops, harness, run_scoped, scopes, tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return ProfileData.from_serialized_xspace(raw), raw


@pytest.fixture(scope="module")
def scoped():
    profile, raw = _load("small_trace_scoped.pbtxt")
    return scopes.summarize(*scopes.read(profile, raw))


def test_op_scopes_read_string_and_reference_stats():
    _, raw = _load("small_trace_scoped.pbtxt")
    got = scopes.op_scopes(raw)
    assert got["%fusion.1 = f32[8]{0} fusion(%p0), kind=kLoop"] == \
        "jit(solve)/getrf/while/body/panel/dot_general"
    assert got["%fusion.4 = f32[8]{0} fusion(%p3)"] == \
        "jit(solve)/getrf/while/body/bulk/dot_general"  # a ref_value stat
    assert "%copy.6 = f32[8]{0} copy(%p4)" not in got  # XLA's own op: no scope
    assert len(got) == 6


def test_read_keeps_program_spans_and_op_scopes():
    profile, raw = _load("small_trace_scoped.pbtxt")
    devices, spans = scopes.read(profile, raw)
    assert ("gather.5", 1050, 1060, "XLA Ops", "jit(solve)/getrf/while/body/swap/gather") \
        in devices["/device:TPU:0"]
    assert sorted(n for n, _, _ in spans) == [
        "call", "make_inputs", "slate_tpu/serve.admit", "slate_tpu/serve.dispatch",
        "slate_tpu/serve.info", "slate_tpu/serve.solve_batch", "to_host", "window"]


def test_phase_and_stage_attribution(scoped):
    ns = 1e-9
    assert scoped["chips"] == 2 and scoped["window_s"] == pytest.approx(200 * ns)
    # chip 0: loop self 40 (no phase), panel 20, bcast 10 (innermost under
    # panel), swap 10, bulk 20, unscoped copy 10; chip 1: bulk 40, copy 10
    assert scoped["phase_s"] == pytest.approx(
        {"panel": 10 * ns, "bcast": 5 * ns, "swap": 5 * ns, "bulk": 30 * ns,
         "unscoped": 30 * ns})
    assert scoped["stage_s"] == pytest.approx({"getrf": 70 * ns, "unscoped": 10 * ns})
    assert sum(scoped["phase_s"].values()) == pytest.approx(sum(scoped["stage_s"].values()))
    ops = dict(scoped["device_ops"])
    assert ops == pytest.approx({
        "getrf/-:while.7": 20 * ns, "getrf/panel:fusion.1": 10 * ns,
        "getrf/bcast:all-reduce.3": 5 * ns, "getrf/swap:gather.5": 5 * ns,
        "getrf/bulk:fusion.4": 10 * ns, "getrf/bulk:fusion.9": 20 * ns,
        "-:copy.6": 10 * ns})
    assert dict(scoped["unscoped_ops"]) == pytest.approx({"copy.6": 10 * ns})


def test_gaps_are_named_by_the_innermost_span(scoped):
    ns = 1e-9
    # chip 0 idles 1100..1150 and chip 1 1050..1200: both midpoints (1125)
    # lie in serve.info, inside serve.solve_batch, inside call
    assert dict(scoped["idle_gaps"]) == pytest.approx(
        {"slate_tpu/serve.info": 100 * ns, "make_inputs": 20 * ns})
    assert scoped["serve_idle_s"] == pytest.approx(100 * ns)


def test_tracereduce_keeps_its_definitions_on_the_scoped_trace():
    profile, _ = _load("small_trace_scoped.pbtxt")
    s = tracereduce.summarize(*tracereduce.read_profile(profile))
    assert dict(s["idle_gaps"]) == pytest.approx({"call": 100e-9, "make_inputs": 20e-9})
    assert s["busy_s"] == pytest.approx((110 + 50) / 2 * 1e-9)
    assert dict(s["device_ops"])["fusion.9"] == pytest.approx(20e-9)


def test_a_trace_without_scopes_reads_all_unscoped():
    profile, raw = _load("small_trace.pbtxt")
    s = scopes.summarize(*scopes.read(profile, raw))
    old = tracereduce.summarize(*tracereduce.read_profile(profile))
    assert set(s["phase_s"]) == set(s["stage_s"]) == {"unscoped"}
    assert s["phase_s"]["unscoped"] == pytest.approx(sum(t for _, t in old["device_ops"]))
    assert all(name.startswith("-:") for name, _ in s["device_ops"])
    assert dict(s["idle_gaps"]) == pytest.approx(dict(old["idle_gaps"]))
    assert s["serve_idle_s"] == 0.0


@pytest.mark.parametrize("path,stage,phase,label", [
    ("jit(f)/potrf/while/body/panel/bcast/psum", "potrf", "bcast", "potrf/bcast:op"),
    ("jit(f)/redistribute/transpose", "redistribute", "", "redistribute/-:op"),
    ("jit(f)/while/body/bulk/dot_general", "", "bulk", "-/bulk:op"),
    ("jit(f)/while/body/closed_call/mul", "", "", "-:op"),
    ("", "", "", "-:op"),
])
def test_innermost_and_labels(path, stage, phase, label):
    assert scopes.innermost(path, scopes.STAGES) == stage
    assert scopes.innermost(path, scopes.PHASES) == phase
    assert scopes.op_label(path, "op") == label


def test_gap_names_pop_closed_spans():
    spans = [("a", 0, 100), ("b", 10, 20), ("c", 30, 40), ("d", 200, 300)]
    gaps = [(12, 14), (25, 27), (34, 36), (150, 160), (250, 260), (400, 410)]
    assert scopes.gap_names(gaps, spans) == ["b", "a", "c", "none", "d", "none"]


# -- the readers ------------------------------------------------------------------


def _view(scoped_summary, op="gesv", calls=3, chips=2):
    cfg = harness.load_json(f"{harness.BENCH_DIR}/configs/"
                            f"{'hpl-f32' if op == 'gesv' else 'spd-f32'}.json")
    traffic = {"path": "mesh" if op == "gesv" else "api", "n": 64, "nrhs": 1,
               "nb": 8, "grid": [1, 2]}
    cell = harness.make_cell("t", chips, cfg, traffic, {})
    ok = [types.SimpleNamespace(ok=True)] * calls + [types.SimpleNamespace(ok=False)]
    trace = None if scoped_summary is None else {"scopes": scoped_summary}
    return types.SimpleNamespace(cell=cell, chips=chips, calls=ok, trace=trace,
                                 peaks=harness.peaks_for("TPU v5 lite"))


def test_share_readers(scoped):
    view = _view(scoped)
    read = {m: harness.metric_reader(m).read(view)
            for m in ("panel_pct", "swap_pct", "router_idle_pct.batch")}
    assert read == pytest.approx({"panel_pct": 5.0, "swap_pct": 2.5,
                                  "router_idle_pct.batch": 50.0})


@pytest.mark.parametrize("op,factor", [("gesv", flops.getrf), ("posv", flops.potrf)])
def test_bulk_roofline_reader(scoped, op, factor):
    view = _view(scoped, op=op)
    least = flops.least_seconds(factor(64), 2 * 64 * 64 * 4, 197e12, 819e9, 2)
    got = harness.metric_reader("bulk_roofline").read(view)
    assert got == pytest.approx(100 * 3 * least / 30e-9)  # three calls served


def test_readers_read_nothing_without_scopes(scoped):
    empty = dict(scoped, phase_s={"unscoped": 1e-9}, serve_idle_s=0.0)
    for summary in (None, empty):
        view = _view(summary)
        for m in ("panel_pct", "swap_pct", "bulk_roofline", "router_idle_pct"):
            assert harness.metric_reader(m).read(view) is None


def test_scope_metrics_name_cells_and_readers():
    spec = harness.load_spec()
    cells = {w["name"] for w in spec["workloads"]}
    assert set(run_scoped.SCOPE_METRICS) <= cells
    for names in run_scoped.SCOPE_METRICS.values():
        for name in names:
            assert callable(harness.metric_reader(name).read)
