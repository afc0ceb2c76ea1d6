"""Observability layer coverage (ISSUE 2): span nesting + tag
propagation, comm-counter accumulation under jit trace-once semantics,
Perfetto JSON schema validation, RunReport schema + ``--check``
pass/fail paths, the Trace.finish JSON fallback, and the measure()
wall/compile/execute split."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu import obs
from slate_tpu.obs import perfetto, report
from slate_tpu.parallel.comm import comm_audit, psum_a


@pytest.fixture
def fresh_obs():
    obs.reset()
    with obs.force_enabled():
        yield
    obs.reset()


def _mesh_and_spd(n=64, nb=8):
    from slate_tpu.parallel import from_dense, make_mesh

    mesh = make_mesh(2, 4, devices=jax.devices("cpu")[:8])
    rng = np.random.default_rng(0)
    g = rng.standard_normal((n, n))
    spd = jnp.asarray((g @ g.T / n + 2 * np.eye(n)).astype(np.float32))
    return mesh, from_dense(spd, mesh, nb, diag_pad_one=True)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_disabled_span_is_noop():
    obs.reset()
    assert not obs.enabled()
    before = len(obs.FINISHED)
    with obs.driver_span("nothing", n=4) as sp:
        sp.set("x", 1.0)  # must not touch the registry
    assert len(obs.FINISHED) == before
    assert obs.REGISTRY.counter_value("span_count", span="nothing") == 0.0


def test_span_nesting_and_tag_propagation(fresh_obs):
    with obs.driver_span("outer", n=32) as so:
        with obs.driver_span("inner", phase="x"):
            pass
    names = {s["name"]: s for s in obs.FINISHED}
    assert names["inner"]["parent"] == "outer"
    assert names["inner"]["depth"] == 1
    assert names["outer"]["parent"] is None
    assert names["outer"]["tags"] == {"n": "32"}
    assert names["inner"]["tags"] == {"phase": "x"}
    assert names["outer"]["metrics"]["wall_seconds"] >= \
        names["inner"]["metrics"]["wall_seconds"]
    assert so.metrics["wall_seconds"] > 0


def test_instrumented_driver_records_span_and_comm_bytes(fresh_obs):
    from slate_tpu.parallel import potrf_dist

    _, ad = _mesh_and_spd()
    jax.clear_caches()
    _, info = potrf_dist(ad)
    assert int(info) == 0
    spans = [s for s in obs.FINISHED if s["name"] == "potrf_dist"]
    assert len(spans) == 1
    # instrument() tags the span with the DistMatrix geometry
    assert spans[0]["tags"] == {"m": "64", "n": "64", "nb": "8"}
    assert spans[0]["metrics"]["comm_bytes"] > 0


def test_comm_counter_trace_once_semantics(fresh_obs):
    """The comm-byte counters record at jit trace time only: a warm call
    (cache hit) must add nothing — the documented comm_audit contract,
    now holding through the span absorption layer too.  The lowering is
    pinned to the legacy psum path so the per-op counter name under test
    is impl-independent (the engine default records ppermute ops)."""
    from slate_tpu.parallel import potrf_dist
    from slate_tpu.parallel.comm import use_bcast_impl

    _, ad = _mesh_and_spd()
    jax.clear_caches()
    with use_bcast_impl("psum"):
        potrf_dist(ad)
        first = obs.REGISTRY.counter_value(
            "comm_bytes", span="potrf_dist", op="psum")
        assert first > 0
        potrf_dist(ad)  # warm: no re-trace, no new bytes
        assert obs.REGISTRY.counter_value(
            "comm_bytes", span="potrf_dist", op="psum") == first
    warm = [s for s in obs.FINISHED if s["name"] == "potrf_dist"][-1]
    assert warm["metrics"]["comm_bytes"] == 0.0
    # span_count keeps counting executions even when bytes don't re-record
    assert obs.REGISTRY.counter_value("span_count", span="potrf_dist") == 2.0


def test_span_propagates_records_to_outer_audit(fresh_obs):
    """A span inside comm_audit() must observe without stealing: the
    outer audit (slate_lint's trace pass, tools/comm_audit.py) still sees
    every record."""
    fn = jax.vmap(lambda x: psum_a(x, "i"), axis_name="i")
    with comm_audit() as outer:
        with obs.driver_span("probe"):
            jax.make_jaxpr(fn)(jnp.zeros((4, 8)))
    assert len(outer) == 1
    assert outer[0][0] == "psum[i]"
    probe = [s for s in obs.FINISHED if s["name"] == "probe"][0]
    assert probe["metrics"]["comm_bytes"] == outer[0][1]


# ---------------------------------------------------------------------------
# measure(): wall/compile/execute phases + cost analysis
# ---------------------------------------------------------------------------


def test_measure_splits_phases_and_pulls_cost():
    obs.reset()
    a = jnp.ones((64, 64), jnp.float32)
    out, m = obs.measure("toy_mm", jax.jit(lambda x: x @ x), a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ a))
    for key in ("wall_seconds", "compile_seconds", "execute_seconds",
                "comm_bytes"):
        assert key in m, key
    # one AOT lower+compile, one execution — wall covers both phases
    assert m["wall_seconds"] >= m["compile_seconds"] + m["execute_seconds"]
    # XLA's cost model knows a 64^3 matmul
    if "flops" in m:
        assert m["flops"] >= 2 * 64**3 * 0.5
    obs.reset()


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_perfetto_export_schema_and_nesting(fresh_obs, tmp_path):
    with obs.driver_span("parent_op", n=16):
        with obs.driver_span("child_op"):
            pass
    path = perfetto.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        tr = json.load(f)
    assert perfetto.validate_chrome_trace(tr) == []
    evs = {e["name"]: e for e in tr["traceEvents"]}
    assert evs["child_op"]["args"]["parent"] == "parent_op"
    assert evs["parent_op"]["args"]["n"] == "16"
    for e in (evs["parent_op"], evs["child_op"]):
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0


def test_perfetto_validator_catches_garbage():
    assert perfetto.validate_chrome_trace([]) != []
    assert perfetto.validate_chrome_trace({"traceEvents": "nope"}) != []
    bad = {"traceEvents": [{"name": "", "ph": "X", "ts": -1}]}
    errs = perfetto.validate_chrome_trace(bad)
    assert any("name" in e for e in errs) and any("ts" in e for e in errs)


# ---------------------------------------------------------------------------
# RunReport schema + --check
# ---------------------------------------------------------------------------


def test_report_roundtrip_validates(fresh_obs, tmp_path):
    with obs.driver_span("r_op"):
        pass
    path = report.write_report(str(tmp_path / "r.json"), name="unit",
                               config={"n": 8},
                               values={"x_gflops": 100.0, "t_seconds": 1.0})
    with open(path) as f:
        rep = json.load(f)
    assert report.validate_report(rep) == []
    assert rep["values"]["x_gflops"] == 100.0
    assert any(s["name"] == "r_op" for s in rep["spans"])
    # corruption is caught
    del rep["values"]
    assert report.validate_report(rep) != []
    assert report.validate_report("not a dict") != []


def test_check_flags_2x_regression_and_passes_unchanged():
    base = {"x_gflops": 100.0, "t_seconds": 1.0}
    # unchanged: clean
    fails, n = report.check_regression(dict(base), dict(base))
    assert fails == [] and n == 2
    # 2x worse in each direction: both flagged
    fails, _ = report.check_regression(
        {"x_gflops": 50.0, "t_seconds": 2.0}, base)
    assert len(fails) == 2
    # 2x BETTER in each direction: never flagged
    fails, _ = report.check_regression(
        {"x_gflops": 200.0, "t_seconds": 0.5}, base)
    assert fails == []
    # within threshold: clean
    fails, _ = report.check_regression(
        {"x_gflops": 80.0, "t_seconds": 1.2}, base)
    assert fails == []


def test_report_cli_check_exit_codes(tmp_path):
    old = str(tmp_path / "old.json")
    new_ok = str(tmp_path / "new_ok.json")
    new_bad = str(tmp_path / "new_bad.json")
    obs.reset()
    report.write_report(old, name="cli", values={"x_gflops": 100.0})
    report.write_report(new_ok, name="cli", values={"x_gflops": 95.0})
    report.write_report(new_bad, name="cli", values={"x_gflops": 40.0})
    assert report.main(["--check", new_ok, old]) == 0
    assert report.main(["--check", new_bad, old]) == 1
    assert report.main([old]) == 0  # pretty-print path
    # no shared metrics -> inconclusive exit 2
    other = str(tmp_path / "other.json")
    report.write_report(other, name="cli", values={"y_gflops": 1.0})
    assert report.main(["--check", other, old]) == 2


def test_report_reads_legacy_bench_and_sweep_shapes():
    bench_line = {"metric": "dgemm_gflops", "value": 4700.0, "unit": "GFLOP/s",
                  "extras": {"gemm_bf16_gflops": 100000.0, "note": "text"}}
    vals = report.load_values(bench_line)
    assert vals == {"dgemm_gflops": 4700.0, "gemm_bf16_gflops": 100000.0}
    sweep = {"results": [
        {"routine": "potrf_f64", "n": 16384, "gflops": 1234.0, "ok": True},
        {"routine": "heev", "n": 8192, "gflops": 99.0, "ok": False},
    ]}
    assert report.load_values(sweep) == {"potrf_f64_n16384_gflops": 1234.0}
    with pytest.raises(ValueError):
        report.load_values({"mystery": 1})


def test_report_unwraps_driver_bench_artifact():
    """The repo's real BENCH_*.json files are driver wrappers holding the
    bench stdout in "tail"; --check must gate against them directly."""
    wrapper = {"n": 4, "cmd": "python bench.py", "rc": 0,
               "tail": "noise\n[bench 1s] progress\n"
                       '{"metric": "dgemm_gflops", "value": 5196.0, '
                       '"extras": {"gemm_bf16_gflops": 150000.0}}\n'}
    vals = report.load_values(wrapper)
    assert vals == {"dgemm_gflops": 5196.0, "gemm_bf16_gflops": 150000.0}
    with pytest.raises(ValueError):  # timed-out run: no metric line
        report.load_values({"rc": 124, "tail": "killed before the line"})


def test_check_skips_tagged_flops_series_and_generator_spans(tmp_path):
    """Review regressions: (1) the _NEUTRAL exclusion must match the
    metric-name side of a flattened 'flops|span=...' series, so a dropped
    XLA flop estimate (an optimization) never fails --check; (2) the
    perfetto exporter must accept a generator of spans without silently
    emitting an empty trace."""
    fails, _ = report.check_regression(
        {"flops|span=dist_chol": 1e6, "x_gflops": 100.0},
        {"flops|span=dist_chol": 2.5e6, "x_gflops": 100.0},
    )
    assert fails == []
    spans = ({"name": f"s{i}", "tags": {}, "t0": float(i), "t1": i + 0.5,
              "depth": 0, "parent": None, "metrics": {}} for i in range(3))
    tr = perfetto.chrome_trace(spans=spans)
    assert perfetto.validate_chrome_trace(tr) == []
    assert {e["name"] for e in tr["traceEvents"]} >= {"s0", "s1", "s2"}


def test_check_defaults_to_headline_values_only(tmp_path):
    """--check gates the workload-keyed headline values by default; the
    run-scaled counter/histogram series join only with --all-metrics."""
    obs.reset()
    old = str(tmp_path / "old.json")
    new = str(tmp_path / "new.json")
    with obs.force_enabled():
        with obs.driver_span("short_op"):
            pass
    report.write_report(old, name="cfg", config={"dim": "256"},
                        values={"x_gflops": 100.0})
    obs.reset()
    with obs.force_enabled():  # a 4x-bigger sweep: 4 spans, same rate
        for _ in range(4):
            with obs.driver_span("short_op"):
                pass
    report.write_report(new, name="cfg", config={"dim": "256:1024:256"},
                        values={"x_gflops": 100.0})
    # default: the 4x-scaled span series do not even enter the gate.
    # The mem section (ISSUE 9) samples at enabled span exits and joins
    # the headline surface like ft/ir; everything ELSE stays out.
    assert report.main(["--check", new, old]) == 0
    vals_default = report.load_values(json.load(open(new)))
    assert {k for k in vals_default if not k.startswith("mem_")} \
        == {"x_gflops"}
    # opt-in exposes the run-scaled series (same-config pairs only)
    vals_all = report.load_values(json.load(open(new)), include_series=True)
    assert vals_all["span_count|span=short_op"] == 4.0
    assert set(vals_all) > set(vals_default)
    obs.reset()


def test_check_cli_inconclusive_on_unreadable_artifacts(tmp_path):
    """--check must exit 2 (inconclusive), not 1 (regression), on corrupt
    or timed-out prior artifacts — exit 1 is reserved for real
    regressions."""
    obs.reset()
    good = str(tmp_path / "good.json")
    report.write_report(good, name="cli", values={"x_gflops": 100.0})
    timed_out = str(tmp_path / "bench_timeout.json")
    with open(timed_out, "w") as f:
        json.dump({"rc": 124, "tail": "killed before the metric line"}, f)
    assert report.main(["--check", good, timed_out]) == 2
    garbage = str(tmp_path / "garbage.json")
    with open(garbage, "w") as f:
        f.write("{not json")
    assert report.main(["--check", good, garbage]) == 2
    assert report.main(["--check", good, str(tmp_path / "missing.json")]) == 2


def test_check_regression_flags_zero_collapse():
    # round-4 (ft PR) review finding: a higher-is-better metric hitting
    # exactly zero must gate as a regression, not skip as an undefined
    # ratio (ft_detected 5 -> 0 = detection coverage silently lost)
    from slate_tpu.obs.report import check_regression

    fails, n = check_regression(
        {"x_gflops": 0.0, "ft_detected": 0.0},
        {"x_gflops": 5.0, "ft_detected": 5.0},
    )
    assert n == 2 and len(fails) == 2
    # lower-is-better hitting zero is an improvement, not a failure
    fails, n = check_regression({"wall_seconds": 0.0}, {"wall_seconds": 5.0})
    assert fails == []


def test_check_mixed_schema_sections_inconclusive(tmp_path, capsys):
    """ISSUE 7 satellite: when the NEW report carries a metrics section
    the OLD artifact predates (sched.* from a flight report, ft_*
    against a pre-ft report), --check reports those keys as
    per-key INCONCLUSIVE instead of failing the whole check — the shared
    metrics still gate normally."""
    # unit surface: the section filter
    assert report.inconclusive_keys(
        {"wall_seconds": 1.0, "sched.overlap_eff": 0.5, "ft_detected": 2.0,
         "new_gflops": 9.0},
        {"wall_seconds": 1.0},
    ) == ["ft_detected", "sched.overlap_eff"]  # new_gflops: not a section
    # shared key present in both: never inconclusive
    assert report.inconclusive_keys(
        {"sched.overlap_eff": 0.5}, {"sched.overlap_eff": 0.4}) == []

    # CLI surface: mixed-schema pair passes (rc 0) with INCONCLUSIVE lines
    old = str(tmp_path / "old.json")
    new = str(tmp_path / "new.json")
    obs.reset()
    report.write_report(old, name="mixed", values={"x_gflops": 100.0})
    report.write_report(new, name="mixed",
                        values={"x_gflops": 101.0,
                                "sched.overlap_eff": 0.6,
                                "sched.critical_path_s": 0.02})
    assert report.main(["--check", new, old]) == 0
    out = capsys.readouterr().out
    assert out.count("INCONCLUSIVE") == 2
    assert "sched.overlap_eff" in out and "sched.critical_path_s" in out
    obs.reset()


def test_histogram_quantiles_exact_reservoir_and_snapshot():
    """ISSUE 14 satellite: first-class histogram quantiles — exact
    (interpolated over every observation) below the reservoir cap with
    running-stats clamping, a deterministic reservoir estimate beyond
    it, and p50/p95/p99 surfaced in snapshots."""
    from slate_tpu.obs.metrics import (
        _HIST_SAMPLE_CAP,
        MetricsRegistry,
        quantile_of,
    )

    reg = MetricsRegistry()
    # tiny counts: 1 observation returns it, 2 interpolate exactly
    reg.observe("lat", 3.0, op="tiny")
    assert reg.quantile("lat", 0.0, op="tiny") == 3.0
    assert reg.quantile("lat", 0.99, op="tiny") == 3.0
    reg.observe("lat", 5.0, op="tiny")
    assert reg.quantile("lat", 0.5, op="tiny") == 4.0
    # exact tier: 1..10 -> interpolated median 5.5, extremes exact
    for v in range(1, 11):
        reg.observe("lat", float(v), op="x")
    assert reg.quantile("lat", 0.5, op="x") == 5.5
    assert reg.quantile("lat", 0.0, op="x") == 1.0
    assert reg.quantile("lat", 1.0, op="x") == 10.0
    # an unobserved series has no quantiles
    assert reg.quantile("lat", 0.5, op="nope") is None
    with pytest.raises(ValueError):
        quantile_of([1.0], 1.5)
    # beyond the cap: reservoir estimate stays within the exact running
    # extrema, monotone across q, with deterministic samples
    nbig = 4 * _HIST_SAMPLE_CAP
    for v in range(nbig):
        reg.observe("lat", float(v), op="big")
    p50 = reg.quantile("lat", 0.5, op="big")
    p95 = reg.quantile("lat", 0.95, op="big")
    p99 = reg.quantile("lat", 0.99, op="big")
    assert 0.0 <= p50 <= p95 <= p99 <= nbig - 1
    assert abs(p50 - nbig / 2) < nbig * 0.15  # loose reservoir sanity
    reg2 = MetricsRegistry()
    for v in range(nbig):
        reg2.observe("lat", float(v), op="big")
    assert reg2.quantile("lat", 0.99, op="big") == p99  # deterministic
    # snapshot carries the quantile surface per series
    hsnap = {(e["name"], str(sorted(e["tags"].items()))): e
             for e in reg.snapshot()["histograms"]}
    entry = hsnap[("lat", str(sorted({"op": "x"}.items())))]
    assert entry["count"] == 10 and entry["p50"] == 5.5
    assert entry["p99"] <= entry["max"] == 10.0
