"""Program spans and named device scopes with the observability layer off.

Driver spans and the Router's phase spans reach a ``jax.profiler`` trace
as ``slate_tpu/<name>`` host events without ``obs.enable()``, and record
nothing else; ``comm.phase_scope`` and the stage scopes reach the
compiled program's op metadata without changing the jaxpr.
"""

import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from slate_tpu import obs
from slate_tpu.obs import numerics
from slate_tpu.parallel import make_mesh
from slate_tpu.parallel.comm import phase_scope

from conftest import cpu_devices

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = obs.PROFILER_PREFIX


@pytest.fixture
def obs_off():
    with obs.force_enabled(False):
        obs.reset()
        yield
        obs.reset()


def _host_spans(fn, tmp_path):
    """Run ``fn`` under a jax.profiler trace; the program's host spans as
    (name, start_ns, end_ns), sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
                if f.endswith(".xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns)
             for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events if e.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def _scope_parts(compiled_text):
    parts = set()
    for name in re.findall(r'op_name="([^"]*)"', compiled_text):
        parts |= set(name.split("/"))
    return parts


# -- host spans with obs off ---------------------------------------------------


def test_router_spans_reach_the_profiler_with_obs_off(obs_off, tmp_path, rng):
    from slate_tpu.serve import Router

    n = 16
    router = Router(bins=(16,), hbm_budget=1 << 30)
    a = [jnp.asarray(rng.standard_normal((n, n)) / n + 2 * np.eye(n)) for _ in range(2)]
    b = [jnp.asarray(rng.standard_normal((n, 2))) for _ in range(2)]
    reqs = [("posv", ai @ ai.T, bi) for ai, bi in zip(a, b)]
    router.solve_batch(reqs)  # compile outside the trace
    spans = _host_spans(lambda: jax.block_until_ready(router.solve_batch(reqs)), tmp_path)
    names = [s[0][len(PREFIX):] for s in spans]
    assert names == ["serve.solve_batch", "serve.admit", "serve.stack", "serve.lookup",
                     "serve.dispatch", "serve.info", "serve.unstack"]
    outer = spans[0]
    assert all(outer[1] <= s <= e <= outer[2] for _, s, e in spans[1:])
    assert all(spans[i][2] <= spans[i + 1][1] for i in range(1, len(spans) - 1))
    assert obs.FINISHED == [] and obs.REGISTRY.snapshot() == obs.MetricsRegistry().snapshot()


def test_driver_spans_reach_the_profiler_with_obs_off(obs_off, tmp_path, rng):
    from slate_tpu.parallel import gesv_mesh

    mesh = make_mesh(2, 2, devices=cpu_devices(4))
    n, nb = 64, 16
    a = jnp.asarray(rng.standard_normal((n, n)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n, 2)), jnp.float32)
    jax.block_until_ready(gesv_mesh(a, b, mesh, nb=nb))
    spans = _host_spans(lambda: jax.block_until_ready(gesv_mesh(a, b, mesh, nb=nb)), tmp_path)
    names = [s[0][len(PREFIX):] for s in spans]
    assert names[0] == "gesv_mesh"
    assert {"getrf_mesh", "getrf_pp_dist", "permute_rows_dist", "trsm_dist"} <= set(names)
    assert names.count("trsm_dist") == 2
    assert obs.FINISHED == [] and obs.REGISTRY.snapshot() == obs.MetricsRegistry().snapshot()


def test_an_obs_off_span_is_the_null_span(obs_off, tmp_path):
    def body():
        with obs.driver_span("probe_off", n=3) as sp:
            assert sp is obs.span._NULL
            sp.set("x", 1.0)

    spans = _host_spans(body, tmp_path)
    assert [s[0] for s in spans] == [PREFIX + "probe_off"]
    assert obs.FINISHED == [] and obs.REGISTRY.snapshot() == obs.MetricsRegistry().snapshot()


def test_an_obs_on_span_keeps_its_bare_name(tmp_path):
    def body():
        with obs.driver_span("probe_on"):
            pass

    with obs.force_enabled(True):
        obs.reset()
        spans = _host_spans(body, tmp_path)
        assert [s[0] for s in spans] == [PREFIX + "probe_on"]
        assert [s["name"] for s in obs.FINISHED] == ["probe_on"]
        assert obs.REGISTRY.counter_value("span_count", span="probe_on") == 1.0
        obs.reset()


def test_instrumented_driver_annotates_with_obs_off(obs_off, tmp_path):
    @obs.instrument("probe_driver")
    def driver(x):
        return x + 1

    spans = _host_spans(lambda: driver(jnp.ones(3)).block_until_ready(), tmp_path)
    assert [s[0] for s in spans] == [PREFIX + "probe_driver"]
    with pytest.raises(ZeroDivisionError):
        obs.instrument("probe_raise")(lambda: 1 / 0)()
    assert obs.FINISHED == []


def test_num_monitor_auto_stays_off_with_obs_off(obs_off):
    with obs.driver_span("probe"):
        assert numerics.resolve_num_monitor("auto") == "off"
    assert numerics.resolve_num_monitor(None) == "off"
    with obs.force_enabled(True):
        assert numerics.resolve_num_monitor("auto") == "on"


# -- named device scopes -------------------------------------------------------


def _potrf_scan():
    from slate_tpu.linalg.chol import _potrf_scan

    a = jnp.eye(96, dtype=jnp.float32) * 4 + 0.01
    return jax.jit(lambda a: _potrf_scan(a, nb=16)).lower(a)


def _getrf_scan():
    from slate_tpu.linalg.lu import getrf_scan_array

    a = jnp.eye(96, dtype=jnp.float32) * 4 + 0.01
    return jax.jit(lambda a: getrf_scan_array(a, nb=16)).lower(a)


def _getrf_rec():
    from slate_tpu.linalg.lu import _getrf_rec

    return jax.jit(_getrf_rec).lower(jnp.eye(160, dtype=jnp.float32) + 0.01)


def _posv():
    from slate_tpu.linalg.chol import posv_array

    return jax.jit(posv_array).lower(jnp.eye(32, dtype=jnp.float32) * 4,
                                     jnp.ones((32, 2), jnp.float32))


def _gesv():
    from slate_tpu.linalg.lu import gesv_array

    return jax.jit(gesv_array).lower(jnp.eye(32, dtype=jnp.float32) * 4,
                                     jnp.ones((32, 2), jnp.float32))


def _mesh_operand(nb=8):
    from slate_tpu.parallel import from_dense

    mesh = make_mesh(2, 2, devices=cpu_devices(4))
    a = jnp.asarray(np.random.default_rng(1).standard_normal((64, 64)), jnp.float32)
    return mesh, from_dense(a, mesh, nb, diag_pad_one=True)


def _pp(la):
    def lower():
        from slate_tpu.parallel.dist_lu import _pp_jit

        mesh, d = _mesh_operand()
        return _pp_jit.lower(d.tiles, mesh, 2, 2, d.nt, d.m, la, "psum", False)
    return lower


def _from_dense():
    from slate_tpu.parallel.dist import _cyclic_tiles

    mesh = make_mesh(2, 2, devices=cpu_devices(4))
    return _cyclic_tiles.lower(jnp.ones((64, 64), jnp.float32), mesh, 8, True)


def _trsm():
    from slate_tpu.parallel.dist_trsm import _trsm_jit
    from slate_tpu.types import Diag, Op, Uplo

    mesh, d = _mesh_operand()
    return _trsm_jit.lower(d.tiles, d.tiles, mesh, 2, 2, d.nt, Uplo.Lower, Op.NoTrans,
                           Diag.Unit, 1, "psum")


def _permute_rows():
    from slate_tpu.parallel.dist_lu import _permute_rows_jit

    mesh, d = _mesh_operand()
    return _permute_rows_jit.lower(d.tiles, jnp.arange(64), mesh, 2, 2)


@pytest.mark.parametrize("lower,want", [
    (_potrf_scan, {"panel", "bulk", "regroup"}),
    (_getrf_scan, {"panel", "swap", "bulk", "regroup"}),
    (_getrf_rec, {"panel", "swap", "bulk"}),
    (_pp(0), {"getrf", "panel", "swap", "bcast", "bulk"}),
    (_pp(1), {"getrf", "panel", "swap", "bcast", "bulk"}),
    (_posv, {"potrf", "potrs", "panel"}),
    (_gesv, {"getrf", "trsm", "panel"}),
    (_from_dense, {"redistribute"}),
    (_trsm, {"trsm"}),
    (_permute_rows, {"redistribute"}),
], ids=["potrf_scan", "getrf_scan", "getrf_rec", "pp_la0", "pp_la1", "posv", "gesv",
        "from_dense", "trsm_dist", "permute_rows"])
def test_scopes_reach_the_compiled_op_metadata(lower, want):
    assert want <= _scope_parts(lower().compile().as_text())


def test_phase_scope_changes_no_jaxpr():
    def marked(x):
        with phase_scope("panel", 0):
            y = jnp.tanh(x)
        with phase_scope("bulk"):
            return y @ y.T

    def plain(x):
        y = jnp.tanh(x)
        return y @ y.T

    x = jnp.ones((8, 8))
    assert str(jax.make_jaxpr(marked)(x)) == str(jax.make_jaxpr(plain)(x))
    text = jax.jit(marked).lower(x).as_text(debug_info=True)
    assert "panel" in text and "bulk" in text


def test_scopes_are_the_same_with_obs_on_and_off():
    from slate_tpu.parallel.dist_lu import getrf_pp_dist

    mesh, d = _mesh_operand(nb=16)

    def trace():
        return jax.make_jaxpr(lambda t: getrf_pp_dist(
            d.__class__(tiles=t, m=d.m, n=d.n, nb=d.nb, mesh=d.mesh, diag_pad=True),
            num_monitor="off")[0].tiles)(d.tiles)

    with obs.force_enabled(False):
        off = trace()
    with obs.force_enabled(True):
        on = trace()
    obs.reset()
    assert str(off) == str(on)


def test_tester_trace_writes_a_profile(tmp_path):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tester.py"), "getrf", "--dim", "32",
         "--type", "s", "--trace", str(tmp_path)],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT))
    assert out.returncode == 0, out.stderr[-2000:]
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
                if f.endswith(".xplane.pb"))
    names = {e.name for p in ProfileData.from_file(path).planes if p.name.startswith("/host:")
             for line in p.lines for e in line.events}
    assert "tester/getrf_array" in names and PREFIX + "getrf_array" in names
