"""Checkpointed k-loops + elastic resume (ISSUE 12 + 13).

Acceptance surface, kept LEAN (one shared n=64/nb=8 shape set, segment
jits reused across tests via the process jit cache, no clear_caches):
kill at step k → resume on the SAME mesh is bitwise-identical to the
uninterrupted factorization for potrf, LU-nopiv, partial-pivot LU, and
the MULTI-ARRAY-carry CAQR; resume on a RESHAPED mesh lands the
bitwise-same solution (tile-stack ops) or a structured refusal
(grid-locked geqrf/he2hb carries); checkpoint off is jaxpr-identical to
the current driver path (potrf / geqrf / he2hb); an in-segment kill
loses exactly kill.k − last_snapshot steps; async snapshots are
bitwise-equal to sync; a monitored nopiv factor growth-aborts mid-loop;
the kill injector is seeded-deterministic and one-shot; recovery-cost
counters reach the RunReport ft section.  The multi-op reshaped sweep
and the he2hb kill→resume sweep are ``-m slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.ft import ckpt, elastic, inject
from slate_tpu.ft.policy import ft_counter_values
from slate_tpu.parallel import from_dense, make_mesh, to_dense
from slate_tpu.parallel.dist_chol import potrf_dist
from slate_tpu.parallel.dist_lu import getrf_nopiv_dist, getrf_pp_dist
from slate_tpu.parallel.dist_qr import geqrf_dist
from slate_tpu.parallel.dist_twostage import he2hb_dist
from slate_tpu.types import Option, SlateError

from conftest import cpu_devices

N, NB = 64, 8
NT = N // NB
EVERY = 3  # segment boundaries 3, 6 — kill at 4 loses exactly 1 step


def mesh24():
    return make_mesh(2, 4, devices=cpu_devices(8))


def mesh42():
    return make_mesh(4, 2, devices=cpu_devices(8))


def _operand(kind, seed=7):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((N, N))
    if kind == "spd":
        a = a @ a.T / N + 2 * np.eye(N)
    elif kind == "dom":
        a = np.tril(a) + N * np.eye(N) + np.triu(
            rng.standard_normal((N, N)), 1)
    return jnp.asarray(a)


_CASES = {
    "potrf": ("spd", potrf_dist, ckpt.potrf_ckpt),
    "getrf_nopiv": ("dom", getrf_nopiv_dist, ckpt.getrf_nopiv_ckpt),
    "getrf_pp": ("general", getrf_pp_dist, ckpt.getrf_pp_ckpt),
}


def _run_case(op, mesh):
    kind, plain, ckpted = _CASES[op]
    d = from_dense(_operand(kind), mesh, NB, diag_pad_one=True)
    return d, plain(d), ckpted


def _assert_tree_bitwise(ref, got, what):
    for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g),
                                      err_msg=what)


@pytest.mark.parametrize("op", list(_CASES))
def test_kill_resume_bitwise_same_mesh(op):
    mesh = mesh24()
    d, ref, ckpted = _run_case(op, mesh)
    # uninterrupted checkpointed chain == fused kernel, bitwise
    _assert_tree_bitwise(ref, ckpted(d, every=EVERY), f"{op} ckpt vs fused")
    # seeded kill inside the second segment -> Preempted with the step-3
    # snapshot; resume must reproduce the fused result bitwise
    with inject.fault_scope(inject.FaultPlan([inject.KillFault(op, 4)])):
        with pytest.raises(ckpt.Preempted) as ei:
            ckpted(d, every=EVERY)
    ck = ei.value.checkpoint
    assert ck is not None and ck.step == 3 and ck.op == op
    _assert_tree_bitwise(ref, elastic.resume(ck, mesh), f"{op} resume")


def test_resume_reshaped_mesh_potrf():
    mesh = mesh24()
    d, ref, ckpted = _run_case("potrf", mesh)
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("potrf", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    res, info = elastic.resume(ei.value.checkpoint, mesh42())
    # the redistribution moves exact bytes: the solution is bitwise
    np.testing.assert_array_equal(
        np.asarray(to_dense(ref[0])), np.asarray(to_dense(res)))
    assert int(info) == int(ref[1])
    assert ft_counter_values()["ckpt_reshards"] >= 1


@pytest.mark.slow
@pytest.mark.parametrize("op", ["getrf_nopiv", "getrf_pp"])
def test_resume_reshaped_mesh_lu(op):
    mesh = mesh24()
    d, ref, ckpted = _run_case(op, mesh)
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault(op, 5)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    res = elastic.resume(ei.value.checkpoint, mesh42())
    np.testing.assert_array_equal(
        np.asarray(to_dense(ref[0])), np.asarray(to_dense(res[0])))
    if op == "getrf_pp":
        # pivot choices are data-driven: the permutation's data prefix
        # must survive the re-based padded row space exactly
        np.testing.assert_array_equal(
            np.asarray(ref[1])[:N], np.asarray(res[1])[:N])


def test_checkpoint_disk_roundtrip(tmp_path):
    mesh = mesh24()
    d, ref, ckpted = _run_case("potrf", mesh)
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("potrf", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    ck = ei.value.checkpoint
    ck2 = ckpt.Checkpoint.load(ck.save(str(tmp_path / "ck.npz")))
    assert (ck2.op, ck2.step, ck2.every, ck2.grid) == (
        ck.op, ck.step, ck.every, ck.grid)
    np.testing.assert_array_equal(ck.tiles, ck2.tiles)
    _assert_tree_bitwise(ref, elastic.resume(ck2, mesh), "disk resume")


def test_checkpoint_with_panel_impl_key_resumes(tmp_path):
    """A snapshot written when the metadata still carried a
    ``panel_impl`` field loads (the key is ignored) and resumes bitwise."""
    import json

    mesh = mesh24()
    d, ref, ckpted = _run_case("potrf", mesh)
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("potrf", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    path = ei.value.checkpoint.save(str(tmp_path / "new.npz"))
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(bytes(arrays["meta"]).decode())
    meta["panel_impl"] = "auto"
    arrays["meta"] = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    old = str(tmp_path / "old.npz")
    with open(old, "wb") as fh:
        np.savez(fh, **arrays)
    ck = ckpt.Checkpoint.load(old)
    _assert_tree_bitwise(ref, elastic.resume(ck, mesh), "old-snapshot resume")


def test_ckpt_off_is_driver_jaxpr_identical():
    """Option.Checkpoint off/absent routes potrf_mesh through the exact
    pre-checkpoint path — same jaxpr, not merely same numbers."""
    from slate_tpu.parallel import potrf_mesh

    mesh = mesh24()
    a = _operand("spd")

    def jx(opts):
        return str(jax.make_jaxpr(
            lambda x: potrf_mesh(x, mesh, NB, opts))(a))

    base = jx(None)
    assert jx({Option.Checkpoint: "off"}) == base
    assert jx({Option.Checkpoint: 0}) == base


def test_kill_injector_deterministic_and_one_shot():
    k1 = inject.seeded_kill(5, "potrf", NT)
    k2 = inject.seeded_kill(5, "potrf", NT)
    assert (k1.op, k1.k) == (k2.op, k2.k) and 1 <= k1.k < NT
    plan = inject.FaultPlan([inject.KillFault("potrf", 4)])
    with inject.fault_scope(plan):
        (kf,) = inject.armed_kills("potrf")
        plan.consume_fault(kf)
        assert inject.armed_kills("potrf") == []  # one-shot: resume clean
    persist = inject.FaultPlan([inject.KillFault("potrf", 4, persist=True)])
    with inject.fault_scope(persist):
        (kf,) = inject.armed_kills("potrf")
        persist.consume_fault(kf)
        assert len(inject.armed_kills("potrf")) == 1  # re-kills on resume
    # kills never leak into the kernel fault spec
    with inject.fault_scope(plan):
        ints, _ = inject.spec_arrays("potrf")
        assert not ints[:, 0].any()


def test_ckpt_counters_reach_runreport():
    from slate_tpu.obs import report

    mesh = mesh24()
    d, _ref, ckpted = _run_case("potrf", mesh)
    before = ft_counter_values()
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("potrf", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    elastic.resume(ei.value.checkpoint, mesh)
    after = ft_counter_values()
    assert after["ckpt_kills"] == before["ckpt_kills"] + 1
    assert after["ckpt_lost_steps"] == before["ckpt_lost_steps"] + 1
    assert after["ckpt_resumes"] == before["ckpt_resumes"] + 1
    assert after["ckpt_snapshots"] > before["ckpt_snapshots"]
    assert after["ckpt_snapshot_bytes"] > before["ckpt_snapshot_bytes"]
    rep = report.make_report("ckpt_counters_probe")
    assert rep["ft"]["ckpt_resumes"] >= after["ckpt_resumes"]
    assert report.validate_report(rep) == []


def test_ckpt_num_monitor_gauges_match_fused():
    """The NumMonitor gauges ride the segment carry: a checkpointed run
    records the same growth/margin values as the fused kernel."""
    from slate_tpu.obs import numerics as num

    mesh = mesh24()
    d = from_dense(_operand("spd"), mesh, NB, diag_pad_one=True)
    num.clear_last("potrf")
    potrf_dist(d, num_monitor="on")
    fused = num.last_gauges("potrf")
    num.clear_last("potrf")
    ckpt.potrf_ckpt(d, every=EVERY, num_monitor="on")
    segd = num.last_gauges("potrf")
    assert fused and segd
    for key in fused:
        assert segd[key] == fused[key], (key, fused, segd)


# ---------------------------------------------------------------------------
# ISSUE 13: multi-array carries (geqrf / he2hb), in-segment kills, async
# snapshots, growth abort — same lean n=64/nb=8 shape set, segment jits
# shared across tests via the process jit cache.
# ---------------------------------------------------------------------------


def test_qr_kill_resume_bitwise(tmp_path):
    """The CAQR chain's MULTI-ARRAY carry (tile stack + T_loc + tree V/T
    stacks): uninterrupted chain == fused kernel bitwise, kill→resume
    (through a disk round trip) bitwise, and a reshaped-grid resume is
    REFUSED with a structured error (the aux carries are grid-locked)."""
    mesh = mesh24()
    d = from_dense(_operand("general"), mesh, NB)
    ref = geqrf_dist(d)
    _assert_tree_bitwise(ref, ckpt.geqrf_ckpt(d, every=EVERY),
                         "geqrf ckpt vs fused")
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("geqrf", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpt.geqrf_ckpt(d, every=EVERY)
    ck = ei.value.checkpoint
    assert ck is not None and ck.step == 3 and ck.op == "geqrf"
    assert set(ck.arrays) == {"tls", "tvs", "tts"}
    ck = ckpt.Checkpoint.load(ck.save(str(tmp_path / "qr.npz")))
    _assert_tree_bitwise(ref, elastic.resume(ck, mesh), "geqrf resume")
    with pytest.raises(SlateError, match="grid-locked"):
        elastic.resume(ck, mesh42())


def test_qr_ckpt_orth_gauge_bitwise_and_recorded():
    """ISSUE 14 satellite (ROADMAP "NumMonitor gauges through the QR/eig
    segment chains"): the monitored CAQR chain carries the per-panel
    reflector/τ orthogonality-loss proxy — results stay BITWISE equal to
    the unmonitored chain (and hence the fused kernel), the gauge lands
    as num.qr_orth_margin / qr_orth_loss_max (eps-class for a healthy
    operand), and off mode records nothing."""
    from slate_tpu.obs import numerics as num

    mesh = mesh24()
    d = from_dense(_operand("general"), mesh, NB)
    ref = geqrf_dist(d)
    num.reset()
    _assert_tree_bitwise(ref, ckpt.geqrf_ckpt(d, every=EVERY,
                                              num_monitor="on"),
                         "monitored geqrf ckpt vs fused")
    vals = num.num_counter_values()
    assert 0.0 < vals["qr_orth_loss_max"] < 1e-10  # ~eps64, healthy panel
    assert num.last_gauges("geqrf")["qr_orth_loss"] \
        == vals["qr_orth_loss_max"]
    # ISSUE 15 acceptance: the FUSED (non-checkpointed) monitored loop
    # reports the SAME gauge bitwise on the same operand (max folds are
    # exact, so segment boundaries cannot move the running max) — and
    # its results stay bitwise too
    chained_gauge = vals["qr_orth_loss_max"]
    num.reset()
    _assert_tree_bitwise(ref, geqrf_dist(d, num_monitor="on"),
                         "monitored fused geqrf vs plain")
    assert num.last_gauges("geqrf")["qr_orth_loss"] == chained_gauge
    # off mode: the plain (unchanged) segment chain — already compiled by
    # test_qr_kill_resume_bitwise — records nothing (the kill->resume
    # gauge flow itself rides the same snapshot gauges dict the potrf/LU
    # chains tier-1-test; no extra segment compiles here)
    num.reset()
    ckpt.geqrf_ckpt(d, every=EVERY, num_monitor="off")
    assert num.num_counter_values()["qr_orth_loss_max"] == 0.0


def test_in_segment_kill_loses_steps_since_snapshot():
    """KillFault(in_segment=True): the partial segment really executes
    (then dies), the loss counter reads exactly kill.k − last_snapshot
    steps, and resume from the boundary snapshot is still bitwise."""
    mesh = mesh24()
    d, ref, ckpted = _run_case("potrf", mesh)
    before = ft_counter_values()
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("potrf", 5, in_segment=True)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpted(d, every=EVERY)
    after = ft_counter_values()
    ck = ei.value.checkpoint
    assert ck is not None and ck.step == 3  # last snapshot boundary
    assert after["ckpt_lost_steps"] - before["ckpt_lost_steps"] == 5 - 3
    assert after["ckpt_inseg_kills"] - before["ckpt_inseg_kills"] == 1
    _assert_tree_bitwise(ref, elastic.resume(ck, mesh), "inseg resume")


def test_async_snapshots_bitwise():
    """Async snapshots (copy_to_host_async fenced at the next boundary)
    are bitwise-equal to sync ones: same results, same snapshot bytes on
    a kill, counters record the overlap."""
    mesh = mesh24()
    d, ref, ckpted = _run_case("potrf", mesh)
    before = ft_counter_values()
    _assert_tree_bitwise(
        ref, ckpt.potrf_ckpt(d, every=EVERY, async_snapshots=True),
        "async ckpt vs fused")
    after = ft_counter_values()
    assert after["ckpt_async_snapshots"] > before["ckpt_async_snapshots"]
    assert after["ckpt_snapshots"] > before["ckpt_snapshots"]  # fenced+counted

    def killed(async_snapshots):
        with inject.fault_scope(
            inject.FaultPlan([inject.KillFault("potrf", 4)])
        ), pytest.raises(ckpt.Preempted) as ei:
            ckpt.potrf_ckpt(d, every=EVERY, async_snapshots=async_snapshots)
        return ei.value.checkpoint

    ck_async, ck_sync = killed(True), killed(False)
    assert ck_async.step == ck_sync.step == 3
    np.testing.assert_array_equal(ck_async.tiles, ck_sync.tiles)


def test_growth_abort_nopiv_mid_loop():
    """ROADMAP "close the control loop": a monitored checkpointed nopiv
    LU whose running growth crosses GROWTH_THRESHOLD aborts at the next
    segment boundary (structured GrowthAbort naming the step) instead of
    completing a garbage factor; growth_abort=False opts out and
    completes; the num.growth_aborts counter moves."""
    from slate_tpu.obs.numerics import GrowthAbort, num_counter_values

    mesh = mesh24()
    g = np.array(_operand("dom"))
    g[0, 0] = 1e-9  # tiny leading pivot: nopiv growth explodes at step 0
    d = from_dense(jnp.asarray(g), mesh, NB, diag_pad_one=True)
    before = num_counter_values()
    with pytest.raises(GrowthAbort) as ei:
        ckpt.getrf_nopiv_ckpt(d, every=EVERY, num_monitor="on")
    after = num_counter_values()
    assert ei.value.op == "getrf_nopiv" and ei.value.step == EVERY
    assert ei.value.growth > ei.value.threshold
    assert after["growth_aborts"] == before["growth_aborts"] + 1
    lu, info = ckpt.getrf_nopiv_ckpt(d, every=EVERY, num_monitor="on",
                                     growth_abort=False)
    assert int(info) == 0  # finite garbage completes when opted out


@pytest.mark.slow
def test_he2hb_kill_resume_bitwise():
    """The two-stage eig stage-1 reduction's multi-array carry (tiles →
    band + sharded reflectors + compact-WY stacks): chain == fused
    bitwise, kill→resume bitwise, reshaped-grid resume refused."""
    mesh = mesh24()
    d = from_dense(_operand("spd"), mesh, NB)
    ref = he2hb_dist(d)
    _assert_tree_bitwise(ref, ckpt.he2hb_ckpt(d, every=2),
                         "he2hb ckpt vs fused")
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("he2hb", 3)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpt.he2hb_ckpt(d, every=2)
    ck = ei.value.checkpoint
    assert ck is not None and ck.step == 2 and set(ck.arrays) == {
        "vqs", "tqs"}
    _assert_tree_bitwise(ref, elastic.resume(ck, mesh), "he2hb resume")
    with pytest.raises(SlateError, match="grid-locked"):
        elastic.resume(ck, mesh42())


def test_ckpt_off_geqrf_jaxpr_identical():
    """Option.Checkpoint off/absent routes geqrf_mesh through the exact
    pre-checkpoint path — same jaxpr, not merely same numbers."""
    from slate_tpu.parallel import geqrf_mesh

    mesh = mesh24()
    a = _operand("general")

    def jx(opts):
        return str(jax.make_jaxpr(
            lambda x: geqrf_mesh(x, mesh, NB, opts))(a))

    base = jx(None)
    assert jx({Option.Checkpoint: "off"}) == base
    assert jx({Option.Checkpoint: 0}) == base


def test_ckpt_off_he2hb_jaxpr_identical():
    """he2hb_ckpt with Checkpoint off routes to the untouched fused
    he2hb_dist — same jaxpr (trace-only: nothing executes)."""
    mesh = mesh24()
    d = from_dense(_operand("spd"), mesh, NB)

    def rewrap(t):
        from slate_tpu.parallel.dist import DistMatrix

        return DistMatrix(tiles=t, m=d.m, n=d.n, nb=d.nb, mesh=mesh)

    base = str(jax.make_jaxpr(lambda t: he2hb_dist(rewrap(t)))(d.tiles))
    off = str(jax.make_jaxpr(
        lambda t: ckpt.he2hb_ckpt(rewrap(t), every=None))(d.tiles))
    assert off == base


def test_growth_abort_survives_resume():
    """Review fix: the growth-abort gate is persisted in the Checkpoint,
    so a preemption BEFORE the gauge crosses cannot smuggle a garbage
    no-pivot factor past the abort — the resumed run still raises."""
    from slate_tpu.obs.numerics import GrowthAbort

    mesh = mesh24()
    g = np.array(_operand("dom"))
    # isolate a tiny pivot at factor step 6: no updates land on (48, 48)
    # (row/col 48 zero left of/above the diagonal), while the column
    # below and row right are O(1) — the step-6 elimination divides by
    # 1e-9 and growth explodes only then, AFTER the step-3 snapshot
    g[48, :48] = 0.0
    g[:48, 48] = 0.0
    g[48, 48] = 1e-9
    g[49:, 48] = 1.0
    g[48, 49:] = 1.0
    d = from_dense(jnp.asarray(g), mesh, NB, diag_pad_one=True)
    with inject.fault_scope(
        inject.FaultPlan([inject.KillFault("getrf_nopiv", 4)])
    ), pytest.raises(ckpt.Preempted) as ei:
        ckpt.getrf_nopiv_ckpt(d, every=EVERY, num_monitor="on")
    ck = ei.value.checkpoint
    assert ck is not None and ck.growth_abort
    with pytest.raises(GrowthAbort):
        elastic.resume(ck, mesh)
