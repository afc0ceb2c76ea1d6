"""ABFT subsystem tests (ISSUE 4): checksum-carrying kernels on the
8-device CPU mesh — FT off bitwise-identical, clean detect runs quiet
across dtypes, injected single-tile faults at every phase detected /
located / repaired within the op's tolerance, double faults escalating
to the structured FtError, and the policy/option/counter plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from slate_tpu.ft import FtError, FtPolicy, Fault, FaultPlan, fault_scope
from slate_tpu.ft import abft, checksum as cks, inject
from slate_tpu.ft.policy import ft_counter_values
from slate_tpu.parallel import (
    gemm_mesh,
    getrf_nopiv_mesh,
    make_mesh,
    posv_mesh,
    potrf_mesh,
    to_dense,
)
from slate_tpu.types import Option

from conftest import cpu_devices

N, NB = 64, 8
NT = N // NB
GRID = (2, 4)


def mesh24():
    return make_mesh(*GRID, devices=cpu_devices(8))


def _rand(rng, m, n, dtype=np.float64):
    return jnp.asarray(rng.standard_normal((m, n)).astype(dtype))


def _spd(rng, n, dtype=np.float64):
    g = rng.standard_normal((n, n))
    return jnp.asarray((g @ g.T + n * np.eye(n)).astype(dtype))


def _ddom(rng, n, dtype=np.float64):
    return jnp.asarray(
        (rng.standard_normal((n, n)) + n * np.eye(n)).astype(dtype)
    )


# ---------------------------------------------------------------------------
# (a) FT off reproduces the plain kernels bitwise
# ---------------------------------------------------------------------------


def test_ft_off_bitwise_identical(rng):
    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    plain = gemm_mesh(1.0, a, b, mesh, nb=NB)
    for off in ("off", FtPolicy.Off):
        routed = gemm_mesh(1.0, a, b, mesh, nb=NB,
                           opts={Option.FaultTolerance: off})
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(routed))
    spd = _spd(rng, N)
    l0, i0 = potrf_mesh(spd, mesh, nb=NB)
    l1, i1 = potrf_mesh(spd, mesh, nb=NB, opts={Option.FaultTolerance: "off"})
    np.testing.assert_array_equal(np.asarray(l0.tiles), np.asarray(l1.tiles))
    assert int(i0) == int(i1)


def test_bad_policy_rejected(rng):
    mesh = mesh24()
    a = _rand(rng, N, N)
    with pytest.raises(ValueError):
        gemm_mesh(1.0, a, a, mesh, nb=NB,
                  opts={Option.FaultTolerance: "warp-speed"})


# ---------------------------------------------------------------------------
# checksum algebra unit tests (no mesh)
# ---------------------------------------------------------------------------


def test_checksum_encode_locate_roundtrip(rng):
    nb, mt, nt = 4, 6, 5
    a = jnp.asarray(rng.standard_normal((mt * nb, nt * nb)))
    cs = cks.row_checksums(a, nb)
    # corrupt one tile, recompute, locate by the ramp/unit ratio
    bad = np.asarray(a).copy()
    ti, tj = 3, 2
    bad[ti * nb : (ti + 1) * nb, tj * nb : (tj + 1) * nb] *= 2.0
    d = np.asarray(cs - cks.row_checksums(jnp.asarray(bad), nb))
    d1, d2 = np.abs(d).reshape(2, nb, nt, nb).max(axis=(1, 3))
    assert np.argmax(d1) == tj and np.count_nonzero(d1 > 1e-12) == 1
    loc = cks.ratio_locate(
        d[:nb, tj * nb : (tj + 1) * nb], d[nb:, tj * nb : (tj + 1) * nb], mt
    )
    assert loc == ti
    # the unit discrepancy added back restores the tile exactly
    bad[ti * nb : (ti + 1) * nb, tj * nb : (tj + 1) * nb] += d[
        :nb, tj * nb : (tj + 1) * nb
    ]
    np.testing.assert_allclose(bad, np.asarray(a), atol=0)


def test_checksum_nonfinite_flags():
    d = np.zeros(6)
    d[2] = np.nan
    d[4] = np.inf
    assert list(cks.flag_mismatches(d, tol=1.0)) == [2, 4]
    assert cks.ratio_locate(np.full((2, 2), np.nan), np.ones((2, 2)), 4) == -1


# ---------------------------------------------------------------------------
# (b) detect with no fault: numerically clean, flags nothing, f32 + f64
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_detect_clean(rng, dtype):
    mesh = mesh24()
    tol = 1e-12 if dtype == np.float64 else 1e-4
    before = ft_counter_values()["detected"]
    a, b = _rand(rng, N, N, dtype), _rand(rng, N, N, dtype)
    c, rep = abft.gemm_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Detect)
    ref = np.asarray(a) @ np.asarray(b)
    assert rep.clean
    assert np.abs(np.asarray(c) - ref).max() / np.abs(ref).max() < tol
    spd = _spd(rng, N, dtype)
    l, info, rep = abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Detect)
    ld = np.tril(np.asarray(to_dense(l)))
    assert rep.clean and int(info) == 0
    assert (np.abs(ld @ ld.T - np.asarray(spd)).max()
            / np.abs(np.asarray(spd)).max() < tol * 10)
    dd = _ddom(rng, N, dtype)
    lu, info, rep = abft.getrf_nopiv_ft(dd, mesh, NB, policy=FtPolicy.Detect)
    lud = np.asarray(to_dense(lu))
    resid = (np.tril(lud, -1) + np.eye(N, dtype=dtype)) @ np.triu(lud) - np.asarray(dd)
    assert rep.clean and int(info) == 0
    assert np.abs(resid).max() / np.abs(np.asarray(dd)).max() < tol * 10
    assert ft_counter_values()["detected"] == before  # nothing flagged


@pytest.mark.parametrize("op", ["potrf", "getrf_nopiv"])
def test_ft_factor_clean_backward_error(rng, op):
    """A clean checksum-carrying factor reports ``clean`` and meets the
    float64 backward-error class of an NB-blocked factorization."""
    mesh = mesh24()
    if op == "potrf":
        a = _spd(rng, N)
        res, info, rep = abft.potrf_ft(a, mesh, NB)
    else:
        a = _ddom(rng, N)
        res, info, rep = abft.getrf_nopiv_ft(a, mesh, NB)
    assert int(info) == 0
    assert rep.action == "clean"
    out = np.asarray(to_dense(res), np.float64)
    an = np.asarray(a, np.float64)
    if op == "potrf":
        rec = np.tril(out) @ np.tril(out).T
    else:
        rec = (np.tril(out, -1) + np.eye(N)) @ np.triu(out)
    tol = 100 * NB * float(np.finfo(np.float64).eps) * N * np.abs(an).max()
    assert np.abs(rec - an).max() < tol


# ---------------------------------------------------------------------------
# (c) injected single-tile faults per phase: detect + locate + repair
# ---------------------------------------------------------------------------


def test_gemm_fault_all_phases(rng):
    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    ref = np.asarray(a) @ np.asarray(b)
    for seed, phase in [(21, "trailing"), (22, "bcast"), (23, "trailing")]:
        f = inject.seeded_fault(seed, "gemm", NT, GRID, phase=phase)
        with fault_scope(FaultPlan([f])):
            c, rep = abft.gemm_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Correct)
        assert rep.action in ("corrected", "recomputed"), (phase, rep.action)
        assert rep.detections, phase
        # located damage names the injected tile row or column
        wheres = [d["where"] for d in rep.detections]
        assert any(f.ti in w or f.tj in w for w in wheres), (f, wheres)
        err = np.abs(np.asarray(c) - ref).max() / np.abs(ref).max()
        assert err < 1e-12, (phase, err)
    # a single-tile trailing fault repairs algebraically, not by rerun
    f = inject.seeded_fault(21, "gemm", NT, GRID, phase="trailing")
    with fault_scope(FaultPlan([f])):
        _, rep = abft.gemm_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Correct)
    assert rep.action == "corrected"


def test_potrf_fault_all_phases(rng):
    mesh = mesh24()
    spd = _spd(rng, N)
    expect = {"panel": "corrected", "bcast": "recomputed", "trailing": "recomputed"}
    for seed, phase in [(31, "panel"), (32, "bcast"), (33, "trailing")]:
        f = inject.seeded_fault(seed, "potrf", NT, GRID, phase=phase)
        with fault_scope(FaultPlan([f])):
            l, info, rep = abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Correct)
        assert rep.action == expect[phase], (phase, rep.action)
        assert int(info) == 0
        ld = np.tril(np.asarray(to_dense(l)))
        resid = (np.abs(ld @ ld.T - np.asarray(spd)).max()
                 / np.abs(np.asarray(spd)).max())
        assert resid < 1e-12, (phase, resid)


def test_lu_fault_all_phases(rng):
    mesh = mesh24()
    dd = _ddom(rng, N)
    expect = {"panel": "corrected", "bcast": "recomputed", "trailing": "recomputed"}
    for seed, phase in [(41, "panel"), (42, "bcast"), (43, "trailing")]:
        f = inject.seeded_fault(seed, "getrf_nopiv", NT, GRID, phase=phase)
        with fault_scope(FaultPlan([f])):
            lu, info, rep = abft.getrf_nopiv_ft(dd, mesh, NB, policy=FtPolicy.Correct)
        assert rep.action == expect[phase], (phase, rep.action)
        assert int(info) == 0
        lud = np.asarray(to_dense(lu))
        resid = (np.tril(lud, -1) + np.eye(N)) @ np.triu(lud) - np.asarray(dd)
        rel = np.abs(resid).max() / np.abs(np.asarray(dd)).max()
        assert rel < 1e-10, (phase, rel)


def test_detect_policy_failstops(rng):
    mesh = mesh24()
    spd = _spd(rng, N)
    f = inject.seeded_fault(51, "potrf", NT, GRID, phase="panel")
    with fault_scope(FaultPlan([f])):
        with pytest.raises(FtError) as ei:
            abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Detect)
    assert ei.value.op == "potrf" and ei.value.detections


def test_recompute_policy_skips_algebra(rng):
    # even the exactly-correctable panel fault reruns under `recompute`
    mesh = mesh24()
    spd = _spd(rng, N)
    f = inject.seeded_fault(52, "potrf", NT, GRID, phase="panel")
    with fault_scope(FaultPlan([f])):
        l, info, rep = abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Recompute)
    assert rep.action == "recomputed" and int(info) == 0
    ld = np.tril(np.asarray(to_dense(l)))
    assert (np.abs(ld @ ld.T - np.asarray(spd)).max()
            / np.abs(np.asarray(spd)).max() < 1e-12)


# ---------------------------------------------------------------------------
# (d) double fault -> FtError
# ---------------------------------------------------------------------------


def test_double_fault_raises_fterror(rng):
    mesh = mesh24()
    spd = _spd(rng, N)
    faults = [
        inject.seeded_fault(61, "potrf", NT, GRID, phase="trailing", persist=True),
        inject.seeded_fault(62, "potrf", NT, GRID, phase="trailing", persist=True),
    ]
    before = ft_counter_values()["uncorrectable"]
    with fault_scope(FaultPlan(faults)):
        with pytest.raises(FtError) as ei:
            abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Correct)
    assert "recompute" in str(ei.value)
    assert ft_counter_values()["uncorrectable"] > before
    # transient (one-shot) double fault: the recompute rerun is clean
    faults = [
        inject.seeded_fault(61, "potrf", NT, GRID, phase="trailing"),
        inject.seeded_fault(62, "potrf", NT, GRID, phase="trailing"),
    ]
    with fault_scope(FaultPlan(faults)):
        l, info, rep = abft.potrf_ft(spd, mesh, NB, policy=FtPolicy.Correct)
    assert rep.action == "recomputed" and int(info) == 0


# ---------------------------------------------------------------------------
# plumbing: drivers opts routing, api facade, counters/RunReport, lookahead
# ---------------------------------------------------------------------------


def test_driver_opts_routing_corrects(rng):
    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    ref = np.asarray(a) @ np.asarray(b)
    f = inject.seeded_fault(71, "gemm", NT, GRID, phase="trailing")
    with fault_scope(FaultPlan([f])):
        c = gemm_mesh(1.0, a, b, mesh, nb=NB,
                      opts={Option.FaultTolerance: "correct"})
    assert np.abs(np.asarray(c) - ref).max() / np.abs(ref).max() < 1e-12
    # factor routing: potrf under FT solves an SPD system end to end
    spd = _spd(rng, N)
    xt = _rand(rng, N, 3)
    bb = jnp.asarray(np.asarray(spd) @ np.asarray(xt))
    x, info = posv_mesh(spd, bb, mesh, nb=NB,
                        opts={Option.FaultTolerance: FtPolicy.Correct})
    assert int(info) == 0
    assert np.abs(np.asarray(x) - np.asarray(xt)).max() < 1e-9
    lu, info = getrf_nopiv_mesh(_ddom(rng, N), mesh, nb=NB,
                                opts={Option.FaultTolerance: "detect"})
    assert int(info) == 0


def test_api_multiply_ft(rng):
    from slate_tpu import api

    a, b = _rand(rng, 48, 40), _rand(rng, 40, 24)
    ref = np.asarray(a) @ np.asarray(b)
    for pol in ("detect", "correct"):
        out = api.multiply(1.0, a, b, opts={Option.FaultTolerance: pol})
        assert np.abs(np.asarray(out) - ref).max() < 1e-12
    with pytest.raises(ValueError):
        api.multiply(1.0, a, b, opts={Option.FaultTolerance: "sometimes"})


def test_ft_counters_reach_runreport(rng):
    from slate_tpu.obs import report

    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    before = ft_counter_values()
    f = inject.seeded_fault(81, "gemm", NT, GRID, phase="trailing")
    with fault_scope(FaultPlan([f])):
        abft.gemm_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Correct)
    after = ft_counter_values()
    assert after["detected"] > before["detected"]
    assert after["corrected"] > before["corrected"]
    rep = report.make_report("ft_test")
    assert report.validate_report(rep) == []
    assert rep["ft"]["detected"] == after["detected"]
    # ft values join the --check comparison surface
    vals = report.load_values(rep)
    assert vals["ft_detected"] == after["detected"]


def test_ft_gemm_lookahead_depth_invariant(rng):
    # the checksum panels ride prefetch_bcast: any depth is bitwise-equal
    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    outs = []
    for la in (0, 2):
        c, rep = abft.gemm_ft(1.0, a, b, mesh, NB,
                              policy=FtPolicy.Detect, lookahead=la)
        assert rep.clean
        outs.append(np.asarray(c))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_non_spd_keeps_info_semantics(rng):
    # verify-drive finding: a legitimately non-SPD input NaN-poisons the
    # factor (info != 0) — the FT layer must return the plain driver's
    # info contract, not misread the poison as corruption and FtError
    mesh = mesh24()
    bad = jnp.asarray(-np.eye(32))
    before = ft_counter_values()["uncorrectable"]
    l, info, rep = abft.potrf_ft(bad, mesh, 8, policy=FtPolicy.Correct)
    assert int(info) != 0
    assert rep.action == "clean"  # honest numerics, no fault claimed
    assert ft_counter_values()["uncorrectable"] == before
    # and under detect too: breakdown is not a detection
    l, info, rep = abft.potrf_ft(bad, mesh, 8, policy=FtPolicy.Detect)
    assert int(info) != 0


# ---------------------------------------------------------------------------
# (h) trsm ABFT (ISSUE 12 satellite): the solution-checksum carrier
# ---------------------------------------------------------------------------


def test_trsm_abft_detect_correct_recompute(rng):
    """The checksum columns ride the RHS through the unchanged TrsmB
    schedule: clean runs are quiet; a corrupted ALREADY-SOLVED X tile
    (final data) repairs exactly from the unit discrepancy; a corrupted
    not-yet-solved tile propagates and escalates to one recompute; the
    detect policy fail-stops."""
    mesh = mesh24()
    tl = jnp.asarray(np.tril(np.asarray(_rand(rng, N, N))) + N * np.eye(N))
    b = _rand(rng, N, 2 * NB)
    ref = np.linalg.solve(np.asarray(tl), np.asarray(b))

    def err(x):
        return np.abs(np.asarray(x) - ref).max() / np.abs(ref).max()

    x, rep = abft.trsm_ft(tl, b, mesh, NB, policy=FtPolicy.Correct)
    assert rep.clean and err(x) < 1e-10

    final = Fault("trsm", k=NT - 1, phase="trailing", ti=1, tj=0,
                  r=1 % GRID[0], c=0, mode=inject.MODE_SCALE, value=3.0)
    with fault_scope(FaultPlan([final])):
        x2, rep2 = abft.trsm_ft(tl, b, mesh, NB, policy=FtPolicy.Correct)
    assert rep2.action == "corrected" and err(x2) < 1e-10

    live = Fault("trsm", k=1, phase="trailing", ti=5, tj=1,
                 r=5 % GRID[0], c=1 % GRID[1], mode=inject.MODE_SCALE,
                 value=3.0)
    with fault_scope(FaultPlan([live])):
        x3, rep3 = abft.trsm_ft(tl, b, mesh, NB, policy=FtPolicy.Correct)
    assert rep3.action == "recomputed" and err(x3) < 1e-10

    with fault_scope(FaultPlan([Fault(
        "trsm", k=NT - 1, phase="trailing", ti=2, tj=0, r=0, c=0,
        mode=inject.MODE_SCALE, value=2.0,
    )])):
        with pytest.raises(FtError):
            abft.trsm_ft(tl, b, mesh, NB, policy=FtPolicy.Detect)


def test_her2k_abft_off_bitwise_and_clean(rng):
    """her2k_ft (ISSUE 13): policy Off is bitwise the plain full her2k;
    a clean protected run is quiet and matches the dense reference."""
    from slate_tpu.parallel import from_dense
    from slate_tpu.parallel.dist_blas3 import her2k_dist

    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    off, rep0 = abft.her2k_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Off)
    plain = to_dense(her2k_dist(
        1.0, from_dense(a, mesh, NB), from_dense(b, mesh, NB), full=True
    ))[:N, :N]
    assert rep0.clean
    np.testing.assert_array_equal(np.asarray(off), np.asarray(plain))

    c, rep = abft.her2k_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Detect)
    ref = np.asarray(a) @ np.asarray(b).T + np.asarray(b) @ np.asarray(a).T
    assert rep.clean
    assert np.abs(np.asarray(c) - ref).max() / np.abs(ref).max() < 1e-12


def test_her2k_abft_inject_detect_repair(rng):
    """Injected accumulator damage is final data — exactly correctable
    from the carried checksums (the GEMM repair class); a received-panel
    (bcast) fault lands clean through repair-or-recompute; the detect
    policy fail-stops; counters move."""
    mesh = mesh24()
    a, b = _rand(rng, N, N), _rand(rng, N, N)
    ref = np.asarray(a) @ np.asarray(b).T + np.asarray(b) @ np.asarray(a).T

    def err(x):
        return np.abs(np.asarray(x) - ref).max() / np.abs(ref).max()

    before = ft_counter_values()
    trail = Fault("her2k", k=NT - 1, phase="trailing", ti=3, tj=1,
                  r=3 % GRID[0], c=1 % GRID[1], mode=inject.MODE_SCALE,
                  value=3.0)
    with fault_scope(FaultPlan([trail])):
        c1, rep1 = abft.her2k_ft(1.0, a, b, mesh, NB,
                                 policy=FtPolicy.Correct)
    assert rep1.action == "corrected" and err(c1) < 1e-12

    bc = Fault("her2k", k=2, phase="bcast", ti=4, tj=2, r=4 % GRID[0],
               c=1, mode=inject.MODE_SCALE, value=3.0)
    with fault_scope(FaultPlan([bc])):
        c2, rep2 = abft.her2k_ft(1.0, a, b, mesh, NB,
                                 policy=FtPolicy.Correct)
    assert rep2.action in ("corrected", "recomputed") and err(c2) < 1e-12

    with fault_scope(FaultPlan([Fault(
        "her2k", k=1, phase="trailing", ti=5, tj=2, r=5 % GRID[0],
        c=2 % GRID[1], mode=inject.MODE_SCALE, value=2.0,
    )])):
        with pytest.raises(FtError):
            abft.her2k_ft(1.0, a, b, mesh, NB, policy=FtPolicy.Detect)
    after = ft_counter_values()
    assert after["detected"] >= before["detected"] + 3
    assert after["corrected"] > before["corrected"]

    # beta C rides the augmented accumulator consistently (linearity)
    c0 = _spd(rng, N)
    cc, repc = abft.her2k_ft(1.0, a, b, mesh, NB, beta=0.5, c=c0,
                             policy=FtPolicy.Detect)
    refc = ref + 0.5 * np.asarray(c0)
    assert repc.clean
    assert np.abs(np.asarray(cc) - refc).max() / np.abs(refc).max() < 1e-12
