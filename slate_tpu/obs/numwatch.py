"""numwatch: the num.* artifact CLI — seeded numerics gauges + distributed
condition estimation + mixed-ladder health routing for the mesh kernels.

CLI::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m slate_tpu.obs.numwatch <op> [--n 48] [--nb 8] \\
            [--impl ring] [--out NUM.report.json]
    python -m slate_tpu.obs.numwatch --smoke [--out artifacts/obs]

``<op>`` is one of lu / potrf / mixed / qr (the last since ISSUE 15:
the QR/eig-chain orthogonality-loss gauges — the fused-vs-checkpointed
geqrf gauge equality pinned at an exact 0.0 key, plus the first he2hb
margin).  Each pass runs SEEDED
deterministic inputs (utils.testing.generate — including the adversarial
kinds: Wilkinson growth, prescribed-spectrum ill-conditioned,
near-singular-diagonal SPD) through the monitored kernels
(Option.NumMonitor=on) and emits an ordinary RunReport whose headline
``values`` carry the ``num.*`` keys:

- ``num.lu_growth_*`` — the in-carry element-growth gauge; the
  Wilkinson input realizes the 2^{n-1} partial-pivot bound EXACTLY, so
  the committed value is closed-form, not just reproducible,
- ``num.chol_margin_*`` / ``num.chol_diag_min_*`` — the Schur-diagonal
  near-breakdown margin (the seeded near-singular SPD pins it at
  1/cond),
- ``num.gecondest_*`` / ``num.pocondest_*`` — the distributed
  Hager-Higham estimates next to their single-chip references
  (``*_match_rel`` is the parity residual the smoke bounds),
- ``num.routed_gmres`` / ``num.ir_iters_*`` / ``num.ir_history_len_*``
  — the mixed ladder's health routing + convergence-trajectory shape,
- ``num.*_runtime_*`` — wall-clock (machine-dependent; CI gates with
  ``--ignore 'num.*_runtime_*'``).

Everything except the runtime keys is a pure function of (matrix,
schedule) on a deterministic backend — growth factors, condition
estimates and iteration counts are bitwise-reproducible at fixed
shape/depth/impl (and bitwise-INVARIANT across Option.BcastImpl, which
the smoke asserts psum-vs-ring), so the committed
``artifacts/obs/num_{lu,potrf,mixed}.report.json`` references gate with
tight thresholds.

``--smoke`` is the CI acceptance run: all three ops, schema-valid
reports, the Wilkinson gauge trips above ``numerics.GROWTH_THRESHOLD``
AND routes the auto ladder to the GMRES tier, distributed condest
matches single-chip to rtol, gauges are bitwise across psum/ring, a
Perfetto trace with the ``num.ir_rnorm`` convergence counter track
validates, and the ``--check`` gate passes an unchanged report while
flagging a seeded growth regression.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time
from typing import Dict

NUM_OPS = ("lu", "potrf", "mixed", "qr")
CONDEST_PARITY_RTOL = 1e-6  # dist vs single-chip probe sequences agree
MARGIN_RTOL = 1e-3          # seeded 1/cond margin reproduction

_N_DEFAULT = 48
_NB_DEFAULT = 8


def _mesh_default():
    import jax

    from ..parallel import make_mesh

    devs = jax.devices("cpu")
    if len(devs) < 8:
        raise RuntimeError(
            f"numwatch needs 8 CPU devices, have {len(devs)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    return make_mesh(2, 4, devices=devs[:8])


def _dist(a, mesh, nb, pad=True):
    import jax.numpy as jnp

    from ..parallel.dist import from_dense

    return from_dense(jnp.asarray(a), mesh, nb, diag_pad_one=pad)


def _run_lu(n, nb, mesh, impl) -> Dict[str, float]:
    """Monitored partial-pivot + no-pivot LU gauges and the distributed
    general condition estimate vs its single-chip reference."""
    import jax.numpy as jnp

    from ..linalg.lu import getrf_array
    from ..linalg.norms import gecondest
    from ..obs import numerics
    from ..ops.tile_ops import genorm
    from ..parallel.dist import from_dense
    from ..parallel.dist_aux import gecondest_dist, norm_dist
    from ..parallel.dist_lu import getrf_nopiv_dist, getrf_pp_dist
    from ..types import Norm
    from ..utils.testing import generate

    vals: Dict[str, float] = {}
    # Wilkinson: worst-case growth, exactly 2^{n-1} under partial pivoting
    w = generate("wilkinson", n)
    _lu, _perm, info = getrf_pp_dist(
        _dist(w, mesh, nb), bcast_impl=impl, num_monitor="on")
    assert int(info) == 0
    vals["num.lu_growth_wilkinson"] = numerics.last_gauges("getrf_pp")["growth"]
    # benign diagonally-dominant input through the no-pivot kernel: the
    # growth gauge must stay O(1) (the false-positive bound)
    d = generate("dominant", n, seed=1)
    _lu2, info2 = getrf_nopiv_dist(
        _dist(d, mesh, nb), bcast_impl=impl, num_monitor="on")
    assert int(info2) == 0
    vals["num.lu_growth_dominant"] = numerics.last_gauges("getrf_nopiv")["growth"]

    # distributed Hager-Higham condest over the factored tiles vs the
    # single-chip estimator on the same matrix (prescribed cond via svd)
    g = generate("svd", n, seed=2, cond=1e6)
    gd = _dist(g, mesh, nb)
    lu, perm, info3 = getrf_pp_dist(gd, bcast_impl=impl)
    assert int(info3) == 0
    anorm = norm_dist(Norm.One, from_dense(jnp.asarray(g), mesh, nb))
    rc_d = float(gecondest_dist(lu, perm, anorm, bcast_impl=impl))
    rc_s = float(gecondest(Norm.One, getrf_array(jnp.asarray(g)),
                           genorm(Norm.One, jnp.asarray(g))))
    vals["num.gecondest_cond"] = 1.0 / rc_d
    vals["num.gecondest_match_rel"] = abs(rc_d - rc_s) / rc_s
    return vals


def _run_potrf(n, nb, mesh, impl) -> Dict[str, float]:
    """Monitored Cholesky margin gauges (benign + seeded near-breakdown)
    and the distributed SPD condition estimate vs single-chip."""
    import jax.numpy as jnp

    from ..linalg.chol import potrf_array
    from ..linalg.norms import pocondest
    from ..obs import numerics
    from ..ops.tile_ops import genorm
    from ..parallel.dist import from_dense
    from ..parallel.dist_aux import norm_dist, pocondest_dist
    from ..parallel.dist_chol import potrf_dist
    from ..types import Norm, Uplo
    from ..utils.testing import generate

    vals: Dict[str, float] = {}
    well = generate("spd", n, seed=3)
    _l, info = potrf_dist(_dist(well, mesh, nb), bcast_impl=impl,
                          num_monitor="on")
    assert int(info) == 0
    gw = numerics.last_gauges("potrf")
    vals["num.chol_margin_well"] = gw["margin"]
    # near-singular diagonal: the Schur margin dips to exactly 1/cond
    near = generate("spd_neardiag", n, seed=4, cond=1e8)
    _l2, info2 = potrf_dist(_dist(near, mesh, nb), bcast_impl=impl,
                            num_monitor="on")
    assert int(info2) == 0
    gn = numerics.last_gauges("potrf")
    vals["num.chol_margin_near"] = gn["margin"]
    vals["num.chol_diag_min_near"] = gn["diag_min"]

    ill = generate("spd_svd", n, seed=5, cond=1e5)
    ld, info3 = potrf_dist(_dist(ill, mesh, nb), bcast_impl=impl)
    assert int(info3) == 0
    anorm = norm_dist(Norm.One, from_dense(jnp.asarray(ill), mesh, nb))
    rc_d = float(pocondest_dist(ld, anorm, bcast_impl=impl))
    f, _ = potrf_array(jnp.asarray(ill), Uplo.Lower)
    rc_s = float(pocondest(Norm.One, f, genorm(Norm.One, jnp.asarray(ill))))
    vals["num.pocondest_cond"] = 1.0 / rc_d
    vals["num.pocondest_match_rel"] = abs(rc_d - rc_s) / rc_s
    return vals


def _run_mixed(n, nb, mesh, impl) -> Dict[str, float]:
    """The health-aware mixed ladder end to end: a pathological input
    must ROUTE to the GMRES tier on measured condest (not burn IR
    iterations), a healthy input must converge in IR with its
    (||r||, ||x||) trajectory exported."""
    import jax.numpy as jnp
    import numpy as np

    from ..obs import REGISTRY, numerics
    from ..parallel.drivers import gesv_mesh
    from ..types import Option
    from ..utils.testing import generate

    rng = np.random.default_rng(6)
    b = rng.standard_normal((n, 2))
    opts = {Option.NumMonitor: "on", Option.BcastImpl: impl}
    vals: Dict[str, float] = {}

    # pathological: prescribed cond 1e8 >> CONDEST_THRESHOLD
    ill = generate("svd", n, seed=7, cond=1e8)
    routed0 = REGISTRY.counter_value("num.routed_gmres", op="gesv")
    x, info = gesv_mesh(jnp.asarray(ill), jnp.asarray(b), mesh, nb, opts=opts)
    assert int(info) == 0
    vals["num.routed_gmres"] = (
        REGISTRY.counter_value("num.routed_gmres", op="gesv") - routed0)
    vals["num.condest_cond"] = numerics.last_gauges("gesv").get("cond", 0.0)
    r = np.asarray(b) - ill @ np.asarray(x)
    scale = np.abs(ill).sum(axis=1).max() * max(np.abs(np.asarray(x)).max(), 1e-300)
    vals["num.mixed_ill_rel_resid"] = float(np.abs(r).max() / scale)

    # healthy: IR converges; the carried trajectory lands in the report
    wellm = generate("dominant", n, seed=8)
    x2, info2 = gesv_mesh(jnp.asarray(wellm), jnp.asarray(b), mesh, nb,
                          opts=opts)
    assert int(info2) == 0
    hist = numerics.last_history("gesv")
    vals["num.ir_history_len_well"] = float(len(hist))
    vals["num.ir_iters_well"] = max(float(len(hist)) - 1, 0.0)
    if len(hist) >= 2:
        # monotone-convergence shape: the trajectory's last residual is
        # finite and far below its first (a stall would flatten this)
        vals["num.ir_history_drop_well"] = (
            hist[0][0] / max(hist[-1][0], 1e-300))
    return vals


def _run_qr(n, nb, mesh, impl) -> Dict[str, float]:
    """The QR/eig-chain orthogonality-loss gauges (ISSUE 15): the FUSED
    monitored geqrf loop vs the checkpointed segment chain on the same
    operand (bitwise-equal by the exact-max-fold contract — the
    acceptance bound, exported as a 0.0 mismatch key), plus the first
    he2hb (two-stage eig) gauge."""
    import jax.numpy as jnp
    import numpy as np

    from ..ft import ckpt
    from ..obs import numerics
    from ..parallel.dist import from_dense
    from ..parallel.dist_qr import geqrf_dist
    from ..parallel.dist_twostage import he2hb_dist
    from ..utils.testing import generate

    vals: Dict[str, float] = {}
    rng = np.random.default_rng(9)
    a = rng.standard_normal((n, n))
    ad = from_dense(jnp.asarray(a), mesh, nb, diag_pad_one=False)
    geqrf_dist(ad, bcast_impl=impl, num_monitor="on")
    fused = numerics.last_gauges("geqrf")["qr_orth_loss"]
    vals["num.qr_orth_margin_fused"] = fused
    numerics.clear_last("geqrf")
    ckpt.geqrf_ckpt(ad, every=2, bcast_impl=impl, num_monitor="on")
    chained = numerics.last_gauges("geqrf")["qr_orth_loss"]
    vals["num.qr_orth_margin_ckpt"] = chained
    # the acceptance bound: fused == checkpointed, BITWISE (max folds
    # are exact) — committed as an always-0.0 lower-better key so any
    # divergence fails the gate outright
    vals["num.qr_orth_fused_vs_ckpt_err"] = abs(fused - chained)

    # an ill-conditioned operand must not trip the gauge (the identity
    # measures the PANEL's internal consistency, not cond(A)) — but it
    # must stay finite and recorded
    ill = generate("svd", n, seed=10, cond=1e10)
    geqrf_dist(_dist(ill, mesh, nb, pad=False), bcast_impl=impl,
               num_monitor="on")
    vals["num.qr_orth_margin_ill"] = numerics.last_gauges(
        "geqrf")["qr_orth_loss"]

    # the first eig-chain gauge: he2hb's replicated panel QR margin
    spd = generate("spd", n, seed=11)
    he2hb_dist(_dist(spd, mesh, nb, pad=False), bcast_impl=impl,
               num_monitor="on")
    vals["num.he2hb_orth_margin"] = numerics.last_gauges(
        "he2hb")["he2hb_orth_loss"]
    return vals


_RUNNERS = {"lu": _run_lu, "potrf": _run_potrf, "mixed": _run_mixed,
            "qr": _run_qr}


def run_numwatch(op: str, n: int = _N_DEFAULT, nb: int = _NB_DEFAULT,
                 bcast_impl: str = "ring", mesh=None) -> dict:
    """One numwatch pass.  Returns the RunReport dict; all non-runtime
    ``num.*`` values are bitwise-reproducible at fixed (n, nb, grid)."""
    from . import report
    from ..parallel.mesh import mesh_shape

    if op not in _RUNNERS:
        raise ValueError(f"unknown numwatch op {op!r}; expected {NUM_OPS}")
    if mesh is None:
        mesh = _mesh_default()
    p, q = mesh_shape(mesh)
    t0 = time.perf_counter()
    values = _RUNNERS[op](n, nb, mesh, bcast_impl)
    values[f"num.{op}_runtime_wall_s"] = time.perf_counter() - t0
    rep = report.make_report(
        f"numwatch_{op}",
        config={"op": op, "n": n, "nb": nb, "grid": f"{p}x{q}",
                "bcast_impl": bcast_impl},
        values=values,
        include_spans=False,
    )
    # the deterministic gauge values live ONLY in the headline num.* keys
    # above; the process-global num section (whatever else this process
    # monitored) would re-enter the gate as un-ignorable num_* keys, so
    # a numwatch artifact carries it empty (the memwatch mem pattern)
    rep["num"] = {}
    return rep


def write_num_report(path: str, rep: dict) -> str:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rep, f, indent=1)
    return path


def _smoke(out_dir: str) -> int:
    import contextlib
    import io

    from . import numerics, perfetto, report

    os.makedirs(out_dir, exist_ok=True)
    failures = []
    mesh = _mesh_default()
    n = _N_DEFAULT
    for op in NUM_OPS:
        rep = run_numwatch(op, n=n, nb=_NB_DEFAULT, bcast_impl="ring",
                           mesh=mesh)
        errs = report.validate_report(rep)
        if errs:
            failures.append(f"{op} schema: {errs[:4]}")
        vals = rep["values"]

        if op == "lu":
            grow = vals["num.lu_growth_wilkinson"]
            if grow != 2.0 ** (n - 1):
                failures.append(
                    f"lu: Wilkinson growth {grow:.6g} != closed-form "
                    f"2^{n - 1} = {2.0 ** (n - 1):.6g}")
            if grow <= numerics.GROWTH_THRESHOLD:
                failures.append(
                    f"lu: Wilkinson growth {grow:.3g} did not trip the "
                    f"alarm threshold {numerics.GROWTH_THRESHOLD:.3g}")
            if vals["num.lu_growth_dominant"] > 4.0:
                failures.append(
                    f"lu: benign growth {vals['num.lu_growth_dominant']:.3g}"
                    " > 4 (false-positive bound)")
            if vals["num.gecondest_match_rel"] > CONDEST_PARITY_RTOL:
                failures.append(
                    f"lu: distributed gecondest off single-chip by "
                    f"{vals['num.gecondest_match_rel']:.2e} "
                    f"(> {CONDEST_PARITY_RTOL:.0e})")
        if op == "potrf":
            near = vals["num.chol_margin_near"]
            if abs(near - 1e-8) > MARGIN_RTOL * 1e-8:
                failures.append(
                    f"potrf: seeded near-breakdown margin {near:.6g} != "
                    "the planted 1/cond = 1e-8")
            if vals["num.pocondest_match_rel"] > CONDEST_PARITY_RTOL:
                failures.append(
                    f"potrf: distributed pocondest off single-chip by "
                    f"{vals['num.pocondest_match_rel']:.2e}")
        if op == "mixed":
            if vals["num.routed_gmres"] < 1:
                failures.append(
                    "mixed: the cond-1e8 input did not health-route the "
                    "auto ladder to the GMRES tier")
            if vals["num.condest_cond"] <= numerics.CONDEST_THRESHOLD:
                failures.append(
                    f"mixed: condest {vals['num.condest_cond']:.3g} under "
                    f"the alarm threshold {numerics.CONDEST_THRESHOLD:.3g}")
            if vals["num.ir_history_len_well"] < 1:
                failures.append("mixed: no IR trajectory exported for the "
                                "healthy solve")
            # Perfetto: the convergence trajectory as a counter track
            hist = numerics.last_history("gesv")
            trace = perfetto.chrome_trace()
            trace["traceEvents"].extend(
                perfetto.numerics_counter_events(hist, op="gesv"))
            terrs = perfetto.validate_chrome_trace(trace)
            if terrs:
                failures.append(f"mixed: numerics trace invalid: {terrs[:3]}")
            if hist and not any(
                    e.get("name") == "num.ir_rnorm[gesv]"
                    for e in trace["traceEvents"]):
                failures.append("mixed: num.ir_rnorm counter track missing")
            tpath = os.path.join(out_dir, "num_mixed.trace.json")
            with open(tpath, "w") as f:
                json.dump(trace, f, indent=1)

        if op == "qr":
            if vals["num.qr_orth_fused_vs_ckpt_err"] != 0.0:
                failures.append(
                    "qr: fused geqrf gauge differs from the checkpointed "
                    f"chain's by {vals['num.qr_orth_fused_vs_ckpt_err']:.3g}"
                    " (must be bitwise-equal)")
            for key in ("num.qr_orth_margin_fused", "num.he2hb_orth_margin"):
                if not 0.0 < vals[key] < 1e-10:
                    failures.append(
                        f"qr: {key} = {vals[key]:.3g} outside the "
                        "healthy-panel eps class (0, 1e-10)")

        # cross-impl bitwise invariance: the gauges measure arithmetic
        # the broadcast lowering must not change (the acceptance bound
        # "gate green under both psum and ring" holds because the values
        # are EQUAL, not merely close)
        rep_psum = run_numwatch(op, n=n, nb=_NB_DEFAULT, bcast_impl="psum",
                                mesh=mesh)
        for k, v in vals.items():
            if "_runtime_" in k:
                continue
            if rep_psum["values"].get(k) != v:
                failures.append(
                    f"{op}: {k} differs across bcast impls "
                    f"(ring {v!r} vs psum {rep_psum['values'].get(k)!r})")

        path = os.path.join(out_dir, f"num_{op}.report.json")
        write_num_report(path, rep)

        # the gate must actually trip on a seeded accuracy regression:
        # an unchanged report passes, a 4x-grown gauge fails
        worse = copy.deepcopy(rep)
        for k in list(worse["values"]):
            if ("growth" in k or "condest_cond" in k or "cond" in k
                    or "orth_margin" in k):
                worse["values"][k] = worse["values"][k] * 4.0
        worse_path = os.path.join(out_dir, f"num_{op}.worse.json")
        with open(worse_path, "w") as f:
            json.dump(worse, f)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc_same = report.main(
                ["--check", path, path, "--ignore", "num.*_runtime_*"])
            rc_worse = report.main(
                ["--check", worse_path, path,
                 "--ignore", "num.*_runtime_*", "--threshold", "2"])
        os.remove(worse_path)
        if rc_same != 0:
            failures.append(f"{op}: --check of an unchanged num report "
                            f"exited {rc_same} (want 0)")
        if rc_worse != 1:
            failures.append(f"{op}: --check missed the seeded 4x gauge "
                            f"regression (exited {rc_worse}, want 1)")
        if failures:
            print(buf.getvalue(), end="")
        headline = {k: v for k, v in sorted(vals.items())
                    if "_runtime_" not in k}
        print(f"obs.numwatch smoke: {op} ok — "
              + ", ".join(f"{k.split('num.', 1)[1]}={v:.4g}"
                          for k, v in list(headline.items())[:4])
              + f" -> {path}")
    if failures:
        print(f"obs.numwatch smoke: FAILED with {len(failures)} problem(s):")
        for msg in failures:
            print(f"  FAIL {msg}")
        return 1
    print(f"obs.numwatch smoke: OK — reports in {out_dir}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m slate_tpu.obs.numwatch", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("op", nargs="?", choices=NUM_OPS,
                    help="numerics pass to run")
    ap.add_argument("--n", type=int, default=_N_DEFAULT)
    ap.add_argument("--nb", type=int, default=_NB_DEFAULT)
    ap.add_argument("--impl", default="ring",
                    help="bcast impl (psum|ring|doubling|auto); gauge "
                         "values are bitwise-invariant across impls")
    ap.add_argument("--out", default=None,
                    help="report path (default artifacts/obs/"
                         "num_<op>.report.json; for --smoke: the "
                         "artifact directory)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI acceptance run (all three ops at the tier-1 "
                         "shape, psum/ring bitwise cross-check, seeded "
                         "regression gate trip)")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_enable_x64", True)  # f64 gauges + mixed ladder

    if args.smoke:
        return _smoke(args.out or os.path.join("artifacts", "obs"))
    if not args.op:
        ap.error("give an op to run or --smoke")
    rep = run_numwatch(args.op, n=args.n, nb=args.nb, bcast_impl=args.impl)
    out = args.out or os.path.join("artifacts", "obs",
                                   f"num_{args.op}.report.json")
    write_num_report(out, rep)
    for k, v in sorted(rep["values"].items()):
        print(f"  {k:<36} {v:.6g}")
    print(f"  wrote {out}")
    return 0


if __name__ == "__main__":
    # runpy loads this file as __main__; delegate to the canonical module
    # instance (the obs.flight pattern) so shared module state is single
    from slate_tpu.obs import numwatch as _canonical

    sys.exit(_canonical.main())
