"""Mixed-precision smoke: the CI acceptance run for the mixed solve path.

Solves one general and one SPD f64 system on the 8-device CPU mesh
through the DEFAULT drivers (``gesv_mesh``/``posv_mesh`` — i.e. the
Option.MixedPrecision=auto ladder of parallel/dist_refine.py) and
asserts the acceptance surface end to end:

- ``off`` is jaxpr-identical to the direct f64 path (trace assert);
- ``auto`` factors in f32, converges, and the returned x meets the
  refine.py residual gate ||r|| <= ||x|| ||A|| eps sqrt(n);
- the Ozaki int8 residual lowering meets the same gate;
- the GMRES-IR escalation tier converges on its own tolerance;
- the ``ir.*`` counters land in a schema-valid RunReport.

The smoke reads ``SLATE_TPU_BCAST_IMPL`` like every mesh kernel, so CI
re-runs it under the ring broadcast to prove the option actually reaches
the f32 factor and the refinement loop's residual SUMMA.

Usage::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m slate_tpu.parallel.mixed_smoke [--out artifacts/mixed] \
        [--n 96] [--nb 16]
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def run_smoke(out_dir: str, n: int = 96, nb: int = 16) -> int:
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices("cpu")
    if len(devs) < 8:
        print(f"mixed_smoke: need 8 CPU devices, have {len(devs)} — set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8")
        return 2

    from ..obs import report, reset
    from ..types import Option
    from . import make_mesh
    from .drivers import (
        _gesv_mesh_plain,
        _posv_mesh_plain,
        gesv_mesh,
        gesv_mixed_gmres_mesh,
        posv_mesh,
    )

    reset()
    mesh = make_mesh(2, 4, devices=devs[:8])
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n)) + n * np.eye(n))
    g = rng.standard_normal((n, n))
    spd = jnp.asarray(g @ g.T / n + 2 * np.eye(n))
    b = jnp.asarray(rng.standard_normal((n, 2)))
    failures = []

    def check(name, ok, detail=""):
        if not ok:
            failures.append(f"{name}: {detail}")

    def gate(a_, x_, b_):
        a_, x_, b_ = map(np.asarray, (a_, x_, b_))
        r = b_ - a_ @ x_
        rn = np.abs(r).sum(axis=1).max()
        return rn, rn <= (np.abs(x_).sum(axis=1).max()
                          * np.abs(a_).sum(axis=1).max()
                          * np.finfo(np.float64).eps * np.sqrt(n))

    # (1) the off switch: trace-identical to the direct f64 path
    off = {Option.MixedPrecision: "off"}
    j_off = jax.make_jaxpr(lambda x, y: gesv_mesh(x, y, mesh, nb, opts=off))(a, b)
    j_pl = jax.make_jaxpr(lambda x, y: _gesv_mesh_plain(x, y, mesh, nb, opts=off))(a, b)
    check("off-identity", str(j_off) == str(j_pl),
          "MixedPrecision=off is not jaxpr-identical to the direct path")

    # (2) the default ladder: f32 factor + fused refinement meets the gate
    vals = {}
    x, info = gesv_mesh(a, b, mesh, nb)
    rn, ok = gate(a, x, b)
    vals["gesv_mixed_resid"] = rn
    check("gesv-auto", int(info) == 0 and ok, f"info={int(info)} rnorm={rn:.3g}")

    xp, infop = posv_mesh(spd, b, mesh, nb)
    rnp, okp = gate(spd, xp, b)
    vals["posv_mixed_resid"] = rnp
    check("posv-auto", int(infop) == 0 and okp,
          f"info={int(infop)} rnorm={rnp:.3g}")

    # (3) the Ozaki int8 residual lowering meets the same gate
    xo, infoo = gesv_mesh(a, b, mesh, nb, opts={Option.ResidualImpl: "ozaki"})
    rno, oko = gate(a, xo, b)
    vals["gesv_ozaki_resid"] = rno
    check("gesv-ozaki", int(infoo) == 0 and oko,
          f"info={int(infoo)} rnorm={rno:.3g}")

    # (4) the GMRES-IR escalation tier converges on its own tolerance
    xg, rng_, infog = gesv_mixed_gmres_mesh(a, b[:, :1], mesh, nb)
    tol = (np.finfo(np.float64).eps * np.sqrt(n)
           * np.linalg.norm(np.asarray(b[:, :1]), axis=0).max())
    vals["gesv_gmres_resid"] = float(rng_)
    check("gesv-gmres", int(infog) == 0 and float(rng_) <= tol
          and np.isfinite(np.asarray(xg)).all(),
          f"info={int(infog)} rnorm={float(rng_):.3g} tol={tol:.3g}")

    # (5) counters + RunReport: the ir section must carry the solves
    os.makedirs(out_dir, exist_ok=True)
    rep_path = os.path.join(out_dir, "mixed_report.json")
    report.write_report(
        rep_path, name="mixed_smoke",
        config={"n": n, "nb": nb, "grid": "2x4",
                "bcast_impl": os.environ.get("SLATE_TPU_BCAST_IMPL", "auto")},
        values=vals,
    )
    with open(rep_path) as fh:
        rep_doc = json.load(fh)
    errs = report.validate_report(rep_doc)
    check("report", not errs, f"schema: {errs}")
    ir = rep_doc.get("ir", {})
    check("report-ir", ir.get("solves", 0) >= 3
          and ir.get("converged", 0) >= 3 and ir.get("gmres_solves", 0) >= 1,
          f"RunReport ir section {ir}")

    if failures:
        print(f"mixed_smoke: FAILED with {len(failures)} problem(s):")
        for msg in failures:
            print(f"  FAIL {msg}")
        return 1
    print(f"mixed_smoke: OK — off trace-identical; auto/ozaki at the "
          f"residual gate; GMRES tier converged; ir counters {ir}; "
          f"report {rep_path}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m slate_tpu.parallel.mixed_smoke")
    ap.add_argument("--out", default=os.path.join("artifacts", "mixed"))
    ap.add_argument("--n", type=int, default=96)
    ap.add_argument("--nb", type=int, default=16)
    args = ap.parse_args(argv)
    return run_smoke(args.out, args.n, args.nb)


if __name__ == "__main__":
    sys.exit(main())
