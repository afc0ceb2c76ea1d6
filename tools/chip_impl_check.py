#!/usr/bin/env python3
"""Check the fused Pallas lowerings against ``xla`` on the chip.

- ``Option.UpdateImpl``: on a TPU backend ``auto`` sends each mesh
  k-loop's trailing update to the fused kernels (``ops/pallas_ops.py``)
  when the broadcast panels fit the VMEM cap — at small local tile grids,
  not at the sizes ``chip_smoke.py`` runs.
- The trailing-update kernels alone, with a random per-tile keep mask,
  against the same select in XLA: each grid step must apply its own
  tile's mask.

Each driver runs at such a shape on a mesh over every visible device
(1x1 on one chip, 2x2 on four) under the fused lowering and under
``xla``; the check asserts that the fused run traced a ``pallas_call``
and the xla run did not, that both factors pass the reconstruction gate,
and that they agree to ~f32 rounding.  One JSON line per check; the last
line names the device.

    python tools/chip_impl_check.py [--n 2048] [--nb 256] [--update-impl auto]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--nb", type=int, default=256)
    ap.add_argument("--update-impl", default="auto",
                    help="the update lowering checked against xla (pallas "
                         "to rehearse on the CPU, where auto means xla)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from slate_tpu.parallel import (
        gemm_mesh, getrf_nopiv_mesh, make_mesh, potrf_mesh, to_dense,
    )
    from slate_tpu.types import Option
    from slate_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devs = jax.devices()
    p = 2 if len(devs) >= 4 else 1
    mesh = make_mesh(p, p, devices=devs[: p * p])
    n, nb = args.n, args.nb
    rng = np.random.default_rng(0)
    g = rng.standard_normal((n, n)).astype(np.float32)
    spd = (g + g.T) / np.float32(2 * np.sqrt(n)) + 3 * np.eye(n, dtype=np.float32)
    diag_dom = g / np.float32(np.sqrt(n)) + 4 * np.eye(n, dtype=np.float32)
    bmat = rng.standard_normal((n, n)).astype(np.float32)

    def chol(a, opts):
        l, info = potrf_mesh(a, mesh, nb, opts)
        return jnp.tril(to_dense(l))

    def lu(a, opts):
        f, info = getrf_nopiv_mesh(a, mesh, nb, opts)
        return to_dense(f)

    def gemm(a, opts):
        return gemm_mesh(1.0, a, jnp.asarray(bmat), mesh, nb, opts=opts)

    def recon_error(name, a, out):
        out = np.asarray(out, np.float64)
        a = np.asarray(a, np.float64)
        if name == "potrf_mesh":
            r = out @ out.T - a
        elif name == "getrf_nopiv_mesh":
            lo = np.tril(out, -1) + np.eye(n)
            r = lo @ np.triu(out) - a
        else:
            r = out - a @ np.asarray(bmat, np.float64)
        return float(np.abs(r).max() / np.abs(a).max())

    ok = True
    for name, fn, a in (("potrf_mesh", chol, spd), ("getrf_nopiv_mesh", lu, diag_dom),
                        ("gemm_mesh", gemm, diag_dom)):
        a = jnp.asarray(a)
        res = {}
        for impl in (args.update_impl, "xla"):
            opts = {Option.UpdateImpl: impl}
            jaxpr = str(jax.make_jaxpr(lambda x: fn(x, opts))(a))
            res[impl] = (np.asarray(fn(a, opts)), "pallas_call" in jaxpr)
        (fused_out, fused), (xla, xla_fused) = res[args.update_impl], res["xla"]
        diff = float(np.abs(fused_out - xla).max()) / float(np.abs(xla).max())
        errs = {k: recon_error(name, a, v[0]) for k, v in res.items()}
        tol = 64 * n * float(np.finfo(np.float32).eps)
        good = fused and not xla_fused and diff <= 1e-5 and max(errs.values()) <= tol
        ok &= good
        print(json.dumps({"option": Option.UpdateImpl.value, "impl": args.update_impl,
                          "driver": name, "n": n, "nb": nb, "grid": f"{p}x{p}",
                          "impl_traced_pallas": fused, "xla_traced_pallas": xla_fused,
                          "max_rel_diff_vs_xla": diff, "diff_gate": 1e-5,
                          "recon_error": errs, "recon_gate": tol, "ok": good}),
              flush=True)

    from slate_tpu.ops import pallas_ops as po

    I, J = 4, 3
    view = jnp.asarray(rng.standard_normal((I, J, nb, nb)), jnp.float32)
    pan = jnp.asarray(rng.standard_normal((I, nb, nb)), jnp.float32)
    other = jnp.asarray(rng.standard_normal((J, nb, nb)), jnp.float32)
    keep = jnp.asarray(rng.random((I, J)) < 0.5)
    hi = jax.lax.Precision.HIGHEST
    refs = {
        "chol_trailing_update_pallas": (po.chol_trailing_update_pallas,
                                        jnp.einsum("iab,jcb->ijac", pan, other, precision=hi)),
        "lu_trailing_update_pallas": (po.lu_trailing_update_pallas,
                                      jnp.einsum("iab,jbc->ijac", pan, other, precision=hi)),
    }
    for kname, (kern, upd) in refs.items():
        want = np.asarray(view - jnp.where(keep[:, :, None, None], upd, 0.0))
        got = np.asarray(jax.jit(kern)(view, pan, other, keep))
        diff = float(np.abs(got - want).max() / np.abs(want).max())
        good = diff <= 1e-5
        ok &= good
        print(json.dumps({"kernel": kname, "nb": nb, "grid": [I, J],
                          "masked_tiles": int((~np.asarray(keep)).sum()),
                          "max_rel_diff_vs_xla": diff, "diff_gate": 1e-5, "ok": good}),
              flush=True)

    d = devs[0]
    print(json.dumps({"ok": bool(ok), "device": {"platform": d.platform,
                                                  "kind": d.device_kind,
                                                  "count": len(devs)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
