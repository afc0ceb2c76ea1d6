#!/usr/bin/env python3
"""North-star size sweep on the real chip (VERDICT round-1 item 2).

Runs each routine in its own subprocess, one after another (OOM/timeout
isolation; the parent never touches JAX, so each child owns the chip),
one JSON line per result, collected in ``chiprun_out/northstar_sweep.json``.
Usage, on a machine with the chip:

    python tools/northstar_sweep.py [--only potrf_scan,getrf_scan]

Timing note: single timed execution after a warm-up compile, ended by a
host transfer of the result.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

CASES = [
    # round 5: artifact-first ordering — the rows that verify round 4's
    # claims (VERDICT r5 item 1) run before the f32 refreshes
    ("heev_vec", 8192, 3600),
    ("getrf_f64", 16384, 7200),
    ("heev_vec", 16384, 7200),
    ("svd", 16384, 7200),
    ("svd_vec", 16384, 9000),
    ("potrf_f64", 16384, 7200),
    # 32768 runs the STAGED per-panel-program form with a donated carry:
    # the fused program's ~5 live matrix copies OOM v5e at 8 GB/matrix
    # (measured r5); per-panel programs cap peak at ~one matrix.
    ("potrf_f64", 32768, 9000),
    ("getrf_scan", 32768, 900),
    ("getrf_scan", 16384, 600),
    ("potrf_scan", 32768, 900),
    ("potrf_scan", 16384, 600),
    ("geqrf", 32768, 900),
    ("geqrf", 16384, 600),
    ("gemm_f32", 16384, 600),
    # no-vector eig/SVD chains + remaining driver families
    ("heev", 8192, 3600),
    ("svd", 8192, 3600),
    ("svd_vec", 8192, 3600),
    ("heev", 16384, 5400),
    ("heev", 4096, 1800),
    ("svd", 4096, 1800),
    ("hesv", 4096, 1800),
    ("pbsv", 16384, 900),
    ("gbsv", 16384, 900),
]

CHILD = r"""
import json, time, sys, os
import numpy as np, jax, jax.numpy as jnp
sys.path.insert(0, {root!r})
# persistent compile cache shared with bench.py (big programs compile once)
from slate_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache(min_compile_secs=10.0)
routine, n = {routine!r}, {n}
key = jax.random.PRNGKey(0)

def emit(secs, gflops, check, ok):
    print("RESULT " + json.dumps({{
        "routine": routine, "n": n,
        "dtype": "f64" if routine.endswith("_f64") else "f32",
        "seconds": secs, "gflops": gflops,
        "device": jax.devices()[0].device_kind,
        "check": check, "ok": bool(ok)}}), flush=True)

if routine == "getrf_scan":
    from slate_tpu.linalg.lu import getrf_scan_array
    a = jax.random.normal(key, (n, n), jnp.float32) / 64
    f = jax.jit(lambda x: getrf_scan_array(x))
    out = f(a); info = int(out.info)
    d0 = float(jnp.abs(jnp.diagonal(out.lu)).min())
    del out
    _ = float(jnp.sum(a[:1, :4]))  # drain the queue before timing
    t0 = time.perf_counter()
    out = f(a)
    info2 = int(out.info)  # host sync
    t1 = time.perf_counter()
    ok = info == 0 and np.isfinite(d0) and d0 > 0
    emit(t1 - t0, 2 / 3 * n**3 / (t1 - t0) / 1e9, f"info={{info}} dmin={{d0:.2e}}", ok)
elif routine == "potrf_scan":
    from slate_tpu.linalg.chol import _potrf_scan
    # Wigner shift: spectrum of sym/sqrt(n) is in [-2, 2], so 3I + W is
    # SPD without materializing a Gram product; input is donated and the
    # program AOT-compiled so peak HBM stays ~2 matrices (n = 32768 = 4GB)
    f = jax.jit(_potrf_scan, donate_argnums=0)
    comp = f.lower(jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    build = jax.jit(
        lambda x: (x + x.T) / (2.0 * np.sqrt(n))
        + 3.0 * jnp.eye(n, dtype=jnp.float32),
        donate_argnums=0,
    )
    # warm run first: the timed run must not pay first-execution costs
    aw = build(jax.random.normal(jax.random.PRNGKey(7), (n, n), jnp.float32))
    lw = comp(aw)
    _ = float(jnp.real(jnp.diagonal(lw)).min())
    del lw
    a = build(jax.random.normal(key, (n, n), jnp.float32))
    _ = float(jnp.sum(a[:1, :4]))  # drain the queue before timing
    t0 = time.perf_counter()
    l = comp(a)
    dmin = float(jnp.real(jnp.diagonal(l)).min())
    t1 = time.perf_counter()
    emit(t1 - t0, n**3 / 3 / (t1 - t0) / 1e9, f"dmin={{dmin:.2e}}",
         np.isfinite(dmin) and dmin > 0)
elif routine == "geqrf":
    from slate_tpu.linalg.qr import geqrf_scan_array
    f = jax.jit(lambda x: geqrf_scan_array(x).r, donate_argnums=0)
    comp = f.lower(jax.ShapeDtypeStruct((n, n), jnp.float32)).compile()
    aw = jax.random.normal(jax.random.PRNGKey(7), (n, n), jnp.float32)
    rw = comp(aw)
    _ = float(jnp.abs(jnp.diagonal(rw)).min())  # warm run
    del rw
    a = jax.random.normal(key, (n, n), jnp.float32)
    _ = float(jnp.sum(a[:1, :4]))  # drain the queue before timing
    t0 = time.perf_counter()
    r = comp(a)
    dmin = float(jnp.abs(jnp.diagonal(r)).min())
    t1 = time.perf_counter()
    emit(t1 - t0, 4 / 3 * n**3 / (t1 - t0) / 1e9, f"rmin={{dmin:.2e}}",
         np.isfinite(dmin) and dmin > 0)
elif routine == "gemm_f32":
    from slate_tpu.ops.matmul import matmul
    a = jax.random.normal(key, (n, n), jnp.float32)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32)
    f = jax.jit(lambda a, b: jnp.sum(jnp.abs(matmul(a, b)[:1])))
    float(f(a, b))
    t0 = time.perf_counter()
    v = float(f(a + 1e-6, b))
    t1 = time.perf_counter()
    emit(t1 - t0, 2 * n**3 / (t1 - t0) / 1e9, f"sum={{v:.3e}}", np.isfinite(v))
elif routine == "heev":
    # staged driver: one XLA program per phase (a single fused program
    # for all phases faults the TPU runtime near n = 8192)
    from slate_tpu.linalg.eig import heev_staged
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = (g + g.T) / 2
    del g
    f = lambda x: heev_staged(x, want_vectors=False)
    t0 = time.perf_counter()
    w = f(a)
    wmax = float(jnp.abs(w).max())
    t1 = time.perf_counter()
    # Weyl sanity: spectral radius of a Wigner matrix ~ 2 sqrt(n) * sigma
    ok = np.isfinite(wmax) and abs(wmax / (2 * np.sqrt(n) * np.sqrt(0.5)) - 1) < 0.2
    emit(t1 - t0, 4 / 3 * n**3 / (t1 - t0) / 1e9, f"wmax={{wmax:.3e}}", ok)
elif routine == "svd":
    from slate_tpu.linalg.svd import svd_staged
    a = jax.random.normal(key, (n, n), jnp.float32)
    f = lambda x: svd_staged(x, want_vectors=False)
    t0 = time.perf_counter()
    s = f(a)
    smax = float(s.max())
    t1 = time.perf_counter()
    ok = np.isfinite(smax) and abs(smax / (2 * np.sqrt(n)) - 1) < 0.2
    emit(t1 - t0, 8 / 3 * n**3 / (t1 - t0) / 1e9, f"smax={{smax:.3e}}", ok)
elif routine == "heev_vec":
    from slate_tpu.linalg.eig import heev_staged
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = (g + g.T) / 2
    del g
    t0 = time.perf_counter()
    w, z = heev_staged(a, want_vectors=True)
    wmax = float(jnp.abs(w).max())
    t1 = time.perf_counter()
    idx = np.arange(0, n, max(1, n // 64))
    zc = np.asarray(z[:, idx]); wc = np.asarray(w)[idx]
    an = np.asarray(a)
    resid = float(np.abs(an @ zc - zc * wc).max() / max(wmax, 1e-30))
    orth = float(np.abs(zc.T @ zc - np.eye(len(idx))).max())
    ok = resid < 5e-5 and orth < 5e-4
    emit(t1 - t0, 4 / 3 * n**3 / (t1 - t0) / 1e9,
         f"resid={{resid:.2e}} orth={{orth:.2e}}", ok)
elif routine == "svd_vec":
    from slate_tpu.linalg.svd import svd_staged
    a = jax.random.normal(key, (n, n), jnp.float32)
    t0 = time.perf_counter()
    u, s, vh = svd_staged(a)
    smax = float(s.max())
    t1 = time.perf_counter()
    idx = np.arange(0, n, max(1, n // 64))
    un = np.asarray(u[:, idx]); vn = np.asarray(vh[idx, :]); sn = np.asarray(s)[idx]
    an = np.asarray(a)
    resid = float(np.abs(an @ vn.conj().T - un * sn).max() / smax)
    orth = float(np.abs(un.T @ un - np.eye(len(idx))).max())
    ok = resid < 5e-5 and orth < 5e-4
    emit(t1 - t0, 8 / 3 * n**3 / (t1 - t0) / 1e9,
         f"resid={{resid:.2e}} orth={{orth:.2e}}", ok)
elif routine == "hesv":
    # symmetric-indefinite solve (unitary-congruence Q T Q^H + pivoted
    # gtsv, linalg/indefinite.py) — first on-chip datapoint (VERDICT r4
    # item 9); flop formula matches the driver's documented ~4x Aasen cost
    from slate_tpu.linalg import hesv_array
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = (g + g.T) / 2
    del g
    b = jax.random.normal(jax.random.PRNGKey(1), (n, 2), jnp.float32)
    x, fac, info = hesv_array(a, b)
    _ = float(jnp.sum(jnp.abs(x[:1])))  # warm + sync
    _ = float(jnp.sum(a[:1, :4]))
    t0 = time.perf_counter()
    x, fac, info = hesv_array(a + 1e-6, b)
    _ = float(jnp.sum(jnp.abs(x[:1])))
    t1 = time.perf_counter()
    an, xn, bn = np.asarray(a + 1e-6), np.asarray(x), np.asarray(b)
    resid = float(np.abs(an @ xn - bn).max()
                  / (np.abs(an).max() * np.abs(xn).max() * n + np.abs(bn).max()))
    ok = int(info) == 0 and resid < 100 * n * 1.2e-7
    emit(t1 - t0, 4 * n**3 / 3 / (t1 - t0) / 1e9, f"resid={{resid:.2e}}", ok)
elif routine == "pbsv":
    # SPD band solve, windowed O(n kd^2) path (VERDICT r4 item 9)
    from slate_tpu.linalg import pbsv_array
    kd = 512
    i = jnp.arange(n)
    band = (jnp.abs(i[:, None] - i[None, :]) <= kd)
    g = jax.random.normal(key, (n, n), jnp.float32)
    a = jnp.where(band, (g + g.T) / 2, 0) + 3 * kd * jnp.eye(n, dtype=jnp.float32)
    del g, band
    b = jax.random.normal(jax.random.PRNGKey(1), (n, 2), jnp.float32)
    x, fac, info = pbsv_array(a, b, kd)
    _ = float(jnp.sum(jnp.abs(x[:1])))
    _ = float(jnp.sum(a[:1, :4]))
    t0 = time.perf_counter()
    x, fac, info = pbsv_array(a + 1e-6 * jnp.eye(n, dtype=jnp.float32), b, kd)
    _ = float(jnp.sum(jnp.abs(x[:1])))
    t1 = time.perf_counter()
    an = np.asarray(a) + 1e-6 * np.eye(n, dtype=np.float32)
    xn, bn = np.asarray(x), np.asarray(b)
    resid = float(np.abs(an @ xn - bn).max()
                  / (np.abs(an).max() * np.abs(xn).max() * n + np.abs(bn).max()))
    ok = int(info) == 0 and resid < 100 * n * 1.2e-7
    # ~n kd^2 factor flops + 4 n kd nrhs solve flops (windowed band path)
    emit(t1 - t0, n * kd * (kd + 8.0) / (t1 - t0) / 1e9,
         f"kd={{kd}} resid={{resid:.2e}}", ok)
elif routine == "gbsv":
    # general band solve, windowed partial-pivot path (VERDICT r4 item 9)
    from slate_tpu.linalg import gbsv_array
    kl = ku = 512
    i = jnp.arange(n)
    band = (i[:, None] - i[None, :] <= kl) & (i[None, :] - i[:, None] <= ku)
    a = jnp.where(band, jax.random.normal(key, (n, n), jnp.float32), 0)
    a = a + 3 * kl * jnp.eye(n, dtype=jnp.float32)
    del band
    b = jax.random.normal(jax.random.PRNGKey(1), (n, 2), jnp.float32)
    x, fac = gbsv_array(a, b, kl, ku)
    _ = float(jnp.sum(jnp.abs(x[:1])))
    _ = float(jnp.sum(a[:1, :4]))
    t0 = time.perf_counter()
    x, fac = gbsv_array(a + 1e-6 * jnp.eye(n, dtype=jnp.float32), b, kl, ku)
    _ = float(jnp.sum(jnp.abs(x[:1])))
    t1 = time.perf_counter()
    an = np.asarray(a) + 1e-6 * np.eye(n, dtype=np.float32)
    xn, bn = np.asarray(x), np.asarray(b)
    resid = float(np.abs(an @ xn - bn).max()
                  / (np.abs(an).max() * np.abs(xn).max() * n + np.abs(bn).max()))
    ok = resid < 100 * n * 1.2e-7
    emit(t1 - t0, 2.0 * n * kl * (kl + ku) / (t1 - t0) / 1e9,
         f"kl=ku={{kl}} resid={{resid:.2e}}", ok)
elif routine == "potrf_f64":
    # f64 left-looking Cholesky: digit-cached Ozaki updates at 16384
    # (potrf_array dispatch), in-place split-per-call at 32768 (cache +
    # matrix exceed HBM) — VERDICT r4 item 1
    jax.config.update("jax_enable_x64", True)
    import numpy as _np
    from slate_tpu.linalg.chol import potrf_array
    rng = _np.random.default_rng(0)
    ah = rng.standard_normal((n, n))
    ah = (ah + ah.T) / (2.0 * _np.sqrt(n)) + 3.0 * _np.eye(n)
    a = jax.device_put(ah); del ah
    _ = float(jnp.sum(a[:1, :4]))
    if n <= 20480:
        f = jax.jit(lambda x: potrf_array(x)[0])
        l = f(a)
        dmin = float(jnp.min(jnp.real(jnp.diagonal(l))))  # sync (real run)
        del l
        a2 = jax.block_until_ready(a + 1e-9)
        _ = float(jnp.sum(a2[:1, :4]))
        t0 = time.perf_counter()
        l = f(a2)
        dmin = float(jnp.min(jnp.real(jnp.diagonal(l))))
        t1 = time.perf_counter()
        # residual via matvec columns, CHUNKED: XLA's f64 emulation
        # materializes ~8 f32 copies of the big operand per dot, so a
        # whole-matrix f64 matvec OOMs next to the factor at 16384
        xv = jax.device_put(rng.standard_normal((n, 4)))
        def mv(mat_rows, x, c=2048):
            return jnp.concatenate([mat_rows[i:i+c] @ x for i in range(0, n, c)])
        lty = mv(l.T, xv)
        num = jnp.linalg.norm(mv(l, lty) - mv(a2, xv))
        den = jnp.linalg.norm(mv(a2, xv))
        resid = float(num / den)
    else:
        # STAGED per-panel programs with donation (the fused form keeps
        # ~5 live matrix copies and OOMs at 32768); input pre-symmetrized
        from slate_tpu.linalg.chol import potrf_left_looking_staged
        l = potrf_left_looking_staged(a, donate=True)
        dmin = float(jnp.min(jnp.real(jnp.diagonal(l))))
        del l, a
        ah = rng.standard_normal((n, n))
        ah = (ah + ah.T) / (2.0 * _np.sqrt(n)) + 3.0 * _np.eye(n)
        a2 = jax.device_put(ah); del ah
        _ = float(jnp.sum(a2[:1, :4]))
        t0 = time.perf_counter()
        l = potrf_left_looking_staged(a2, donate=True)
        dmin = float(jnp.min(jnp.real(jnp.diagonal(l))))
        t1 = time.perf_counter()
        resid = float("nan")  # input donated; dmin + 16384-run gate accuracy
    ok = _np.isfinite(dmin) and dmin > 0 and (not _np.isfinite(resid) or resid < 1e-12)
    emit(t1 - t0, n**3 / 3 / (t1 - t0) / 1e9,
         f"dmin={{dmin:.2e}} resid={{resid:.2e}}", ok)
elif routine == "getrf_f64":
    # f64 partial-pivot LU through the shipped dispatch: left-looking at
    # the chip-validated sizes (<= 8192), the scanned single-program form
    # past the _GETRF_LL_MAX_N gate (see lu.py — the 16384 left-looking
    # program factors wrong on chip despite every component passing)
    jax.config.update("jax_enable_x64", True)
    import numpy as _np
    from slate_tpu.linalg.lu import getrf_array
    rng = _np.random.default_rng(0)
    a = jax.device_put(rng.standard_normal((n, n)) / 64)
    _ = float(jnp.sum(a[:1, :4]))
    # donate the input: the 16384 f64 program peaks ~14.4 GB un-donated
    # (memory_analysis) — aliasing the 2 GB input is what fits v5e HBM
    f = jax.jit(lambda x: getrf_array(x), donate_argnums=0)
    out = f(a)
    dmin = float(jnp.min(jnp.abs(jnp.diagonal(out.lu))))
    del out, a
    # timed run on a donated input; the matrix is rebuilt from its seed
    # AFTER the factorization for the residual check (nothing but the
    # program's own buffers is resident while it runs)
    a2_in = jax.device_put(_np.random.default_rng(7).standard_normal((n, n)) / 64)
    _ = float(jnp.sum(a2_in[:1, :4]))
    t0 = time.perf_counter()
    out = f(a2_in)
    dmin = float(jnp.min(jnp.abs(jnp.diagonal(out.lu))))
    t1 = time.perf_counter()
    info = int(out.info)
    a2 = jax.device_put(_np.random.default_rng(7).standard_normal((n, n)) / 64)
    # residual via matvec columns, CHUNKED (see potrf_f64 note): P A x vs
    # L (U x) with triangles taken per row chunk
    xv = jax.device_put(rng.standard_normal((n, 4)))
    lu = out.lu
    cols = jnp.arange(n)
    def tri_mv(low):
        outs = []
        for i in range(0, n, 2048):
            blk = lu[i:i+2048]
            r = (cols[i:i+2048, None] > cols[None, :]) if low else (cols[i:i+2048, None] <= cols[None, :])
            outs.append(jnp.where(r, blk, 0) @ (xv if not low else ux))
        return jnp.concatenate(outs)
    ux = tri_mv(False)
    lv = ux + tri_mv(True)  # L (U x), unit diagonal
    pax = jnp.concatenate([a2[out.perm[i:i+2048]] @ xv for i in range(0, n, 2048)])
    resid = float(jnp.linalg.norm(lv - pax) / jnp.linalg.norm(pax))
    ok = info == 0 and resid < 1e-12
    emit(t1 - t0, 2.0 * n**3 / 3 / (t1 - t0) / 1e9,
         f"info={{info}} dmin={{dmin:.2e}} resid={{resid:.2e}}", ok)
"""


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    only = None
    if len(sys.argv) > 2 and sys.argv[1] == "--only":
        only = set(sys.argv[2].split(","))
    out = os.path.join(root, "chiprun_out", "northstar_sweep.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    results = []
    if only and os.path.exists(out):
        with open(out) as f:  # keep other routines' existing rows
            results = [
                r for r in json.load(f)["results"] if r["routine"] not in only
            ]
    for routine, n, tmo in CASES:
        if only and routine not in only:
            continue
        code = CHILD.format(root=root, routine=routine, n=n)
        try:
            proc = subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                timeout=tmo,
            )
            line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
            if line:
                results.append(json.loads(line[-1][7:]))
            else:
                tail = (proc.stderr or "")[-300:]
                results.append({"routine": routine, "n": n, "ok": False,
                                "error": f"rc={proc.returncode} {tail}"})
        except subprocess.TimeoutExpired:
            results.append({"routine": routine, "n": n, "ok": False,
                            "error": f"timeout>{tmo}s"})
        print(json.dumps(results[-1]), flush=True)
        with open(out, "w") as f:
            json.dump(
                {"chip": _chip(results), "results": results},
                f, indent=1,
            )
    print(f"wrote {out}")
    _emit_obs_report(root, out, results)


def _chip(results):
    kinds = sorted({r["device"] for r in results if "device" in r})
    return ", ".join(kinds) or "unknown"


def _emit_obs_report(root, out, results):
    """RunReport twin of the sweep file (slate_tpu.obs): schema-versioned,
    diffable against any prior sweep with
    ``python -m slate_tpu.obs.report --check`` (which also reads the
    legacy SWEEP_*.json shape directly)."""
    try:
        sys.path.insert(0, root)
        from slate_tpu.obs.report import write_report

        values = {
            f"{r['routine']}_n{r['n']}_gflops": float(r["gflops"])
            for r in results
            if r.get("ok") and isinstance(r.get("gflops"), (int, float))
        }
        rpath = out[:-5] + ".report.json" if out.endswith(".json") else out + ".report.json"
        write_report(rpath, name="northstar_sweep",
                     config={"chip": _chip(results)},
                     values=values)
        print(f"wrote {rpath}")
    except Exception as e:  # sweep results must never die on obs
        print(f"obs report failed: {e!r}")


if __name__ == "__main__":
    main()
