#!/usr/bin/env python
"""Parameter-sweep tester: the testsweeper/`test/tester` analogue.

Usage (mirrors `test/tester <routine> --dim ... --type ...`, SURVEY §4):

    python tester.py gemm --dim 256:1024:256 --type s,d
    python tester.py potrf --dim 1024 --type d --check y
    python tester.py heev svd --dim 200 --type d
    python tester.py --help

Per combination prints: routine, type, dims, error, status, time, gflops —
the reference tester's output row (docs/usage.md:36-44).  Gflop formulas
follow blas::Gflop (gemm 2mnk; potrf n^3/3; getrf 2n^3/3; geqrf 4mn^2-4n^3/3;
heev ~4n^3/3; svd ~8n^3/3).  Residual gates follow test/*.cc (3-eps style).
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

_DTYPES = {"s": np.float32, "d": np.float64, "c": np.complex64, "z": np.complex128}


def _parse_dims(spec: str):
    for part in spec.split(","):
        if ":" in part:
            bits = [int(x) for x in part.split(":")]
            start, stop = bits[0], bits[1]
            step = bits[2] if len(bits) > 2 else start
            yield from range(start, stop + 1, step)
        else:
            yield int(part)


def _eps(dtype):
    return np.finfo(np.float32 if dtype in (np.float32, np.complex64) else np.float64).eps


def _rand(rng, m, n, dtype):
    a = rng.standard_normal((m, n))
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal((m, n))
    return a.astype(dtype)


def _sync(out):
    """Finish the work with a host transfer of one element of the result
    before the clock is read."""
    import jax

    for leaf in jax.tree_util.tree_leaves(out):
        if hasattr(leaf, "ndim"):
            jax.device_get(leaf[(0,) * leaf.ndim])
            break
    return out


def _time(fn, *args, label: str = ""):
    """(result, seconds) of one call after a warm-up call.  The timed call
    runs inside a ``tester/<label>`` profiler annotation, so a ``--trace``
    profile marks it among the warm-ups."""
    import jax

    _sync(fn(*args))  # warm/compile (and drain the dispatch queue)
    name = "tester/" + (label or getattr(fn, "__name__", "op"))
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        out = _sync(fn(*args))
        t1 = time.perf_counter()
    return out, t1 - t0


def _ref_solve(routine, a, extra=None):
    """--ref mode: run the same problem through scipy/LAPACK and compare
    (the reference tester's ScaLAPACK `ref` comparison, test_gemm.cc:310,
    with scipy as the single-process reference library)."""
    import scipy.linalg as sla

    if routine == "gesv":
        return sla.solve(a, extra)
    if routine == "heev":
        return np.linalg.eigvalsh(a)
    if routine == "svd":
        return np.linalg.svd(a, compute_uv=False)
    return None


def _make_mesh_from_grid(grid: str):
    import jax

    from slate_tpu.parallel.mesh import make_mesh

    p, q = (int(x) for x in grid.lower().split("x"))
    devs = jax.devices()
    if len(devs) < p * q:
        raise SystemExit(
            f"--grid {grid} needs {p * q} devices but only {len(devs)} are "
            f"visible; for a virtual mesh set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={p * q} "
            f"JAX_PLATFORMS=cpu"
        )
    return make_mesh(p, q, devices=devs[: p * q])


def run_gemm_mesh(n, dtype, rng, check, grid):
    import jax.numpy as jnp

    from slate_tpu.parallel import gemm_mesh

    mesh = _make_mesh_from_grid(grid)
    a, b = _rand(rng, n, n, dtype), _rand(rng, n, n, dtype)
    nb = max(8, min(64, n // max(*_make_grid_dims(grid))))
    c, t = _time(lambda x, y: gemm_mesh(1.0, x, y, mesh, nb=nb),
                 jnp.asarray(a), jnp.asarray(b), label="gemm_mesh")
    err = 0.0
    if check:
        ref = a @ b
        err = np.abs(np.asarray(c) - ref).max() / (np.abs(ref).max() + 1e-30)
    return err, t, 2 * n**3 / t / 1e9, err < 100 * n * _eps(dtype)


def _make_grid_dims(grid):
    return tuple(int(x) for x in grid.lower().split("x"))


def run_posv_mesh(n, dtype, rng, check, grid):
    import jax.numpy as jnp

    from slate_tpu.parallel import posv_mesh

    mesh = _make_mesh_from_grid(grid)
    g = _rand(rng, n, n, dtype)
    a = g @ g.conj().T + n * np.eye(n, dtype=dtype)
    b = _rand(rng, n, 2, dtype)
    (x, info), t = _time(lambda aa, bb: posv_mesh(aa, bb, mesh, nb=16),
                         jnp.asarray(a), jnp.asarray(b), label="posv_mesh")
    err = np.abs(a @ np.asarray(x) - b).max() / np.abs(b).max() if check else 0.0
    return err, t, n**3 / 3 / t / 1e9, int(info) == 0 and err < 100 * n * _eps(dtype)


def run_gesv_mesh(n, dtype, rng, check, grid):
    import jax.numpy as jnp

    from slate_tpu.parallel import gesv_tntpiv_mesh

    mesh = _make_mesh_from_grid(grid)
    a = _rand(rng, n, n, dtype)
    b = _rand(rng, n, 2, dtype)
    (x, info), t = _time(lambda aa, bb: gesv_tntpiv_mesh(aa, bb, mesh, nb=16),
                         jnp.asarray(a), jnp.asarray(b), label="gesv_tntpiv_mesh")
    x = np.asarray(x)
    err = (np.abs(a @ x - b).max() / (np.abs(a).max() * max(1, np.abs(x).max()) * n)
           if check else 0.0)
    return err, t, 2 * n**3 / 3 / t / 1e9, int(info) == 0 and err < 100 * _eps(dtype)


def run_gemm(n, dtype, rng, check, precision=None):
    """Times the gemm driver at its default tier (Highest for every dtype,
    matching the reference's full-precision vendor BLAS), or at an explicit
    --precision tier.  The --check gate uses a tier-aware tolerance: Fast
    is single-pass bf16 (~2^-8 relative on N(0,1) data), High is bf16x3
    (~2^-16), Highest is ~f32 (3-eps style)."""
    import jax.numpy as jnp
    from slate_tpu.blas3.blas3 import _mul_prec
    from slate_tpu.ops.matmul import matmul
    from slate_tpu.types import Precision

    a, b = _rand(rng, n, n, dtype), _rand(rng, n, n, dtype)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    prec = precision or _mul_prec(None)
    c, t = _time(lambda x, y: matmul(x, y, precision=prec), aj, bj, label="gemm")
    gflops = 2 * n**3 / t / 1e9
    err = 0.0
    if check:
        x = _rand(rng, n, 1, dtype)
        lhs = np.asarray(c) @ x
        rhs = a @ (b @ x)
        err = np.abs(lhs - rhs).max() / (np.abs(rhs).max() + 1e-30)
    # documented tier tolerances (measured v5e, types.Precision docstring):
    # input-rounding dominated for Fast/High, 3-eps style for Highest
    tier_eps = {Precision.Fast: 2.0**-8, Precision.High: 2.0**-16}
    if dtype in (np.float64, np.complex128):  # Ozaki dispatch dtypes only
        tier_eps[Precision.Fast] = 2.0**-33  # 6-slice Ozaki
        tier_eps[Precision.High] = 0.0
    tol = max(100 * n * _eps(dtype), 16 * tier_eps.get(prec, 0.0))
    return err, t, gflops, err < tol


def run_potrf(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import potrf_array

    g = _rand(rng, n, n, dtype)
    a = g @ g.conj().T + n * np.eye(n, dtype=dtype)
    (l, info), t = _time(potrf_array, jnp.asarray(a))
    gflops = n**3 / 3 / t / 1e9
    ld = np.tril(np.asarray(l))
    err = np.linalg.norm(ld @ ld.conj().T - a) / np.linalg.norm(a) if check else 0.0
    return err, t, gflops, int(info) == 0 and err < 30 * n * _eps(dtype)


def run_getrf(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import getrf_array

    a = _rand(rng, n, n, dtype)
    f, t = _time(getrf_array, jnp.asarray(a))
    gflops = 2 * n**3 / 3 / t / 1e9
    err = 0.0
    if check:
        lu, perm = np.asarray(f.lu), np.asarray(f.perm)
        l = np.tril(lu, -1) + np.eye(n, dtype=dtype)
        u = np.triu(lu)
        err = np.linalg.norm(l @ u - a[perm]) / np.linalg.norm(a)
    return err, t, gflops, err < 30 * n * _eps(dtype)


def run_gesv(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import gesv_array

    a = _rand(rng, n, n, dtype)
    b = _rand(rng, n, 8, dtype)
    (x, f), t = _time(gesv_array, jnp.asarray(a), jnp.asarray(b))
    gflops = (2 * n**3 / 3 + 2 * n**2 * 8) / t / 1e9
    err = np.abs(a @ np.asarray(x) - b).max() / (np.abs(b).max() * np.abs(a).sum(1).max()) if check else 0.0
    return err, t, gflops, err < 30 * n * _eps(dtype)


def run_geqrf(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import geqrf_array
    from slate_tpu.linalg.qr import geqrf_q, geqrf_r

    m = n
    a = _rand(rng, m, n, dtype)
    f, t = _time(geqrf_array, jnp.asarray(a))
    gflops = (4 * m * n**2 - 4 * n**3 / 3) / t / 1e9
    err = 0.0
    if check:
        q = np.asarray(geqrf_q(f))
        r = np.asarray(geqrf_r(f))
        err = np.linalg.norm(q @ r - a) / np.linalg.norm(a)
    return err, t, gflops, err < 30 * n * _eps(dtype)


def run_gels(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import gels_array

    m = 2 * n
    a = _rand(rng, m, n, dtype)
    b = _rand(rng, m, 4, dtype)
    x, t = _time(gels_array, jnp.asarray(a), jnp.asarray(b))
    gflops = (2 * m * n**2) / t / 1e9
    err = 0.0
    if check:  # normal-equations residual: A^H (A x - b) ~ 0
        r = a @ np.asarray(x) - b
        err = np.abs(a.conj().T @ r).max() / (np.abs(a).max() ** 2 * np.abs(x).max() * m)
    return err, t, gflops, err < 100 * n * _eps(dtype)


def run_heev(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import heev_array

    a = _rand(rng, n, n, dtype)
    a = (a + a.conj().T) / 2
    (w, z), t = _time(lambda x: heev_array(x, nb=32), jnp.asarray(a))
    gflops = 4 * n**3 / 3 / t / 1e9
    err = 0.0
    if check:
        w, z = np.asarray(w), np.asarray(z)
        err = np.abs(a @ z - z * w).max() / (np.abs(w).max() + 1e-30) / n
    return err, t, gflops, err < 100 * _eps(dtype)


def run_svd(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.linalg import svd_array

    a = _rand(rng, n, n, dtype)
    (u, s, vh), t = _time(lambda x: svd_array(x, nb=32), jnp.asarray(a))
    gflops = 8 * n**3 / 3 / t / 1e9
    err = 0.0
    if check:
        u, s, vh = np.asarray(u), np.asarray(s), np.asarray(vh)
        err = np.abs(a - (u * s) @ vh).max() / (s[0] + 1e-30) / n
    return err, t, gflops, err < 100 * _eps(dtype)


def run_trsm(n, dtype, rng, check):
    import jax.numpy as jnp
    from slate_tpu.blas3.blas3 import trsm_array
    from slate_tpu.types import Diag, Op, Side, Uplo

    t_mat = np.tril(_rand(rng, n, n, dtype)) + n * np.eye(n, dtype=dtype)
    b = _rand(rng, n, n, dtype)
    x, t = _time(
        lambda a_, b_: trsm_array(Side.Left, Uplo.Lower, Op.NoTrans, Diag.NonUnit, 1.0, a_, b_),
        jnp.asarray(t_mat), jnp.asarray(b),
    )
    gflops = n**3 / t / 1e9
    err = np.abs(t_mat @ np.asarray(x) - b).max() / (np.abs(b).max() * n) if check else 0.0
    return err, t, gflops, err < 30 * _eps(dtype)


ROUTINES = {
    "gemm": run_gemm,
    "potrf": run_potrf,
    "getrf": run_getrf,
    "gesv": run_gesv,
    "geqrf": run_geqrf,
    "gels": run_gels,
    "heev": run_heev,
    "svd": run_svd,
    "trsm": run_trsm,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("routines", nargs="+", choices=sorted(ROUTINES), help="routines to sweep")
    ap.add_argument("--dim", default="256", help="sizes: N | start:stop[:step] | comma list")
    ap.add_argument("--type", default="d", help="precisions from s,d,c,z")
    ap.add_argument("--check", default="y", choices=["y", "n"])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--grid", default="",
                    help="PxQ mesh grid: run the distributed variants "
                         "(gemm/posv/gesv) over a device mesh")
    ap.add_argument("--precision", default="",
                    choices=["", "fast", "high", "highest", "emulated"],
                    help="BLAS-3 accumulation tier for gemm (types.Precision); "
                         "empty = driver default (fast for s, highest for d/z)")
    ap.add_argument("--ref", default="n", choices=["y", "n"],
                    help="also run scipy/LAPACK and report the comparison "
                         "(reference tester's ScaLAPACK ref mode)")
    ap.add_argument("--trace", default="",
                    help="write a jax.profiler trace of the sweep to this "
                         "directory: each timed call (a tester/<routine> "
                         "span), the library's slate_tpu/* spans and the "
                         "device ops on one clock (open it in TensorBoard "
                         "or xprof)")
    ap.add_argument("--report", default="",
                    help="write a slate_tpu.obs RunReport JSON of the sweep "
                         "(also enables observability: driver spans + comm "
                         "bytes ride along)")
    ap.add_argument("--flight", default="",
                    help="also write a step-level FlightReport JSON "
                         "(slate_tpu.obs.flight) for the first requested "
                         "routine that has a flight driver (gemm / potrf / "
                         "getrf / trsm); needs the 8-device CPU mesh")
    ap.add_argument("--mem", default="",
                    help="also write a mem.* RunReport JSON "
                         "(slate_tpu.obs.memwatch: AOT memory analysis + "
                         "MemoryModel + donation aliasing) for the first "
                         "requested routine with a mem driver (gemm / "
                         "potrf / getrf); needs the 8-device CPU mesh")
    ap.add_argument("--num", default="",
                    help="also write a num.* RunReport JSON "
                         "(slate_tpu.obs.numwatch: monitored growth/margin "
                         "gauges + distributed condest + mixed-ladder "
                         "health routing on seeded inputs) for the first "
                         "requested routine with a num driver (getrf / "
                         "gesv -> lu, potrf / posv -> potrf, else mixed); "
                         "needs the 8-device CPU mesh")
    args = ap.parse_args(argv)

    import jax

    if any(p in args.type for p in "dz"):
        jax.config.update("jax_enable_x64", True)

    rng = np.random.default_rng(args.seed)
    check = args.check == "y"
    if args.trace:
        jax.profiler.start_trace(args.trace)
    if args.report:
        from slate_tpu import obs

        obs.enable()
    report_values = {}
    hdr = (f"{'routine':<10} {'type':<4} {'n':>7} {'error':>10} {'status':>6} "
           f"{'time(s)':>9} {'gflops':>10}")
    print(hdr + ("  ref_diff" if args.ref == "y" else ""))
    failures = 0
    for routine in args.routines:
        for prefix in args.type.split(","):
            for n in _parse_dims(args.dim):
                dtype = _DTYPES[prefix]
                if args.grid and routine in MESH_ROUTINES:
                    if args.precision:
                        print(f"note: --precision {args.precision} ignored for "
                              f"mesh routine {routine}@{args.grid} (mesh kernels "
                              f"run their documented fixed tiers)", file=sys.stderr)
                    if args.trace:
                        # collective-volume audit rides the trace flag
                        # (VERDICT r4 item 7; full table: tools/comm_audit.py)
                        import jax as _jax

                        from slate_tpu.parallel.comm import comm_audit

                        _jax.clear_caches()
                        with comm_audit() as _comm_recs:
                            err, t, gflops, ok = MESH_ROUTINES[routine](
                                n, dtype, rng, check, args.grid)
                        payload = sum(b * m for _, b, m in _comm_recs)
                        execs = sum(m for _, _, m in _comm_recs)
                        print(f"  comm: {payload:,} payload B/dev over "
                              f"{execs:,} collective execs", file=sys.stderr)
                    else:
                        err, t, gflops, ok = MESH_ROUTINES[routine](
                            n, dtype, rng, check, args.grid)
                    rname = routine + "@" + args.grid
                elif routine == "gemm" and args.precision:
                    from slate_tpu.types import Precision

                    err, t, gflops, ok = run_gemm(
                        n, dtype, rng, check, Precision(args.precision))
                    rname = routine + ":" + args.precision
                else:
                    err, t, gflops, ok = ROUTINES[routine](n, dtype, rng, check)
                    rname = routine
                refcol = ""
                if args.ref == "y":
                    import scipy  # noqa: F401  (fail loudly if missing)

                    refcol = "  " + _ref_compare(routine, n, dtype, args.seed)
                status = "pass" if ok else "FAILED"
                failures += 0 if ok else 1
                key = f"{rname.replace('@', '_').replace(':', '_')}_{prefix}_n{n}"
                report_values[f"{key}_gflops"] = round(gflops, 2)
                report_values[f"{key}_seconds"] = round(t, 6)
                print(f"{rname:<10} {prefix:<4} {n:>7} {err:>10.2e} {status:>6} "
                      f"{t:>9.4f} {gflops:>10.1f}{refcol}")
    if args.trace:
        jax.profiler.stop_trace()
        print(f"trace written to {args.trace}")
    if args.report:
        import os

        from slate_tpu.obs.report import write_report

        d = os.path.dirname(os.path.abspath(args.report))
        os.makedirs(d, exist_ok=True)
        write_report(
            args.report, name="tester",
            config={"routines": ",".join(args.routines), "dim": args.dim,
                    "type": args.type, "grid": args.grid or "single"},
            values=report_values,
        )
        print(f"report written to {args.report}")
    if args.flight:
        from slate_tpu.obs import flight as _flight

        fl_ops = {"gemm": "summa", "potrf": "potrf",
                  "getrf": "getrf_nopiv", "trsm": "trsm"}
        op = next((fl_ops[r] for r in args.routines if r in fl_ops), None)
        if op is None:
            print(f"flight: none of {args.routines} has a flight driver "
                  f"({sorted(fl_ops)})")
        else:
            try:
                n_fl = max(_parse_dims(args.dim))
                rep = _flight.run_flight(op, n=n_fl, nb=max(8, n_fl // 12))
                _flight.write_flight_report(args.flight, rep)
                print(f"flight report written to {args.flight} (overlap_eff "
                      f"{rep['sched']['overlap_eff']:.3f})")
            except Exception as e:
                # obs must never flip a passed sweep's exit code (e.g.
                # <8 CPU devices without the forced-device XLA_FLAGS)
                print(f"flight report failed: {e!r}")
    if args.mem:
        from slate_tpu.obs import memwatch as _memwatch

        mem_ops = {"gemm": "summa", "potrf": "potrf",
                   "getrf": "getrf_nopiv"}
        op = next((mem_ops[r] for r in args.routines if r in mem_ops), None)
        if op is None:
            print(f"mem: none of {args.routines} has a mem driver "
                  f"({sorted(mem_ops)})")
        else:
            try:
                n_m = max(_parse_dims(args.dim))
                rep = _memwatch.run_memwatch(op, n=n_m,
                                             nb=max(8, n_m // 12))
                _memwatch.write_mem_report(args.mem, rep)
                v = rep["values"]
                print(f"mem report written to {args.mem} (temp "
                      f"{v['mem.temp_bytes']:,.0f} B/dev, model err "
                      f"{v['mem.model_err_frac']:.1%})")
            except Exception as e:
                # obs must never flip a passed sweep's exit code
                print(f"mem report failed: {e!r}")
    if args.num:
        from slate_tpu.obs import numwatch as _numwatch

        num_ops = {"getrf": "lu", "gesv": "lu", "potrf": "potrf",
                   "posv": "potrf"}
        op = next((num_ops[r] for r in args.routines if r in num_ops),
                  "mixed")
        try:
            rep = _numwatch.run_numwatch(op)
            _numwatch.write_num_report(args.num, rep)
            keys = [k for k in sorted(rep["values"]) if "_runtime_" not in k]
            print(f"num report written to {args.num} ("
                  + ", ".join(f"{k.split('num.', 1)[1]}="
                              f"{rep['values'][k]:.3g}" for k in keys[:3])
                  + ")")
        except Exception as e:
            # obs must never flip a passed sweep's exit code
            print(f"num report failed: {e!r}")
    return 1 if failures else 0


def _ref_compare(routine, n, dtype, seed) -> str:
    """Re-run the same seeded problem through scipy and diff the results
    (seeded identically so 'random matrices are the same regardless of
    distribution', CHANGELOG.md:25-26)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + n)
    if routine == "gesv":
        from slate_tpu.linalg import gesv_array

        a = _rand(rng, n, n, dtype)
        b = _rand(rng, n, 2, dtype)
        x, _ = gesv_array(jnp.asarray(a), jnp.asarray(b))
        ref = _ref_solve("gesv", a, b)
        return f"|x-ref|={np.abs(np.asarray(x) - ref).max():.2e}"
    if routine == "heev":
        from slate_tpu.linalg import heev_array

        g = _rand(rng, n, n, dtype)
        a = (g + g.conj().T) / 2
        w = heev_array(jnp.asarray(a), want_vectors=False)
        ref = _ref_solve("heev", a)
        return f"|w-ref|={np.abs(np.asarray(w) - ref).max():.2e}"
    if routine == "svd":
        from slate_tpu.linalg import svd_array

        a = _rand(rng, n, n, dtype)
        sv = svd_array(jnp.asarray(a), want_vectors=False)
        ref = _ref_solve("svd", a)
        return f"|s-ref|={np.abs(np.sort(np.asarray(sv))[::-1] - ref).max():.2e}"
    return "(no ref)"


MESH_ROUTINES = {
    "gemm": run_gemm_mesh,
    "potrf": run_posv_mesh,
    "gesv": run_gesv_mesh,
}


if __name__ == "__main__":
    sys.exit(main())
