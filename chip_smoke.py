#!/usr/bin/env python3
"""Bring-up smoke of slate_tpu's public solvers on a TPU.

    python chip_smoke.py              # one chip, four phases
    python chip_smoke.py --chips 4    # the 2x2 mesh solves, and nothing else

One chip (the default):

1. SPD solve: ``api.chol_solve`` in f32, n = 30720, nrhs = 16, through
   the scanned factor ``_potrf_scan``.  30720 is the largest multiple of
   1024 whose program fits one v5e: at n = 32768 the TPU compiler counts
   16.02 GiB of 15.75 (the operand plus about three matrix-sized
   buffers), and n <= 16384 takes the recursive factor instead.
2. General solve: ``api.lu_solve`` (partial pivoting) in f32, n = 16384,
   nrhs = 16 — the HPL-shaped solve.
3. f64 GEMM: ``api.multiply`` in f64 at n = 8192 (the int8-MXU Ozaki
   dispatch), checked against a host f64 product of sampled rows.
4. Served solves: eight n = 1024 f32 SPD requests through
   ``api.serve_router()``; every request must come back ``served``.

Four chips (``--chips 4``): ``posv_mesh`` and ``gesv_mesh`` in f32 at
n = 32768, nb = 256 on ``make_mesh(2, 2)``, from an operand split over
the mesh and from one held whole by chip 0.  Each run checks that the
tile stack the driver builds sits on four distinct devices and that
every chip's bytes in use rose during the run by its share of it.

Every phase prints one JSON line: n, dtype, compile and run seconds, and
its backward error against its gate, computed in f64 on the host in the
reference tester's form ``||B - A X|| / (n ||A|| ||X||) <= 25 eps`` (the
tester's default tol 50 times eps / 2), over a seeded sample of rows
where the full matrix is too large for the host check.  Every phase ends
in a host transfer of its result before the clock is read.  All data is
made from ``--seed``, on the device where it is large.  The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The script refuses to run anywhere but a TPU, catches no phase's
failure, and starts no child process.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time

import numpy as np

TOL = 25.0  # reference solve testers: tol = 50 * 0.5 * eps
GEMM_TOL = 3.0  # reference gemm tester: 3 eps
EPS32 = float(np.finfo(np.float32).eps)
SAMPLE_ROWS = 256


def _emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def _norm_inf(m: np.ndarray) -> float:
    return float(np.abs(m).sum(axis=1).max())


def _solve_error(a_rows, b_rows, x, a_norm, n) -> float:
    """||B_S - A_S X||_inf / (n ||A||_inf ||X||_inf) in f64 over the
    sampled rows S (a lower bound of the full-row figure)."""
    r = np.asarray(b_rows, np.float64) - np.asarray(a_rows, np.float64) @ x
    return _norm_inf(r) / (n * a_norm * _norm_inf(x))


def _sample(n: int, seed: int, k: int = SAMPLE_ROWS) -> np.ndarray:
    return np.sort(np.random.default_rng(seed).choice(n, size=min(k, n), replace=False))


def _check(name: str, err: float, gate: float, **rec) -> None:
    ok = bool(np.isfinite(err) and err <= gate)
    _emit({"phase": name, **rec, "backward_error": err, "gate": gate, "ok": ok})
    if not ok:
        raise SystemExit(f"chip_smoke: phase {name} failed its gate: {err} > {gate}")


def _spd(key, n, dtype, sharding=None):
    """Wigner-shifted SPD operand, 3 I + (G + G^T) / (2 sqrt n): the
    spectrum sits in [3 - sqrt 2, 3 + sqrt 2], no Gram product."""
    import jax
    import jax.numpy as jnp

    def build(key):
        g = jax.random.normal(key, (n, n), dtype)
        return (g + g.T) / jnp.asarray(2.0 * np.sqrt(n), dtype) + 3 * jnp.eye(n, dtype=dtype)

    return jax.jit(build, out_shardings=sharding)(key)


def _general(key, n, dtype, sharding=None):
    import jax

    return jax.jit(
        lambda k: jax.random.uniform(k, (n, n), dtype, -0.5, 0.5), out_shardings=sharding
    )(key)


def _rows_and_norm(a, idx):
    """Host copy of the sampled rows and ||A||_inf (row sums on device)."""
    import jax.numpy as jnp

    return np.asarray(a[idx]), float(jnp.max(jnp.sum(jnp.abs(a), axis=1)))


def _bytes_in_use(devs) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def _aot(fn, *args):
    """Compile ``fn`` for ``args`` ahead of time; returns (compiled, secs)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def phase_spd(seed: int, n: int = 30720, nrhs: int = 16) -> None:
    import jax
    import jax.numpy as jnp

    from slate_tpu import api

    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = _spd(ka, n, jnp.float32)
    b = jax.random.normal(kb, (n, nrhs), jnp.float32)
    idx = _sample(n, seed)
    a_rows, a_norm = _rows_and_norm(a, idx)
    b_rows = np.asarray(b[idx])
    solve, compile_s = _aot(api.chol_solve, a, b)
    t0 = time.perf_counter()
    x, info = solve(a, b)
    x, info = np.asarray(x, np.float64), int(info)
    run_s = time.perf_counter() - t0
    if info != 0:
        raise SystemExit(f"chip_smoke: chol_solve info={info}")
    _check("spd_solve", _solve_error(a_rows, b_rows, x, a_norm, n),
           TOL * EPS32, entry="api.chol_solve", n=n,
           nrhs=nrhs, dtype="float32", compile_s=compile_s, run_s=run_s,
           sampled_rows=len(idx))


def phase_lu(seed: int, n: int = 16384, nrhs: int = 16) -> None:
    import jax
    import jax.numpy as jnp

    from slate_tpu import api

    ka, kb = jax.random.split(jax.random.PRNGKey(seed + 1))
    a = _general(ka, n, jnp.float32)
    b = jax.random.normal(kb, (n, nrhs), jnp.float32)
    idx = _sample(n, seed + 1)
    a_rows, a_norm = _rows_and_norm(a, idx)
    b_rows = np.asarray(b[idx])
    solve, compile_s = _aot(api.lu_solve, a, b)
    t0 = time.perf_counter()
    x = np.asarray(solve(a, b), np.float64)
    run_s = time.perf_counter() - t0
    _check("general_solve", _solve_error(a_rows, b_rows, x, a_norm, n),
           TOL * EPS32, entry="api.lu_solve", n=n,
           nrhs=nrhs, dtype="float32", compile_s=compile_s, run_s=run_s,
           sampled_rows=len(idx))


def phase_gemm_f64(seed: int, n: int = 8192) -> None:
    """f64 GEMM through the int8-MXU Ozaki dispatch, checked in the
    reference gemm tester's form ||C - C_ref|| / ((sqrt(k) + 2) ||A|| ||B||)
    over sampled rows against a host f64 product."""
    import jax.numpy as jnp

    from slate_tpu import api

    rng = np.random.default_rng(seed + 2)
    ah = rng.standard_normal((n, n))
    bh = rng.standard_normal((n, n))
    a, b = jnp.asarray(ah), jnp.asarray(bh)
    assert a.dtype == jnp.float64, a.dtype
    mult, compile_s = _aot(lambda x, y: api.multiply(1.0, x, y), a, b)
    t0 = time.perf_counter()
    c = mult(a, b)
    idx = _sample(n, seed + 2, 64)
    c_rows = np.asarray(c[idx])
    run_s = time.perf_counter() - t0
    ref = ah[idx] @ bh
    err = _norm_inf(c_rows - ref) / ((np.sqrt(n) + 2) * _norm_inf(ah[idx]) * _norm_inf(bh))
    _check("gemm_f64", err, GEMM_TOL * float(np.finfo(np.float64).eps),
           entry="api.multiply", n=n, dtype="float64", compile_s=compile_s,
           run_s=run_s, sampled_rows=len(idx))


def phase_router(seed: int, n: int = 1024, count: int = 8) -> None:
    import jax.numpy as jnp

    from slate_tpu import api, obs
    from slate_tpu.serve import trace as rtrace

    rng = np.random.default_rng(seed + 3)
    reqs = []
    for _ in range(count):
        g = rng.standard_normal((n, n)).astype(np.float32)
        a = (g + g.T) / np.float32(2 * np.sqrt(n)) + 3 * np.eye(n, dtype=np.float32)
        reqs.append((a, rng.standard_normal((n, 4)).astype(np.float32)))
    router = api.serve_router()
    obs.enable()
    try:
        times = []
        for _ in range(2):  # cold (traces + compiles), then warm
            rtrace.reset()
            t0 = time.perf_counter()
            xs = router.solve_batch(
                [("posv", jnp.asarray(a), jnp.asarray(b)) for a, b in reqs])
            xs = [np.asarray(x, np.float64) for x in xs]
            times.append(time.perf_counter() - t0)
            outcomes = [t.outcome for t in rtrace.finished_traces()]
    finally:
        obs.disable()
    if outcomes != ["served"] * count:
        raise SystemExit(f"chip_smoke: router outcomes {outcomes}")
    err = max(_solve_error(a, b, x, _norm_inf(a), n) for (a, b), x in zip(reqs, xs))
    _check("served_solves", err, TOL * EPS32,
           entry="api.serve_router().solve_batch", n=n, requests=count,
           dtype="float32", compile_s=times[0] - times[1], run_s=times[1],
           outcomes=sorted(set(outcomes)))


class _PeakWatch:
    """Highest ``bytes_in_use`` per device while a driver runs, polled
    from a host thread (the drivers free their temporaries before they
    return, so a reading after the run would miss them)."""

    def __init__(self, devs, period_s: float = 0.002):
        self.devs, self.period_s = devs, period_s
        self.peak = _bytes_in_use(devs)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while not self._stop.is_set():
            self.peak = [max(p, u) for p, u in zip(self.peak, _bytes_in_use(self.devs))]
            time.sleep(self.period_s)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()


@contextlib.contextmanager
def _tile_placement(record: list):
    """Record, for every DistMatrix the mesh drivers build
    (``drivers.from_dense``), its shape and the devices holding its tile
    shards — ids only, so no array outlives the driver."""
    from slate_tpu.parallel import drivers

    orig = drivers.from_dense

    def spy(*args, **kw):
        d = orig(*args, **kw)
        record.append(((d.m, d.n), sorted({s.device.id for s in d.tiles.addressable_shards})))
        return d

    drivers.from_dense = spy
    try:
        yield
    finally:
        drivers.from_dense = orig


def phase_mesh(seed: int, n: int = 32768, nb: int = 256, nrhs: int = 16) -> None:
    """posv_mesh and gesv_mesh on a 2x2 mesh over four chips, each run
    twice on an operand already split over the mesh (cold, then warm) and
    once on an operand held whole by chip 0, as a user passes a dense
    array.  In every run the tile stack of A the driver builds must sit on
    the four chips, and each chip's bytes in use must rise during the run
    by at least its quarter of that stack above the operand-only baseline."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from slate_tpu.parallel import gesv_mesh, make_mesh, posv_mesh
    from slate_tpu.parallel.mesh import COL_AXIS, ROW_AXIS

    devs = jax.devices()[:4]
    if len(devs) != 4:
        raise SystemExit(f"chip_smoke: --chips 4 needs four devices, found {len(devs)}")
    mesh = make_mesh(2, 2, devices=devs)
    dense = NamedSharding(mesh, P(ROW_AXIS, COL_AXIS))
    share = n * n * 4 // 4  # one chip's quarter of A's f32 tile stack

    def run(driver, a, b):
        placed = []
        base = _bytes_in_use(devs)
        with _tile_placement(placed), _PeakWatch(devs) as watch:
            t0 = time.perf_counter()
            x, info = driver(a, b, mesh, nb=nb)
            x, info = np.asarray(x, np.float64), int(info)
            secs = time.perf_counter() - t0
        tile_devs = [d for shape, d in placed if shape == (n, n)]
        rise = [p - u for p, u in zip(watch.peak, base)]
        if tile_devs != [sorted(d.id for d in devs)]:
            raise SystemExit(f"chip_smoke: tile stacks of A on devices {tile_devs}")
        if min(rise) < share:
            raise SystemExit(f"chip_smoke: bytes_in_use rose by {rise}, under {share} on a chip")
        if info != 0:
            raise SystemExit(f"chip_smoke: info={info}")
        return x, secs, base, rise, tile_devs[0]

    for name, driver, make in (("posv_mesh", posv_mesh, _spd),
                               ("gesv_mesh", gesv_mesh, _general)):
        ka, kb = jax.random.split(jax.random.PRNGKey(seed + len(name)))
        a = make(ka, n, jnp.float32, dense)
        b = jax.random.normal(kb, (n, nrhs), jnp.float32)
        idx = _sample(n, seed + len(name))
        a_rows, a_norm = _rows_and_norm(a, idx)
        b_rows = np.asarray(b[idx])
        gate = TOL * EPS32
        _, cold_s, _, _, _ = run(driver, a, b)
        x, warm_s, base, rise, tile_devs = run(driver, a, b)
        _check(name, _solve_error(a_rows, b_rows, x, a_norm, n), gate,
               entry=f"parallel.{name}", operand="split over the 2x2 mesh",
               n=n, nb=nb, nrhs=nrhs, grid="2x2", dtype="float32",
               compile_s=cold_s - warm_s, run_s=warm_s, sampled_rows=len(idx),
               tile_devices=tile_devs, bytes_in_use_baseline=base,
               bytes_in_use_peak_rise=rise)
        a = jax.device_put(a, devs[0])  # whole on chip 0, as a user's dense array
        x, one_s, base, rise, tile_devs = run(driver, a, b)
        _check(f"{name}_from_one_chip", _solve_error(a_rows, b_rows, x, a_norm, n),
               gate, entry=f"parallel.{name}", operand="whole on chip 0",
               n=n, nb=nb, nrhs=nrhs, grid="2x2", dtype="float32", run_s=one_s,
               sampled_rows=len(idx), tile_devices=tile_devs,
               bytes_in_use_baseline=base, bytes_in_use_peak_rise=rise)
        del a


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
              f"({dev.device_kind}) — no phase ran", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)  # phase 3 is f64; the rest pin f32

    from slate_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.chips == 4:
        phase_mesh(args.seed)
    else:
        phase_spd(args.seed)
        phase_lu(args.seed)
        phase_gemm_f64(args.seed)
        phase_router(args.seed)
    _emit({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                  "count": len(jax.devices())}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
