"""Persistent executable cache: one compiled program per request class.

Today every request that reaches a driver with a fresh options dict can
re-trace; at serving rates that is the difference between MXU-bound and
compiler-bound.  The cache pins ONE jitted program per ``CacheKey`` —
``(op, shape signature, dtype, batch, mesh, resolved Options)`` — so
steady-state traffic hits exactly the programs warmed at startup and
performs ZERO retraces (transfer-guard style: asserted by trace
counters, not hoped).

Layering: this is the HOST half (key -> traced program identity); the
DISK half is JAX's persistent compilation cache, which
``utils.compile_cache.enable_compile_cache`` turns on so a restarted server
re-loads compiled binaries instead of re-running XLA.  Note the PR 10
finding: cache-DESERIALIZED executables report an empty
``memory_analysis``, which is why the mem gates (obs/memory.py) force
their measuring compile to bypass the compilation cache — that bypass is
orthogonal to this layer and stays intact (tests/test_mem.py).

Trace counting: the cached program's Python body increments the key's
trace counter — the body only runs when JAX actually traces, so the
counter IS the retrace count (a cache hit at the jit layer never
re-enters Python).  ``ExecutableCache.assert_steady`` turns that into
the CI-facing zero-retrace assertion.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax

from .metrics import serve_count



class CacheKey(NamedTuple):
    """The request-class identity every compiled program is pinned to."""

    op: str            # driver name ("posv", "gesv", "gemm", "potrf", ...)
    shape: Tuple       # problem shape signature, e.g. ((8, 512, 512), (8, 512, 1))
    dtype: str         # operand dtype ("float64", ...)
    batch: int         # stack depth B (1 = single problem)
    mesh: str          # mesh descriptor ("none" = single-chip stacked path)
    opts: Tuple        # sorted resolved-option items, e.g. (("bcast_impl", "ring"),)


def options_signature(opts: Optional[Dict]) -> Tuple:
    """Canonical hashable form of a resolved Options mapping (enum keys
    and values collapse to their .value strings)."""
    if not opts:
        return ()
    items = []
    for k, v in opts.items():
        kk = getattr(k, "value", k)
        vv = getattr(v, "value", v)
        items.append((str(kk), vv))
    return tuple(sorted(items))


def mesh_signature(mesh) -> str:
    if mesh is None:
        return "none"
    shape = dict(mesh.shape)
    plat = mesh.devices.flat[0].platform
    return f"{plat}:" + "x".join(str(shape[a]) for a in mesh.axis_names)


def make_key(op: str, args: Tuple[jax.Array, ...], batch: int = 1,
             mesh=None, opts: Optional[Dict] = None) -> CacheKey:
    return CacheKey(
        op=op,
        shape=tuple(tuple(a.shape) for a in args),
        dtype=str(args[0].dtype),
        batch=batch,
        mesh=mesh_signature(mesh),
        opts=options_signature(opts),
    )


class ExecutableCache:
    """Key -> pinned jitted program, with trace accounting."""

    def __init__(self) -> None:
        self._programs: Dict[CacheKey, Callable] = {}
        self._trace_counts: Dict[CacheKey, int] = {}
        self._pinned: set = set()

    def __len__(self) -> int:
        return len(self._programs)

    def contains(self, key: CacheKey) -> bool:
        """Pure membership probe (no counter side effects): the request
        tracer reads it to attribute a lookup as hit vs miss BEFORE
        ``get_or_build`` performs (and counts) the real lookup."""
        return key in self._programs

    def get_or_build(self, key: CacheKey, build: Callable[[], Callable]):
        """The request path: a hit returns the pinned program; a miss
        builds the pure array->array function via ``build()``, wraps it
        in a trace-counting jit, and pins it under ``key``."""
        prog = self._programs.get(key)
        if prog is not None:
            serve_count("cache_hits")
            return prog
        serve_count("cache_misses")
        fn = build()

        def traced(*args):
            # body runs at TRACE time only: this is the retrace counter
            self._trace_counts[key] = self._trace_counts.get(key, 0) + 1
            serve_count("traces")
            return fn(*args)

        prog = jax.jit(traced)
        self._programs[key] = prog
        self._trace_counts.setdefault(key, 0)
        return prog

    def warmup(self, key: CacheKey, build: Callable[[], Callable],
               example_args: Tuple) -> None:
        """Compile ``key`` ahead of traffic: trace + compile + execute
        once on representative operands, so the first real request is a
        pure execution (and, with the persistent compilation cache on, a
        restarted server pays deserialization instead of XLA)."""
        prog = self.get_or_build(key, build)
        jax.block_until_ready(prog(*example_args))
        serve_count("warmups")
        self._pinned.add(key)

    def pin(self, key: CacheKey) -> None:
        self._pinned.add(key)

    def trace_count(self, key: CacheKey) -> int:
        return self._trace_counts.get(key, 0)

    def total_traces(self) -> int:
        return sum(self._trace_counts.values())

    def assert_steady(self, before: Optional[Dict[CacheKey, int]] = None) -> None:
        """Zero-retrace assertion: every known key has been traced at
        most once (or exactly its count in the ``before`` snapshot —
        take one with ``snapshot_traces`` after warm-up, run traffic,
        then assert nothing re-traced)."""
        ref = before if before is not None else {}
        for key, count in self._trace_counts.items():
            want = ref.get(key, 1)
            if count > want:
                raise AssertionError(
                    f"serve cache retraced {key.op} {key.shape} "
                    f"{count - want} time(s) past steady state — the key "
                    "is not capturing everything the trace depends on")

    def snapshot_traces(self) -> Dict[CacheKey, int]:
        return dict(self._trace_counts)

    def clear_unpinned(self) -> None:
        for key in list(self._programs):
            if key not in self._pinned:
                del self._programs[key]
                self._trace_counts.pop(key, None)

    def clear(self) -> None:
        self._programs.clear()
        self._trace_counts.clear()
        self._pinned.clear()


# The process-wide cache the Router and smoke use; tests may build their
# own isolated instances.
executable_cache = ExecutableCache()
