"""Test configuration.

Mirrors the reference's test strategy (SURVEY.md §4): run everything on
XLA:CPU with a forced 8-device host platform — the "multi-node without a
cluster" fake backend (analogue of the reference's MPI-stub serial builds and
oversubscribed single-node MPI CI, Jenkinsfile-mpi) — with float64 enabled so
numerical checks use the same 3-eps style gates as the reference tester.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

jax.config.update("jax_enable_x64", True)
# no persistent compile cache, even where JAX_COMPILATION_CACHE_DIR is set:
# an XLA:CPU executable loaded back from the disk cache can give two of its
# collective-permutes one rendezvous id, and a mesh program whose permutes
# sit in conditional branches (getrf_pp_dist's k-loop on the 2x2 mesh) then
# aborts the process in the rendezvous.  Programs compiled in-process
# are unaffected.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def cpu_devices(n=8):
    return jax.devices("cpu")[:n]


def loop_view_copies(hlo: str, min_dim: int) -> dict:
    """{nv: copies of the view} for every while loop of compiled HLO text
    whose carry holds a square f32 (nv, nv) view with nv >= min_dim; the
    copies are the loop body's ``copy`` / ``copy-start`` instructions
    whose result has the view's shape."""
    import re

    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    out = {}
    for name in set(re.findall(r"body=%?([\w.\-]+)", hlo)):
        lines = comps[name]
        param = next(l for l in lines if "parameter(0)" in l)
        square = [int(m) for m, k in re.findall(r"f32\[(\d+),(\d+)\]", param) if m == k]
        if not square or max(square) < min_dim:
            continue
        nv = max(square)
        shape = f"f32[{nv},{nv}]"
        out[nv] = [l for l in lines
                   if (m := re.match(r"\S+ = (.*?) copy(?:-start)?\(", l))
                   and shape in m.group(1)]
    return out


def loop_copies_at_least(hlo: str, elems: int) -> dict:
    """{body: copies} for every while loop of compiled HLO text whose
    carry holds an array of at least ``elems`` elements; the copies are
    the ``copy`` / ``copy-start`` instructions with a result of at least
    ``elems`` elements, in the loop body and in every computation it
    calls (nested loops, branches, fusions).  Elements are counted, not
    shapes matched, so a copy of the same data in another shape counts."""
    import math
    import re

    def size(text):
        return max([math.prod(int(d) for d in dims.split(",") if d)
                    for dims in re.findall(r"[a-z]\d+\[([\d,]*)\]", text)] + [0])

    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line.strip())
    calls = re.compile(r"(?:calls|to_apply|body|condition|branch_computations"
                       r"|true_computation|false_computation)=\{?([%\w.\-, ]+)")
    out = {}
    for body in set(re.findall(r"body=%?([\w.\-]+)", hlo)):
        param = next(l for l in comps[body] if "parameter(0)" in l)
        if size(param.split(" parameter(0)")[0]) < elems:
            continue
        seen, todo, found = set(), [body], []
        while todo:
            name = todo.pop()
            if name in seen or name not in comps:
                continue
            seen.add(name)
            for l in comps[name]:
                m = re.match(r"\S+ = (.*?) copy(?:-start)?\(", l)
                if m and size(m.group(1)) >= elems:
                    found.append(re.split(r", (?:metadata|backend_config)=", l)[0])
                todo.extend(n.strip().lstrip("%") for c in calls.findall(l)
                            for n in c.split(","))
        out[body] = found
    return out
