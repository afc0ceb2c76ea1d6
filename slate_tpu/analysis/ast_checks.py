"""AST pass over the slate_tpu sources.

Three rules, none of which need to import the modules under inspection:

- ``ast-shard-map-import``: ``shard_map`` imported straight from jax
  anywhere but ``parallel/comm.py`` — every kernel must come through
  ``shard_map_compat`` so the installed signature is met in one place.
- ``ast-raw-collective``: a raw ``lax.psum``/``all_gather``/
  ``psum_scatter``/``ppermute``/``all_to_all`` call outside
  ``parallel/comm.py`` — the audited wrappers (``psum_a`` etc.) exist so
  the comm-volume audit sees every byte.
- ``ast-kwargs``: a keyword passed to a known JAX API that the *installed*
  signature does not accept (e.g. the retired ``check_rep`` spelling of
  ``shard_map``'s ``check_vma``): the lint compares call sites against
  ``inspect.signature`` of the running JAX, so CI fails at lint time
  instead of at the 30th kernel launch.
- ``ast-masked-psum-bcast``: ``psum(where(...), axis)`` /
  ``psum_a(where(...), axis)`` outside ``parallel/comm.py`` — the
  masked-psum broadcast idiom pays ~2x the bytes of a rooted broadcast
  and bypasses ``Option.BcastImpl``; new drivers must use the comm
  engine's ``bcast_from_row``/``bcast_from_col``/``reduce_to_*``
  wrappers (genuine masked REDUCTIONS whose mask is not a broadcast,
  e.g. tuple-axis owner selects, take a waiver naming the site).
"""

from __future__ import annotations

import ast
import inspect
import os
from typing import Dict, List, Optional

from .findings import Finding

RAW_COLLECTIVES = frozenset(
    {"psum", "psum_scatter", "all_gather", "ppermute", "all_to_all"}
)
# the psum spellings the masked-psum-broadcast rule matches: the raw
# collective and its audited wrapper (the other audited wrappers —
# all_gather_a / psum_scatter_a / ppermute_a, the broadcast engine's hop
# verb — are not reductions, so the idiom cannot ride them)
_PSUM_NAMES = frozenset({"psum", "psum_a"})
COMM_MODULE = os.path.join("parallel", "comm.py")

# (rel, source) pairs injected by lint --seed-violation for rules that
# operate on sources rather than registry drivers (the masked-psum seed)
SEEDED_SOURCES: list = []


def _installed_signatures() -> Dict[str, frozenset]:
    """Parameter-name sets of the JAX APIs whose call sites we validate."""
    import jax

    from jax import shard_map as _sm

    sigs = {}
    for name, fn in (("shard_map", _sm), ("jit", jax.jit)):
        try:
            params = inspect.signature(fn).parameters
        except (TypeError, ValueError):  # pragma: no cover
            continue
        if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
            continue  # **kwargs swallows anything; nothing to validate
        sigs[name] = frozenset(params)
    return sigs


def _call_name(node: ast.Call) -> Optional[str]:
    """Trailing name of the called function: lax.psum -> 'psum'."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _call_root(node: ast.Call) -> Optional[str]:
    """Leading name: jax.lax.psum -> 'jax', lax.psum -> 'lax'."""
    f = node.func
    while isinstance(f, ast.Attribute):
        f = f.value
    return f.id if isinstance(f, ast.Name) else None


def check_file(path: str, rel: str, sigs: Dict[str, frozenset]) -> List[Finding]:
    with open(path) as fh:
        src = fh.read()
    return check_source(src, rel, sigs, filename=path)


def check_source(
    src: str, rel: str, sigs: Dict[str, frozenset], filename: str = "<src>"
) -> List[Finding]:
    try:
        tree = ast.parse(src, filename=filename)
    except SyntaxError as e:  # a file that cannot parse is its own finding
        return [Finding("ast-parse", f"{rel}:{e.lineno}", str(e))]

    in_comm = rel.replace(os.sep, "/").endswith("parallel/comm.py")
    out: List[Finding] = []

    # first pass: aliases that could smuggle collectives past a naive
    # name match — `from jax.lax import psum [as p]`, `import jax.lax as L`
    fn_aliases: Dict[str, str] = {}  # local name -> collective
    mod_aliases = {"lax", "jax"}  # roots whose .psum/... is a collective
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod == "jax" or mod.startswith("jax."):
                for a in node.names:
                    if a.name in RAW_COLLECTIVES:
                        fn_aliases[a.asname or a.name] = a.name
                    if a.name == "lax":
                        mod_aliases.add(a.asname or a.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("jax", "jax.lax"):
                    mod_aliases.add((a.asname or a.name).split(".")[0])

    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            # any raw-shard_map import outside comm.py — from jax OR
            # re-imported from comm — bypasses the compat kwarg mapping
            if not in_comm and any(a.name == "shard_map" for a in node.names):
                src = node.module or "."
                out.append(
                    Finding(
                        "ast-shard-map-import",
                        f"{rel}:{node.lineno}",
                        f"raw shard_map import from {src} — use "
                        "parallel.comm.shard_map_compat",
                    )
                )
            continue
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name is None:
            continue
        root = _call_root(node)

        raw_attr = name in RAW_COLLECTIVES and root in mod_aliases
        raw_bare = (
            isinstance(node.func, ast.Name) and node.func.id in fn_aliases
        )
        if not in_comm and (raw_attr or raw_bare):
            coll = fn_aliases.get(name, name)
            out.append(
                Finding(
                    "ast-raw-collective",
                    f"{rel}:{node.lineno}",
                    f"raw lax.{coll} outside parallel/comm.py — use the "
                    f"audited wrapper ({coll}_a)",
                )
            )

        # masked-psum broadcast idiom: psum(where(...), axis) — whether
        # through the audited wrapper or raw — outside the comm engine.
        # The where-mask fed straight into an all-reduce is the broadcast
        # pattern the ppermute engine replaces at half the bytes.
        if (
            not in_comm
            and (name in _PSUM_NAMES or fn_aliases.get(name) == "psum")
            and node.args
            and isinstance(node.args[0], ast.Call)
            and _call_name(node.args[0]) == "where"
        ):
            out.append(
                Finding(
                    "ast-masked-psum-bcast",
                    f"{rel}:{node.lineno}",
                    "masked-psum broadcast idiom (psum(where(owner-mask), "
                    "axis)) outside parallel/comm.py — use the broadcast "
                    "engine (bcast_from_row/bcast_from_col/reduce_to_*) so "
                    "Option.BcastImpl can lower it to ppermute at half the "
                    "bytes",
                )
            )

        # kwarg drift: direct calls (shard_map_compat forwards to the
        # same signature)...
        base = sigs.get("shard_map" if name == "shard_map_compat" else name)
        if base is not None:
            for kw in node.keywords:
                if kw.arg is not None and kw.arg not in base:
                    out.append(
                        Finding(
                            "ast-kwargs",
                            f"{rel}:{node.lineno}",
                            f"{name}() called with keyword {kw.arg!r} the "
                            "installed JAX signature does not accept",
                        )
                    )
        # ...and functools.partial(jax.jit, static_argnums=...) style
        if name == "partial" and node.args:
            target = node.args[0]
            tname = None
            if isinstance(target, ast.Attribute):
                tname = target.attr
            elif isinstance(target, ast.Name):
                tname = target.id
            if tname in sigs:
                for kw in node.keywords:
                    if kw.arg is not None and kw.arg not in sigs[tname]:
                        out.append(
                            Finding(
                                "ast-kwargs",
                                f"{rel}:{node.lineno}",
                                f"partial({tname}, ...) passes keyword "
                                f"{kw.arg!r} the installed JAX signature "
                                "does not accept",
                            )
                        )
    return out


def check_tree(root: Optional[str] = None) -> List[Finding]:
    """Lint every .py file under the slate_tpu package."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pkg_parent = os.path.dirname(root)
    sigs = _installed_signatures()
    out: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fname in sorted(filenames):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            rel = os.path.relpath(path, pkg_parent)
            out.extend(check_file(path, rel, sigs))
    for rel, src in SEEDED_SOURCES:  # lint --seed-violation masked-psum
        out.extend(check_source(src, rel, sigs))
    return out
