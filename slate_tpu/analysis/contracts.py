"""Contract-matrix autoprover: ``python -m slate_tpu.analysis.contracts``.

Every registry entry declares the option contracts it claims
(``registry.Contract``); this CLI PROVES each declared cell statically —
abstract traces on the forced 8-device CPU mesh, nothing executes — and
fails the run on any cell that does not hold:

``off_jaxpr_identical``   the entry's jaxpr string equals its base
                          entry's (Checkpoint-off routes the plain
                          kernel, the serve tracer is host-side), or —
                          with no base — its own re-trace under the
                          option's off/neutral-forcing context
                          (NumMonitor off, UpdateImpl xla, obs forced
                          on must all leave the jaxpr untouched).
``zero_extra_collectives``  the audited comm-record MULTISET —
                          (op, payload bytes, audit multiplicity)
                          tuples from ``comm_audit`` — equals the
                          base's: the variant moves not one extra byte
                          and not one extra collective.
``bytes_invariant``       the audited total comm volume (sum of
                          bytes x multiplicity) equals the base's:
                          lookahead depths and ring-vs-doubling
                          lowerings move WHEN bytes travel, never how
                          many.

Two registry-completeness checks run first, so a new driver cannot ship
with an undeclared contract: every contract-bearing ``Option``
(Checkpoint / NumMonitor / FaultTolerance / Lookahead / BcastImpl /
UpdateImpl / serve_queue) must be consumed by at least one declaration,
and every naming-convention variant (``*_num`` / ``*_ckpt*`` /
``*_abft*`` / ``*_flight`` / ``*_queue``) must declare (or belong to a
family that declares) the matching contract.

Exit codes mirror lint: 0 proven (or waived), 1 failed cells, 2
internal error.

Options:
  --waivers PATH      alternate waiver file (default analysis/waivers.cfg)
  --only PATTERN      restrict proved entries to names containing PATTERN
  --list              print the declared contract matrix and exit
  --seed-violation K  inject a known-bad declaration (undeclared-contract |
                      broken-contract) — proves the prover trips
"""

from __future__ import annotations

import argparse
import os
import sys
from collections import Counter
from typing import Dict, List, Optional, Tuple

# environment must be pinned before jax is imported anywhere below
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

from ..types import Option  # noqa: E402  (no jax dependency)
from .findings import Finding  # noqa: E402

# Options the contract matrix covers; "obs" is the ambient observability
# layer (forced on rather than off — recording must be trace-neutral),
# "serve_queue" the service layer (ISSUE 19: window dispatch must route
# the Router's own programs — service-off is byte-identical dispatch).
CONTRACT_OPTIONS = (
    Option.Checkpoint, Option.NumMonitor, Option.FaultTolerance,
    Option.Lookahead, Option.BcastImpl, Option.UpdateImpl, "obs",
    "serve_queue",
)

# naming-convention rules: (predicate kind, token, option, scope).
# per-entry scope: the entry itself must declare the option.  family
# scope: ANY entry whose name shares the family stem (the name cut at
# the token) may carry the declaration — the *_ckpt_seg segment jits ARE
# the checkpoint mechanism, so the family's Checkpoint contract lives on
# the *_ckpt_off entry that proves off-routing.
NAMING_RULES: Tuple[Tuple[str, str, object, str], ...] = (
    ("suffix", "_num", Option.NumMonitor, "entry"),
    ("infix", "_ckpt", Option.Checkpoint, "family"),
    ("infix", "_abft", Option.FaultTolerance, "family"),
    ("suffix", "_flight", "obs", "entry"),
    # *_traced entries run under an ARMED TraceContext (ISSUE 17): the
    # request-attribution spine must prove it is host-side only
    ("suffix", "_traced", "obs", "entry"),
    # *_queue entries are the BatchQueue's window-dispatch bodies (ISSUE
    # 19): the queue is host-side scheduling, so each must prove its
    # program equals the direct Router/packed driver's
    ("suffix", "_queue", "serve_queue", "entry"),
    # *_upd_* entries pin an Option.UpdateImpl lowering (PR 20): each
    # must prove its cell — xla trace-identical to the base, pallas
    # bytes-invariant against its xla twin
    ("infix", "_upd", Option.UpdateImpl, "entry"),
)


def _opt_name(option) -> str:
    return option.name if isinstance(option, Option) else str(option)


def _matches(name: str, kind: str, token: str) -> bool:
    return name.endswith(token) if kind == "suffix" else token in name


def check_registry_completeness(registry) -> List[Finding]:
    """Pure structural checks over the declared matrix (no tracing)."""
    out: List[Finding] = []
    declared_options = {
        c.option for spec in registry.values() for c in spec.contracts
    }
    for opt in CONTRACT_OPTIONS:
        if opt not in declared_options:
            out.append(Finding("contract-option-unconsumed", "registry", (
                f"Option {_opt_name(opt)} has no contract declaration on "
                "any registry entry — the option is ungated")))

    # base references must exist and differ from the entry
    for name, spec in sorted(registry.items()):
        for c in spec.contracts:
            if c.base is not None and c.base not in registry:
                out.append(Finding("contract-undeclared", f"contract:{name}", (
                    f"contract base {c.base!r} is not a registry entry")))

    # naming-convention variants must declare the matching contract
    for kind, token, option, scope in NAMING_RULES:
        family_declared = {
            spec.name.split(token)[0]
            for spec in registry.values()
            if _matches(spec.name, kind, token)
            and any(c.option == option for c in spec.contracts)
        }
        for name, spec in sorted(registry.items()):
            if not _matches(name, kind, token):
                continue
            if scope == "entry":
                ok = any(c.option == option for c in spec.contracts)
            else:
                ok = name.split(token)[0] in family_declared
            if not ok:
                out.append(Finding("contract-undeclared", f"contract:{name}", (
                    f"naming convention '*{token}*' requires a declared "
                    f"{_opt_name(option)} contract"
                    + ("" if scope == "entry" else
                       f" somewhere in the {name.split(token)[0]!r} family")
                    + " — a variant cannot ship with its contract "
                    "unproven")))
    return out


def _off_context(option):
    """The context that forces ``option`` to its trace-neutral pole for
    a self-compared off_jaxpr_identical cell."""
    if option == "obs":
        from .. import obs

        return obs.force_enabled()
    if option is Option.NumMonitor:
        from ..obs.numerics import use_num_monitor

        return use_num_monitor("off")
    if option is Option.UpdateImpl:
        from ..ops.pallas_ops import use_update_impl

        return use_update_impl("xla")
    raise KeyError(
        f"no off-forcing context for {_opt_name(option)}; declare the "
        "contract with an explicit base entry instead"
    )


class _Prover:
    """Trace cache + the three contract checkers.  Each entry is traced
    at most once per run (clear_caches first, so the comm-audit hooks —
    which record at trace time only — see every inner jit fresh)."""

    def __init__(self, ctx, registry):
        self.ctx = ctx
        self.registry = registry
        self._built: Dict[str, tuple] = {}
        self._traced: Dict[str, Tuple[str, list]] = {}

    def _build(self, name):
        if name not in self._built:
            self._built[name] = self.registry[name].build(self.ctx)
        return self._built[name]

    def trace(self, name) -> Tuple[str, list]:
        if name not in self._traced:
            import jax

            from ..parallel.comm import comm_audit

            fn, args = self._build(name)
            jax.clear_caches()
            with comm_audit() as records:
                closed = jax.make_jaxpr(fn)(*args)
            self._traced[name] = (str(closed.jaxpr), list(records))
        return self._traced[name]

    def trace_under(self, name, option) -> str:
        """Re-trace ``name`` under the option's off-forcing context (no
        cache clearing: the jaxpr is complete either way, and the cell
        only compares jaxprs)."""
        import jax

        fn, args = self._build(name)
        with _off_context(option):
            return str(jax.make_jaxpr(fn)(*args).jaxpr)

    def prove(self, name, contract) -> List[Finding]:
        cell = (f"contract:{name}:{_opt_name(contract.option)}:"
                f"{contract.klass}")
        try:
            if contract.klass == "off_jaxpr_identical":
                ja, _ = self.trace(name)
                if contract.base is None:
                    jb = self.trace_under(name, contract.option)
                    other = "its own re-trace under the off-forcing context"
                else:
                    jb, _ = self.trace(contract.base)
                    other = f"base entry {contract.base!r}"
                if ja != jb:
                    return [Finding("contract-off-jaxpr", cell, (
                        f"jaxpr differs from {other} — the option's off "
                        "pole is NOT trace-neutral "
                        f"({len(ja)} vs {len(jb)} chars)"))]
            elif contract.klass == "zero_extra_collectives":
                _, ra = self.trace(name)
                _, rb = self.trace(contract.base)
                ca, cb = Counter(ra), Counter(rb)
                if ca != cb:
                    extra = ca - cb
                    lost = cb - ca
                    return [Finding("contract-extra-collectives", cell, (
                        f"audited comm records differ from base "
                        f"{contract.base!r}: {sum(extra.values())} "
                        f"extra / {sum(lost.values())} missing (e.g. "
                        f"{next(iter(extra or lost))!r})"))]
            elif contract.klass == "bytes_invariant":
                _, ra = self.trace(name)
                _, rb = self.trace(contract.base)
                va = sum(b * m for _, b, m in ra)
                vb = sum(b * m for _, b, m in rb)
                if va != vb:
                    return [Finding("contract-bytes", cell, (
                        f"audited comm volume {va} B differs from base "
                        f"{contract.base!r}'s {vb} B — the option moved "
                        "bytes it promised not to"))]
            else:  # pragma: no cover — register() validates klass
                return [Finding("contract-trace-error", cell,
                                f"unknown contract class {contract.klass!r}")]
        except Exception as e:  # a broken build/trace is itself a finding
            return [Finding("contract-trace-error", cell,
                            f"{type(e).__name__}: {e}")]
        return []


def _seed_violation(kind: str, registry) -> None:
    """Register deliberately-broken declarations so the prover provably
    trips (the contracts sibling of lint's --seed-violation)."""
    import jax.numpy as jnp

    from .registry import Contract, register

    if kind == "undeclared-contract":
        # a *_num variant with NO declared NumMonitor contract: the
        # naming-convention completeness check must fail it
        @register("seeded_monitored_num", tags=("num",))
        def _seeded_num(ctx):
            x = jnp.ones((8, 8))
            return (lambda t: t * 2.0), (x,)

    elif kind == "broken-contract":
        # a declared zero-extra contract that is FALSE: the variant
        # issues a collective its base never does
        from ..parallel.comm import psum_a, shard_map_compat

        def _pair(extra):
            import jax
            import numpy as np
            from jax.sharding import Mesh, PartitionSpec as P

            def build(ctx):
                devs = jax.devices("cpu")[:4]
                mesh = Mesh(np.asarray(devs).reshape(2, 2), ("p", "q"))
                x = jnp.zeros((4, 4))

                def fn(x):
                    def kernel(t):
                        t = psum_a(t, "p")
                        if extra:
                            t = t + psum_a(t, "q")
                        return t

                    return shard_map_compat(
                        kernel,
                        mesh=mesh,
                        in_specs=(P("p", "q"),),
                        out_specs=P("p", "q"),
                        check_vma=False,
                    )(x)

                return fn, (x,)

            return build

        register("seeded_contract_base")(_pair(extra=False))
        register("seeded_contract_broken", contracts=(
            Contract(Option.NumMonitor, "zero_extra_collectives",
                     "seeded_contract_base"),
        ))(_pair(extra=True))

    else:
        raise SystemExit(f"unknown --seed-violation kind: {kind}")


def run(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="slate_contracts")
    ap.add_argument("--waivers", default=None)
    ap.add_argument("--only", default=None)
    ap.add_argument("--list", action="store_true", dest="list_cells")
    ap.add_argument(
        "--seed-violation",
        default=None,
        choices=["undeclared-contract", "broken-contract"],
    )
    args = ap.parse_args(argv)

    from . import registry as reg
    from .waivers import (
        CONTRACT_RULES,
        DEFAULT_WAIVER_FILE,
        check_hygiene,
        check_stale,
        load_waivers,
    )

    if args.seed_violation:
        _seed_violation(args.seed_violation, reg.REGISTRY)

    cells = [
        (name, c)
        for name, spec in sorted(reg.REGISTRY.items())
        for c in spec.contracts
    ]
    if args.list_cells:
        for name, c in cells:
            print(f"{name:36s} {_opt_name(c.option):14s} {c.klass:24s} "
                  f"base={c.base or '(self)'}")
        print(f"{len(cells)} declared cell(s) over "
              f"{len({n for n, _ in cells})} driver(s)")
        return 0

    findings: List[Finding] = []
    findings += check_registry_completeness(reg.REGISTRY)

    import jax

    # mirror lint: drivers trace in f64 on the shared CPU mesh
    jax.config.update("jax_enable_x64", True)

    ctx = reg.make_ctx()
    prover = _Prover(ctx, reg.REGISTRY)
    n_proved = 0
    by_class: Counter = Counter()
    for name, c in cells:
        if args.only and args.only not in name:
            continue
        cell_findings = prover.prove(name, c)
        findings += cell_findings
        if not cell_findings:
            n_proved += 1
            by_class[c.klass] += 1

    wpath = args.waivers or DEFAULT_WAIVER_FILE
    waivers = load_waivers(args.waivers)
    findings += check_hygiene(waivers, set(reg.REGISTRY),
                              set(reg.DONATIONS), wpath)
    hard, waived = [], []
    for f in findings:
        w = waivers.match(f)
        (waived if w else hard).append((f, w))
    full_run = not (args.only or args.seed_violation)
    if full_run:
        hard += [(f, None)
                 for f in check_stale(waivers, CONTRACT_RULES, wpath)]

    classes = ", ".join(f"{k}={v}" for k, v in sorted(by_class.items()))
    print(
        f"slate_contracts: {n_proved}/{len(cells)} cell(s) proved across "
        f"{len({n for n, _ in cells})} driver(s) ({classes}), "
        f"{len(findings)} finding(s), {len(waived)} waived"
    )
    for f, w in waived:
        print(f"  WAIVED {f.render()}  [{w.reason}]")
    for f, _ in hard:
        print(f"  FAIL   {f.render()}")
    if hard:
        print(f"slate_contracts: FAILED with {len(hard)} unproven "
              "cell(s)/finding(s)")
        return 1
    print("slate_contracts: OK")
    return 0


def main() -> None:
    try:
        sys.exit(run())
    except SystemExit:
        raise
    except Exception as e:  # pragma: no cover
        print(f"slate_contracts: internal error: {type(e).__name__}: {e}")
        sys.exit(2)


if __name__ == "__main__":
    main()
