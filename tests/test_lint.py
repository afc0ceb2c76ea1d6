"""slate_lint (ISSUE 1 tentpole) tests: each invariant check flags its
seeded violation, the shipped tree is clean, and the CLI wires exit codes
correctly.  The full driver trace runs in CI (ci/run_ci.sh); here we lint
a fast subset in-process plus the pure passes."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import cpu_devices

from slate_tpu.analysis.jaxpr_checks import (
    check_collective_axes,
    check_comm_upcast,
    check_donation,
    check_dot_precision,
)


def _mesh_psum_jaxpr(axes):
    """Trace a psum-over-first-axis kernel on a 2x2 mesh named ``axes``."""
    from jax.sharding import Mesh, PartitionSpec as P

    from slate_tpu.parallel.comm import shard_map_compat

    mesh = Mesh(np.asarray(cpu_devices(4)).reshape(2, 2), axes)
    spec = P(*axes)
    fn = shard_map_compat(
        lambda x: jax.lax.psum(x, axes[0]),
        mesh=mesh,
        in_specs=(spec,),
        out_specs=P(None, axes[1]),
        check_vma=False,
    )
    return jax.make_jaxpr(fn)(jnp.zeros((4, 4)))


def test_flags_bad_axis_name():
    closed = _mesh_psum_jaxpr(("row", "col"))
    found = check_collective_axes(closed, ("p", "q"), "driver:toy")
    assert len(found) == 1
    assert found[0].rule == "axis-name" and "row" in found[0].message


def test_accepts_declared_axes():
    closed = _mesh_psum_jaxpr(("p", "q"))
    assert check_collective_axes(closed, ("p", "q"), "driver:toy") == []


def test_flags_missing_precision():
    closed = jax.make_jaxpr(lambda a: a @ a)(jnp.zeros((4, 4)))
    found = check_dot_precision(closed, "driver:toy")
    assert len(found) == 1 and found[0].rule == "precision"


def test_accepts_highest_precision_and_int_dots():
    closed = jax.make_jaxpr(
        lambda a: jnp.einsum("ij,jk->ik", a, a, precision=jax.lax.Precision.HIGHEST)
    )(jnp.zeros((4, 4)))
    assert check_dot_precision(closed, "driver:toy") == []
    # integer dots have no precision semantics
    closed_i = jax.make_jaxpr(lambda a: a @ a)(jnp.zeros((4, 4), jnp.int32))
    assert check_dot_precision(closed_i, "driver:toy") == []


def test_flags_silent_f64_upcast_of_comm_payload():
    def fn(x):
        return jax.lax.psum(x.astype(jnp.float64), "i")

    closed = jax.make_jaxpr(jax.vmap(fn, axis_name="i"))(
        jnp.zeros((2, 4), jnp.float32)
    )
    found = check_comm_upcast(closed, "driver:toy")
    assert len(found) == 1 and found[0].rule == "comm-upcast"
    # an all-f64 driver psumming f64 is fine
    closed64 = jax.make_jaxpr(jax.vmap(fn, axis_name="i"))(
        jnp.zeros((2, 4), jnp.float64)
    )
    assert check_comm_upcast(closed64, "driver:toy") == []


def test_flags_unusable_donation():
    found = check_donation(
        lambda x: x[:300, :300], (jnp.zeros((320, 320)),), (0,), "donation:toy"
    )
    assert len(found) == 1 and found[0].rule == "donation"
    # shape-preserving donation is aliasable
    assert (
        check_donation(lambda x: x * 2, (jnp.zeros((320, 320)),), (0,), "d:ok")
        == []
    )


def test_flags_second_donation_with_single_output():
    """Two same-aval donations can alias only one output buffer: the
    shared-pool matching must flag the second one."""

    def fn(x, y):
        return x + y  # one (n, n) output

    args = (jnp.zeros((16, 16)), jnp.zeros((16, 16)))
    found = check_donation(fn, args, (0, 1), "donation:toy2")
    assert len(found) == 1 and found[0].rule == "donation"


def test_shard_map_compat_rejects_unknown_kwarg():
    import pytest as _pytest

    from jax.sharding import Mesh, PartitionSpec as P

    from slate_tpu.parallel.comm import shard_map_compat

    mesh = Mesh(np.asarray(cpu_devices(4)).reshape(2, 2), ("p", "q"))
    with _pytest.raises(TypeError, match="check_vm"):
        shard_map_compat(
            lambda x: x,
            mesh=mesh,
            in_specs=(P("p", "q"),),
            out_specs=P("p", "q"),
            check_vm=False,  # typo: must fail fast, not silently drop
        )


def test_loop_audit_one_scope_does_not_mask_second_loop():
    """A properly scoped loop must not hide a second, unscoped loop."""
    from slate_tpu.analysis.jaxpr_checks import check_loop_audit
    from slate_tpu.parallel.comm import audit_scope, comm_audit, psum_a

    def two_loops(x):
        with audit_scope(3):
            x = jax.lax.fori_loop(0, 3, lambda i, a: a + psum_a(a, "i"), x)
        # second loop: audited wrapper but NO scope
        return jax.lax.fori_loop(0, 5, lambda i, a: a + psum_a(a, "i"), x)

    with comm_audit() as recs:
        closed = jax.make_jaxpr(jax.vmap(two_loops, axis_name="i"))(
            jnp.zeros((2, 4))
        )
    found = check_loop_audit(closed, list(recs), "driver:toy")
    assert len(found) == 1 and found[0].rule == "loop-audit"


def test_staged_potrf_donation_contract_clean():
    """The (fixed) staged left-looking potrf path: both its donating jit
    stages must be aliasable (the float64[320,320] warning regression)."""
    from slate_tpu.analysis.registry import DONATIONS, make_ctx

    ctx = make_ctx()
    for name in ("potrf_ll_staged_step", "potrf_ll_staged_finale"):
        fn, args, donate = DONATIONS[name].build(ctx)
        assert check_donation(fn, args, donate, name) == [], name


def test_grid_invariants_clean():
    from slate_tpu.analysis.grid_checks import run_grid_checks

    assert run_grid_checks() == []


def test_ast_pass_clean_or_waived():
    from slate_tpu.analysis.ast_checks import check_tree
    from slate_tpu.analysis.waivers import load_waivers

    waivers = load_waivers()
    unwaived = [f for f in check_tree() if waivers.match(f) is None]
    assert unwaived == [], [f.render() for f in unwaived]


def test_ast_pass_flags_bad_kwarg(tmp_path):
    from slate_tpu.analysis.ast_checks import _installed_signatures, check_file

    bad = tmp_path / "bad_kernel.py"
    bad.write_text(
        "from jax.experimental.shard_map import shard_map\n"
        "import jax.lax as lax\n"
        "def k(f, mesh, spec, x):\n"
        "    y = lax.psum(x, 'p')\n"
        "    return shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,\n"
        "                     totally_bogus_kwarg=False)(y)\n"
    )
    found = check_file(str(bad), "toy/bad_kernel.py", _installed_signatures())
    rules = sorted(f.rule for f in found)
    assert rules == ["ast-kwargs", "ast-raw-collective", "ast-shard-map-import"]
    kw = [f for f in found if f.rule == "ast-kwargs"][0]
    assert "totally_bogus_kwarg" in kw.message


def test_ast_pass_rep_aliases_only_via_compat(tmp_path):
    """check_vma is the installed spelling; the retired check_rep is
    kwarg drift even through shard_map_compat, and a comm re-import of
    raw shard_map is flagged too."""
    from slate_tpu.analysis.ast_checks import _installed_signatures, check_file

    ok = tmp_path / "ok_kernel.py"
    ok.write_text(
        "def k(shard_map_compat, f, mesh, spec, x):\n"
        "    return shard_map_compat(f, mesh=mesh, in_specs=spec, out_specs=spec,\n"
        "                            check_vma=False)(x)\n"
    )
    assert check_file(str(ok), "toy/ok_kernel.py", _installed_signatures()) == []

    old = tmp_path / "old_kernel.py"
    old.write_text(
        "def k(shard_map_compat, f, mesh, spec, x):\n"
        "    return shard_map_compat(f, mesh=mesh, in_specs=spec, out_specs=spec,\n"
        "                            check_rep=False)(x)\n"
    )
    found = check_file(str(old), "toy/old_kernel.py", _installed_signatures())
    assert [f.rule for f in found] == ["ast-kwargs"]

    bad = tmp_path / "bad_kernel2.py"
    bad.write_text(
        "from slate_tpu.parallel.comm import shard_map\n"
        "def k(f, mesh, spec, x):\n"
        "    return shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec,\n"
        "                     check_vma=False)(x)\n"
    )
    found = check_file(str(bad), "toy/bad_kernel2.py", _installed_signatures())
    assert "ast-shard-map-import" in sorted(f.rule for f in found)


def test_ast_pass_catches_aliased_collectives(tmp_path):
    """Aliased imports must not smuggle raw collectives past the rule."""
    from slate_tpu.analysis.ast_checks import _installed_signatures, check_file

    f = tmp_path / "sneaky.py"
    f.write_text(
        "from jax.lax import psum as p\n"
        "import jax.lax as L\n"
        "def k(x):\n"
        "    return p(x, 'p') + L.all_gather(x, 'q')\n"
    )
    found = check_file(str(f), "toy/sneaky.py", _installed_signatures())
    msgs = sorted(x.message for x in found if x.rule == "ast-raw-collective")
    assert len(msgs) == 2 and "psum" in msgs[1] and "all_gather" in msgs[0], msgs


def test_shard_map_compat_rejects_retired_check_rep():
    import pytest as _pytest

    from jax.sharding import Mesh, PartitionSpec as P

    from slate_tpu.parallel.comm import shard_map_compat

    mesh = Mesh(np.asarray(cpu_devices(4)).reshape(2, 2), ("p", "q"))
    with _pytest.raises(TypeError, match="check_rep"):
        shard_map_compat(
            lambda x: x,
            mesh=mesh,
            in_specs=(P("p", "q"),),
            out_specs=P("p", "q"),
            check_rep=False,
        )


def test_lint_traces_summa_clean():
    """One registered driver end-to-end in-process: trace + all jaxpr
    checks on the real SUMMA kernel come back clean."""
    from slate_tpu.analysis.jaxpr_checks import check_loop_audit
    from slate_tpu.analysis.registry import REGISTRY, make_ctx
    from slate_tpu.parallel.comm import comm_audit

    ctx = make_ctx()
    fn, args = REGISTRY["gemm_summa_c"].build(ctx)
    jax.clear_caches()
    with comm_audit() as recs:
        closed = jax.make_jaxpr(fn)(*args)
    findings = (
        check_collective_axes(closed, ("p", "q"), "driver:gemm_summa_c")
        + check_dot_precision(closed, "driver:gemm_summa_c")
        + check_comm_upcast(closed, "driver:gemm_summa_c")
        + check_loop_audit(closed, list(recs), "driver:gemm_summa_c")
    )
    assert findings == [], [f.render() for f in findings]


def test_cli_exit_codes():
    """CLI: clean (fast passes) exits 0; a seeded unusable donation exits 1.
    --skip-trace keeps this at import cost rather than 24 driver traces."""
    base = [sys.executable, "-m", "slate_tpu.analysis.lint", "--skip-trace"]
    r = subprocess.run(base, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    r2 = subprocess.run(
        base + ["--seed-violation", "donation"],
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert r2.returncode == 1, r2.stdout + r2.stderr
    assert "donation" in r2.stdout


def test_ast_pass_flags_masked_psum_bcast(tmp_path):
    """ISSUE 5: the masked-psum broadcast idiom outside comm.py is a
    finding (it pays ~2x a rooted broadcast's bytes and bypasses
    Option.BcastImpl); routing through the engine wrappers is clean."""
    from slate_tpu.analysis.ast_checks import (
        _installed_signatures, check_file, check_source,
    )

    bad = tmp_path / "masked.py"
    bad.write_text(
        "from slate_tpu.parallel.comm import psum_a\n"
        "import jax.numpy as jnp\n"
        "def k(x, me, owner):\n"
        "    return psum_a(jnp.where(me == owner, x, 0), 'q')\n"
    )
    found = check_file(str(bad), "toy/masked.py", _installed_signatures())
    rules = [f.rule for f in found]
    assert rules == ["ast-masked-psum-bcast"], found

    ok = (
        "from slate_tpu.parallel.comm import bcast_from_col, psum_a\n"
        "import jax.numpy as jnp\n"
        "def k(x, me, owner, masked):\n"
        "    a = bcast_from_col(jnp.where(me == owner, x, 0), owner)\n"
        "    return a + psum_a(masked, 'q')\n"  # pre-masked var: a reduction
    )
    assert check_source(ok, "toy/ok.py", _installed_signatures()) == []

    # inside parallel/comm.py the idiom IS the psum lowering itself
    in_comm = (
        "import jax.numpy as jnp\n"
        "from jax import lax\n"
        "def bcast(x, owner):\n"
        "    me = lax.axis_index('q')\n"
        "    return lax.psum(jnp.where(me == owner, x, 0), 'q')\n"
    )
    assert check_source(in_comm, "slate_tpu/parallel/comm.py",
                        _installed_signatures()) == []


def test_loop_audit_counts_switch_branches_once():
    """The broadcast engine dispatches rooted hop schedules through
    lax.switch: exactly one branch executes per trip, so the loop-audit
    eqn count must take the max over cond branches, not their sum —
    otherwise every engine-lowered driver would need q x the audit
    records it can honestly emit."""
    from slate_tpu.analysis.jaxpr_checks import (
        check_loop_audit, count_loop_collectives,
    )
    from slate_tpu.parallel.comm import audit_scope, comm_audit, psum_a

    def body(i, acc):
        # 3 branches, each with ONE collective; one audited record is
        # emitted per loop step by the shared recording below
        def br(k):
            return lambda a: a + jax.lax.psum(a * k, "i")

        return acc + jax.lax.switch(i % 3, [br(0), br(1), br(2)], acc)

    def fn(x):
        with audit_scope(3):
            # the engine's pattern: record once per hop, outside the switch
            _ = psum_a(x, "i")  # stands in for the per-hop _rec call
            return jax.lax.fori_loop(0, 3, body, x)

    with comm_audit() as recs:
        closed = jax.make_jaxpr(jax.vmap(fn, axis_name="i"))(jnp.zeros((2, 4)))
    # 3 branches x 1 collective counts as ONE executed collective
    assert count_loop_collectives(closed) == 1
    assert check_loop_audit(closed, list(recs), "driver:toy") == []


def test_lint_cli_masked_psum_seed():
    """--seed-violation masked-psum works with --skip-trace and exits 1."""
    base = [sys.executable, "-m", "slate_tpu.analysis.lint", "--skip-trace"]
    r = subprocess.run(
        base + ["--seed-violation", "masked-psum"],
        capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 1, r.stdout + r.stderr
    assert "ast-masked-psum-bcast" in r.stdout
