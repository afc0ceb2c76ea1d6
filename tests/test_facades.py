"""Facade tests: LAPACK-named API, C API (native lib via ctypes), tracing,
tester harness — reference analogues lapack_api/, c_api/, Trace, testsweeper."""

import ctypes
import os
import shutil
import subprocess

import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_lapack_api_names():
    from slate_tpu import lapack_api as la

    rng = np.random.default_rng(0)
    n = 24
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    xt = rng.standard_normal((n, 2))
    x, f, info = la.slate_dgesv(a, a @ xt)
    assert info == 0
    assert np.abs(np.asarray(x) - xt).max() < 1e-10
    # bare names + float32 variant exist
    l, info = la.dpotrf(a @ a.T + n * np.eye(n))
    assert info == 0
    c = la.sgemm("N", "N", n, n, n, 1.0, a, a, 0.0, np.zeros((n, n)))
    assert np.asarray(c).dtype == np.float32


def test_lapack_api_gecon():
    from slate_tpu import lapack_api as la

    n = 30
    rng = np.random.default_rng(1)
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    rcond = la.dgecon("1", a)
    assert 0 < rcond <= 1


def _libpython_available():
    """True when the shared libpython the native build links against
    (the -lpythonX.Y tokens hardcoded in native/build.sh) is findable by
    the linker.  Some containers ship a different interpreter (or only a
    static one) — there the C-API build cannot succeed and the tests
    skip with a clear reason instead of erroring (pre-existing breakage,
    CHANGES.md PR 3)."""
    import ctypes.util
    import glob
    import re
    import sysconfig

    build = open(os.path.join(_ROOT, "native", "build.sh")).read()
    needed = set(re.findall(r"-l(python[\w.]+)", build)) or {"python3"}
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    for lib in needed:
        if not (ctypes.util.find_library(lib)
                or glob.glob(os.path.join(libdir, f"lib{lib}.so*"))):
            return False
    return True


def _build_native():
    lib = os.path.join(_ROOT, "native", "lib", "libslatetpu_c.so")
    if not os.path.exists(lib):
        if shutil.which("g++") is None:
            pytest.skip("no g++")
        if not _libpython_available():
            pytest.skip(
                "libpython shared library not available in this container "
                "(native C-API build links -lpython; cannot succeed)"
            )
        subprocess.run(["bash", os.path.join(_ROOT, "native", "build.sh")], check=True)
    return lib


def test_c_api_dgesv():
    lib_path = _build_native()
    lib = ctypes.CDLL(lib_path)
    lib.slate_tpu_dgesv.argtypes = [ctypes.c_int64] * 2 + [ctypes.c_void_p] * 3
    n = 16
    rng = np.random.default_rng(2)
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    xt = rng.standard_normal((n, 1))
    b = a @ xt
    x = np.zeros_like(xt)
    info = lib.slate_tpu_dgesv(n, 1, a.ctypes.data, b.ctypes.data, x.ctypes.data)
    assert info == 0
    assert np.abs(x - xt).max() < 1e-10


def test_c_api_dposv_and_gels():
    lib_path = _build_native()
    lib = ctypes.CDLL(lib_path)
    lib.slate_tpu_dposv.argtypes = [ctypes.c_int64] * 2 + [ctypes.c_void_p] * 3
    lib.slate_tpu_dgels.argtypes = [ctypes.c_int64] * 3 + [ctypes.c_void_p] * 3
    n = 20
    rng = np.random.default_rng(3)
    g = rng.standard_normal((n, n))
    a = g @ g.T + n * np.eye(n)
    xt = rng.standard_normal((n, 1))
    b = a @ xt
    x = np.zeros_like(xt)
    assert lib.slate_tpu_dposv(n, 1, a.ctypes.data, b.ctypes.data, x.ctypes.data) == 0
    assert np.abs(x - xt).max() < 1e-9
    m = 30
    aa = rng.standard_normal((m, n))
    bb = rng.standard_normal((m, 1))
    xx = np.zeros((n, 1))
    assert lib.slate_tpu_dgels(m, n, 1, aa.ctypes.data, bb.ctypes.data, xx.ctypes.data) == 0
    assert np.abs(aa.T @ (aa @ xx - bb)).max() < 1e-9


def test_tester_cli():
    r = subprocess.run(
        ["python", os.path.join(_ROOT, "tester.py"), "gemm", "--dim", "64", "--type", "s"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "pass" in r.stdout


def test_simplified_api():
    from slate_tpu import api
    from slate_tpu.types import Side

    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((16, 8)))
    b = jnp.asarray(rng.standard_normal((8, 12)))
    c = api.multiply(1.0, a, b)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a) @ np.asarray(b), atol=1e-12)
    n = 20
    g = rng.standard_normal((n, n))
    spd = jnp.asarray(g @ g.T + n * np.eye(n))
    xt = rng.standard_normal((n, 1))
    x, info = api.chol_solve(spd, jnp.asarray(np.asarray(spd) @ xt))
    assert int(info) == 0 and np.abs(np.asarray(x) - xt).max() < 1e-9
    w = api.eig_vals(jnp.asarray((g + g.T) / 2))
    assert np.abs(np.asarray(w) - np.linalg.eigvalsh((g + g.T) / 2)).max() < 1e-9


def test_simplified_api_precision_opts(rng):
    # round-3: Option.Precision must reach blas3 through every multiply verb
    import jax.numpy as jnp

    from slate_tpu import api
    from slate_tpu.types import Option, Precision, Side

    a = jnp.asarray(rng.standard_normal((32, 24)))
    b = jnp.asarray(rng.standard_normal((24, 16)))
    ref = np.asarray(a) @ np.asarray(b)
    for tier in (Precision.Fast, Precision.High, Precision.Highest, "fast"):
        out = api.multiply(1.0, a, b, opts={Option.Precision: tier})
        # CPU computes exactly regardless of tier; this asserts the opts
        # path is plumbed (a bad tier value would raise)
        assert np.abs(np.asarray(out) - ref).max() < 1e-12
    h = jnp.asarray(rng.standard_normal((24, 24)))
    h = (h + h.T) / 2
    out = api.hermitian_multiply(Side.Left, 1.0, h, b, opts={"precision": "highest"})
    assert np.abs(np.asarray(out) - np.asarray(h) @ np.asarray(b)).max() < 1e-12
    import pytest

    with pytest.raises(ValueError):
        api.multiply(1.0, a, b, opts={Option.Precision: "warp-speed"})
