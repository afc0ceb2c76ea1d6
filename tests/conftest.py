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
# persistent compile cache: the suite re-compiles hundreds of CPU programs
# per run; the disk cache cuts warm reruns
from slate_tpu.utils.compile_cache import enable_compile_cache

enable_compile_cache(min_compile_secs=2.0)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def cpu_devices(n=8):
    return jax.devices("cpu")[:n]
