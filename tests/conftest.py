"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the analog of the reference's local multi-process test harness
(``subtree/rabit/tracker/rabit_demo.py``): distributed code paths are
exercised on one host by forcing 8 virtual CPU devices.  Must run before
jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the session env may point at TPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration drives (demo suite)")


@pytest.fixture
def mesh8():
    """The 8-virtual-device data-parallel mesh."""
    from xgboost_tpu.parallel.mesh import data_parallel_mesh
    return data_parallel_mesh(8)


@pytest.fixture
def recompile_guard():
    """XLA backend-compile budget assertions (ANALYSIS.md): the
    generalized form of the serving zero-steady-state-recompile test —
    ``with recompile_guard.expect(0): hot_path()`` fails if the region
    compiles anything."""
    from xgboost_tpu.analysis.runtime import RecompileGuard
    return RecompileGuard()


@pytest.fixture
def lock_race_checker():
    """Instrumented-lock race observer (ANALYSIS.md): ``instrument`` an
    object under concurrency stress, then ``assert_clean()``.  Teardown
    asserts automatically so a test cannot forget to look."""
    from xgboost_tpu.analysis.runtime import LockRaceChecker
    checker = LockRaceChecker()
    yield checker
    checker.assert_clean()


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Free compiled executables between test MODULES: a single pytest
    process accumulates every jit executable of ~190 tests, and the XLA
    CPU compiler has been seen segfaulting late in the run under that
    memory pressure.  Cross-module cache reuse is minimal (each module
    compiles its own shapes), so this costs little."""
    yield
    jax.clear_caches()
