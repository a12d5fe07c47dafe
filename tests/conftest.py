"""Test bootstrap: force an 8-device virtual CPU platform BEFORE jax loads.

Mirrors the reference's embedded-cluster test strategy (SURVEY.md §4: every
test spins a hermetic in-process store); here the "cluster" is 8 virtual XLA
CPU devices so multi-chip sharding paths run without TPU hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU's AOT loader logs a ~3KB ERROR line per program loaded from the
# persistent cache (the compile-time machine string carries XLA-internal
# pseudo-features the loader does not recognise; the real ISA matches).
# Keep the test logs readable; the package itself no longer sets this,
# because on the chip the same stream carries libtpu's init errors.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Tests run on CPU. The config.update below holds even if jax was already
# imported (and read JAX_PLATFORMS) before this file set the variable.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from tidb_tpu.utils import failpoint  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "chaos: seeded fault-injection runs (tests/test_chaos.py;"
        " deepen locally with CHAOS_SEEDS=n)")
    config.addinivalue_line(
        "markers", "chaos_threads: concurrent (multi-threaded) chaos runs"
        " with invariant-only checks (tests/test_chaos.py; deepen locally"
        " with CHAOS_THREAD_SEEDS=n CHAOS_THREADS=n)")
    config.addinivalue_line(
        "markers", "multichip: MPP mesh-path tests that need the 8-device"
        " virtual CPU platform this conftest forces"
        " (XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(autouse=True)
def _no_failpoint_leaks():
    """A test that leaks an active failpoint corrupts every test after it;
    fail loudly at the source instead (satellite: failpoint hygiene)."""
    yield
    leaked = failpoint.list_active()
    if leaked:
        failpoint.disable_all()
        pytest.fail(f"test leaked active failpoints: {leaked}")
