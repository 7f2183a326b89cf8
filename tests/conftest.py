"""Test harness: force an 8-device virtual CPU platform.

Multi-chip TPU hardware is not available in CI; sharded code paths are
validated on a virtual 8-device CPU mesh instead (same XLA semantics).
JAX reads these variables when it initializes, so they are set before
any test imports it.  Tests keep no persistent compilation cache unless
JAX_COMPILATION_CACHE_DIR asks for one (subprocesses inherit both).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_device_route_floor():
    """The process-wide dispatch-floor cache makes routing (and the
    device/host audit counters) adapt across runs — desirable in a
    long-lived process, order-dependent in a test session.  Reset per
    test."""
    from shadow_tpu.ops.propagate import DeviceRouteModel
    DeviceRouteModel.reset_shared()
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from tier-1 (-m 'not slow'); device-kernel "
        "XLA compiles take minutes on the CPU backend")
