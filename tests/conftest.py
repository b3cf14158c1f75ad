"""Test harness: CPU backend with 8 fake devices (SURVEY.md §5).

Env must be set before jax initialises — this file is imported by pytest
before any test module touches jax. The 8-device CPU mesh is the standard
JAX idiom for testing multi-chip sharding without a pod; the driver's
separate `dryrun_multichip` uses the same mechanism.
"""
import os

# The test suite always runs on the CPU backend with 8 fake devices. Both
# are environment settings jax reads when it is first imported, so they are
# made here, before any test module imports jax.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# The entry points turn the persistent compile cache on (utils/platform.py);
# tests and the child processes they start leave it off, so that a test run
# writes nothing into the checkout.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs
