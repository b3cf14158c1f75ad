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


@pytest.fixture
def launches(tmp_path):
    """`with launches() as seen:` records a profiler trace around its body
    and, once the body is done, fills `seen` from the host tracer's events:
    `programs`, how many compiled programs the CPU client was handed (one
    `PjRtCpuExecutable::Execute` per launch, whatever the mesh), `jitted`,
    the names of the jitted functions that were called
    (`PjitFunction(<name>)`): what the chip's trace counts per XLA module,
    and `pulls`, how many device arrays were brought to the host (jax's own
    `np.asarray(jax.Array)` event, one per array: the CPU backend's
    transfer guard lets a device-to-host copy pass, so it cannot count)."""
    import contextlib
    import glob
    import re
    import tempfile

    @contextlib.contextmanager
    def record():
        import jax
        from jax.profiler import ProfileData
        seen = {"programs": 0, "jitted": set(), "pulls": 0}
        trace_dir = tempfile.mkdtemp(prefix="launches_", dir=tmp_path)
        with jax.profiler.trace(trace_dir):
            yield seen
        path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "PjRtCpuExecutable::Execute":
                        seen["programs"] += 1
                    if ev.name == "np.asarray(jax.Array)":
                        seen["pulls"] += 1
                    m = re.fullmatch(r"PjitFunction\((.*)\)", ev.name)
                    if m:
                        seen["jitted"].add(m.group(1))

    return record
