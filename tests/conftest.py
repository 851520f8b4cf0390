"""Test configuration: force an 8-device virtual CPU platform BEFORE jax init.

Mirrors the reference's strategy of testing distributed logic on one machine
with fake resources (SURVEY.md §4.2): all sharding/collective tests run on a
virtual 8-device CPU mesh; real-TPU behavior is covered by the driver's bench.
"""

import os

_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run on the virtual CPU mesh
# hermetic: no test process or worker reads or writes the persistent
# compilation cache (tests/test_compile_cache.py checks its placement only)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]
