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


def warm_daemon_lease(client, submit_and_get, timeout=90, idle_wait=1.5):
    """Drive `submit_and_get()` until the driver holds a DAEMON-granted
    lease (two-level warm path). The head may win the cold-grant race;
    when it does, wait `idle_wait` so the head lease idles out, then
    retry — the daemon's node has warm pool workers by then and grants
    instantly. Shared by the chaos/head-FT drills so the known-flaky
    warmup dance has one implementation."""
    import time as _time

    deadline = _time.time() + timeout
    while (_time.time() < deadline
           and client.lease_stats["daemon_grants"] == 0):
        submit_and_get()
        if client.lease_stats["daemon_grants"]:
            break
        _time.sleep(idle_wait if client._leases else 0.05)
    assert client.lease_stats["daemon_grants"] >= 1, client.lease_stats
