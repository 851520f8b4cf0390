"""Chip grants follow the scheduler: a worker granted `num_tpu_chips=k`
is bound to k of its node's free chip ids and to the `tpu` platform, a
worker granted none stays held to the CPU, and a granted worker that
cannot open its chips raises instead of computing on the CPU.

The chips here are fake (`num_tpu_chips=4` on a host without any), so
the binding is asserted from the worker's environment and from the error
JAX raises when the granted worker first touches it.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu.core.resources import (chips_needed, strip_device_env,
                                    take_chips)

REFUSAL = "was granted TPU chips"


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=8, num_tpu_chips=4, max_workers=8)
    yield
    ray_tpu.shutdown()


def _device_env():
    return {"pid": os.getpid(),
            "platforms": os.environ.get("JAX_PLATFORMS"),
            "chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "bounds": os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS")}


def _touch_jax():
    """Compute something; report where. Raises in a granted worker here."""
    import jax

    return jax.numpy.ones((2, 2)).sum().devices().pop().platform


@ray_tpu.remote
class Holder:
    def env(self):
        return _device_env()

    def touch_jax(self):
        return _touch_jax()


@ray_tpu.remote
def task_env():
    return _device_env()


@ray_tpu.remote
def task_touch_jax():
    return _touch_jax()


@pytest.mark.parametrize("free,k,want", [
    ([0, 1, 2, 3], 0, []),
    ([0, 1, 2, 3], 1, [0]),
    ([1, 3], 1, [1]),
    ([0, 1, 2, 3], 4, [0, 1, 2, 3]),
    ([0, 1, 3], 4, None),
    ([], 1, None),
    ([1, 2, 3], 2, [2, 3]),      # an aligned pair, not the two lowest
    ([1, 2], 2, None),           # chips 1 and 2 are not neighbours
])
def test_take_chips(free, k, want):
    assert take_chips(free, k) == want


@pytest.mark.parametrize("resources,k", [
    ({"CPU": 1}, 0), ({"TPU": 1.0}, 1), ({"TPU": 0.5}, 1), ({"TPU": 4}, 4)])
def test_chips_needed(resources, k):
    assert chips_needed(resources) == k


def test_control_plane_env_is_held_to_cpu():
    env = strip_device_env({"JAX_PLATFORMS": "tpu,cpu", "PATH": "/bin"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert (os.path.dirname(os.path.dirname(ray_tpu.__file__))
            in env["PYTHONPATH"].split(os.pathsep))


def test_one_chip_actors_get_disjoint_chips(cluster):
    actors = [Holder.options(num_cpus=0, num_tpu_chips=1).remote()
              for _ in range(2)]
    envs = ray_tpu.get([a.env.remote() for a in actors], timeout=120)
    assert sorted(e["chips"] for e in envs) == ["0", "1"]
    assert envs[0]["pid"] != envs[1]["pid"]
    for e in envs:
        assert e["platforms"] == "tpu,cpu"
        assert e["bounds"] == "1,1,1"
    for a in actors:
        ray_tpu.kill(a)


def test_whole_host_grant_leaves_topology_alone(cluster):
    a = Holder.options(num_cpus=0, num_tpu_chips=4).remote()
    env = ray_tpu.get(a.env.remote(), timeout=120)
    assert env["platforms"] == "tpu,cpu"
    assert env["chips"] is None and env["bounds"] is None
    ray_tpu.kill(a)


@pytest.mark.parametrize("warm_cpu_backend", [False, True])
def test_ungranted_task_cannot_open_a_chip(cluster, warm_cpu_backend):
    env = ray_tpu.get(task_env.remote(), timeout=120)
    assert env["platforms"] == "cpu" and env["chips"] is None
    if warm_cpu_backend:
        assert ray_tpu.get(task_touch_jax.remote(), timeout=120) == "cpu"


def _wait_tpu_free(n, timeout=60):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if ray_tpu.available_resources().get("TPU", 0) >= n:
            return
        time.sleep(0.1)
    raise AssertionError(ray_tpu.available_resources())


def test_released_chip_is_reassigned(cluster):
    _wait_tpu_free(4)
    actors = [Holder.options(num_cpus=0, num_tpu_chips=1).remote()
              for _ in range(4)]
    envs = ray_tpu.get([a.env.remote() for a in actors], timeout=120)
    assert sorted(e["chips"] for e in envs) == ["0", "1", "2", "3"]
    # every chip is held: a fifth one-chip actor waits for a release
    late = Holder.options(num_cpus=0, num_tpu_chips=1).remote()
    ref = late.env.remote()
    ready, _ = ray_tpu.wait([ref], timeout=1.0)
    assert not ready
    ray_tpu.kill(actors[2])
    got = ray_tpu.get(ref, timeout=120)
    assert got["chips"] == envs[2]["chips"]
    assert got["pid"] not in {e["pid"] for e in envs}
    for a in actors[:2] + actors[3:] + [late]:
        ray_tpu.kill(a)


def test_granted_task_worker_exits_with_its_task(cluster):
    """The chip stays with the process, so the process is not pooled
    again: the next granted task runs in a new one, on the same chip."""
    _wait_tpu_free(4)
    first = ray_tpu.get(task_env.options(num_tpu_chips=1).remote(),
                        timeout=120)
    second = ray_tpu.get(task_env.options(num_tpu_chips=1).remote(),
                         timeout=120)
    assert first["platforms"] == second["platforms"] == "tpu,cpu"
    assert first["pid"] != second["pid"]
    assert first["chips"] == second["chips"] == "0"


@pytest.mark.parametrize("kind", ["actor", "task", "task_after_cpu_jax"])
def test_granted_worker_never_falls_back_to_cpu(cluster, kind):
    """No chip opens here, so the first use of JAX must raise the stated
    refusal — also in a pooled worker whose CPU backend an earlier
    ungranted task had already initialized."""
    _wait_tpu_free(4)
    if kind == "actor":
        a = Holder.options(num_cpus=0, num_tpu_chips=1).remote()
        ref = a.touch_jax.remote()
    else:
        if kind == "task_after_cpu_jax":
            # warm every pooled worker's CPU backend first
            ray_tpu.get([task_touch_jax.remote() for _ in range(8)],
                        timeout=120)
        ref = task_touch_jax.options(num_tpu_chips=1).remote()
    with pytest.raises(Exception, match=REFUSAL):
        ray_tpu.get(ref, timeout=120)
    if kind == "actor":
        ray_tpu.kill(a)
