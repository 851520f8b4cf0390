"""Node resource detection, TPU chips as first-class resources.

Behavioral parity with the reference's accelerator plugin semantics
(`python/ray/_private/accelerators/tpu.py`): chip autodetect, valid chip
group sizes {1,2,4,8}, per-process visibility via TPU_VISIBLE_CHIPS, slice
labels for gang scheduling.

Who may open a chip: a chip belongs to one process until that process
exits. Control-plane processes (head, node daemons, job drivers) and
pooled workers start with JAX held to the CPU (`strip_device_env`). A
worker leaves that state only when the scheduler dispatches work granted
`TPU: k` to it: the head picks k of the node's free chip ids
(`take_chips`), ships them with the task or actor spec, and the worker
binds itself to exactly those chips before user code runs
(`claim_chips`). Such a worker is never pooled again: it exits with its
task or actor, and the head returns the ids to the node's free list when
the process is gone.
"""

from __future__ import annotations

import glob
import math
import os
from ray_tpu.core import config as _config
from typing import Dict, List, Optional, Sequence

VALID_TPU_CHIP_COUNTS = (1, 2, 4, 8)


def detect_num_tpu_chips() -> int:
    """Chips attached to this host, counted without initializing a backend
    (detection runs in the head and node daemons, which must never open a
    chip): the RAY_TPU_NUM_CHIPS override, else the device nodes the TPU
    driver creates — `/dev/accel<N>` (accel driver, up to v4) or
    `/dev/vfio/<N>` (vfio driver, v5e and later: one group per chip handed
    to this machine). The PCI bus is deliberately not consulted: a sandbox
    given one chip of a four-chip host still lists all four there."""
    override = _config.get("num_chips")
    if override >= 0:
        return override
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


# --------------------------------------------------- GKE/GCE pod metadata
# Reference: `python/ray/_private/accelerators/tpu.py:326-433` — GKE pods
# preset env vars; GCE TPU VMs expose the same facts via the metadata
# server. Without this, multi-host pod bring-up cannot self-label slices
# and gang scheduling needs hand-set env vars on every host.
GCE_METADATA_ENDPOINT = (
    "http://metadata.google.internal/computeMetadata/v1/instance/attributes/")
_gce_cache: Dict[str, Optional[str]] = {}
_gce_down = False


def _gce_metadata(key: str) -> Optional[str]:
    """One metadata-server attribute; cached, fast-fails permanently for
    the process once the server proves unreachable (non-GCP hosts).
    `RAY_TPU_GCE_METADATA_ENDPOINT` overrides the endpoint (tests point it
    at a local mock; also enables probing on chip-less hosts)."""
    global _gce_down
    if key in _gce_cache:
        return _gce_cache[key]
    endpoint = _config.get("gce_metadata_endpoint") or GCE_METADATA_ENDPOINT
    if _gce_down and endpoint == GCE_METADATA_ENDPOINT:
        return None
    import urllib.error
    import urllib.request

    req = urllib.request.Request(endpoint.rstrip("/") + "/" + key,
                                 headers={"Metadata-Flavor": "Google"})
    try:
        with urllib.request.urlopen(req, timeout=2) as resp:
            value = resp.read().decode() if resp.status == 200 else None
    except (urllib.error.URLError, OSError, TimeoutError):
        _gce_down = True
        value = None
    _gce_cache[key] = value
    return value


def _probe_metadata() -> bool:
    """Only touch the metadata server when this host plausibly has TPUs
    (or a test mock endpoint is set) — CPU-only nodes must not pay a
    resolve timeout at every bring-up."""
    return (bool(_config.get("gce_metadata_endpoint"))
            or detect_num_tpu_chips() > 0)


def tpu_pod_type() -> Optional[str]:
    """Slice/pod type, e.g. 'v5e-64': env (GKE presets it) → GCE
    metadata `accelerator-type`."""
    explicit = (_config.get("pod_type")
                or os.environ.get("TPU_ACCELERATOR_TYPE"))
    if explicit:
        return explicit
    if _probe_metadata():
        return _gce_metadata("accelerator-type")
    return None


def tpu_worker_id() -> int:
    # empty string == unset: lets a parent scrub inherited TPU identity
    # vars for child nodes without tripping int("")
    env = (_config.get("worker_id")
           or os.environ.get("TPU_WORKER_ID"))
    if env:
        return int(env)
    if _probe_metadata():
        mid = _gce_metadata("agent-worker-number")
        if mid is not None:
            try:
                return int(mid)
            except ValueError:
                pass
    return 0


def tpu_slice_name() -> Optional[str]:
    explicit = (_config.get("slice_name")
                or os.environ.get("TPU_NAME"))
    if explicit:
        return explicit
    if _probe_metadata():
        return _gce_metadata("instance-id")
    return None


def tpu_topology() -> Optional[str]:
    """Physical topology, e.g. '2x4': env (GKE) → GCE `tpu-env` blob."""
    if (topo := os.environ.get("TPU_TOPOLOGY")):
        return topo
    if _probe_metadata():
        blob = _gce_metadata("tpu-env")
        if blob:
            import re

            m = re.search(r"TOPOLOGY:\s*'([^']+)'", blob)
            if m:
                return m.group(1)
    return None


def node_resources(num_cpus: Optional[float] = None,
                   num_tpu_chips: Optional[int] = None,
                   custom: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    res: Dict[str, float] = {}
    res["CPU"] = float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))
    chips = num_tpu_chips if num_tpu_chips is not None else detect_num_tpu_chips()
    if chips:
        res["TPU"] = float(chips)
        pod = tpu_pod_type()
        if pod and tpu_worker_id() == 0:
            # one head-resource per slice: the gang-scheduling anchor
            res[f"TPU-{pod}-head"] = 1.0
    if custom:
        res.update(custom)
    return res


def node_labels() -> Dict[str, str]:
    labels: Dict[str, str] = {}
    if (name := tpu_slice_name()):
        labels["ray.io/tpu-slice-name"] = name
    if (pod := tpu_pod_type()):
        labels["ray.io/tpu-pod-type"] = pod
    labels["ray.io/tpu-worker-id"] = str(tpu_worker_id())
    if (topo := tpu_topology()):
        labels["ray.io/tpu-topology"] = topo
    return labels


def strip_device_env(env: Dict[str, str]) -> Dict[str, str]:
    """Env for control-plane children — head, node daemons, job drivers and
    the pooled workers they start: JAX, should one of them import it, is
    held to the CPU, so none of them can open a chip."""
    env = dict(env)
    env["JAX_PLATFORMS"] = "cpu"
    return with_package_path(env)


def with_package_path(env: Dict[str, str]) -> Dict[str, str]:
    """Child processes must be able to `import ray_tpu` regardless of cwd."""
    import ray_tpu

    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(ray_tpu.__file__)))
    parts = env.get("PYTHONPATH", "").split(os.pathsep) if env.get("PYTHONPATH") else []
    if pkg_parent not in parts:
        env = dict(env)
        env["PYTHONPATH"] = os.pathsep.join([pkg_parent] + parts)
    return env


def chips_needed(resources: Dict[str, float]) -> int:
    """Whole chips a resource request occupies (a chip is never shared:
    it belongs to one process)."""
    return math.ceil(resources.get("TPU", 0) - 1e-9)


def take_chips(free: Sequence[int], k: int) -> Optional[List[int]]:
    """k chip ids out of the sorted `free` list, or None while too few are
    free. Two chips are an aligned pair (2j, 2j+1): neighbours on the
    host's ICI grid, which libtpu's per-process bounds require."""
    if k == 2:
        for c in free:
            if c % 2 == 0 and c + 1 in free:
                return [c, c + 1]
        return None
    return list(free[:k]) if len(free) >= k else None


# TPU_CHIPS_PER_PROCESS_BOUNDS for a grant smaller than the host
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def claim_chips(chip_ids: Sequence[int], host_chips: int) -> None:
    """Bind this worker process to the chips the scheduler granted it.

    Called by the worker before the granted task or actor runs. From here
    on JAX's default backend must be `tpu` with one device per granted
    chip: the first use of JAX raises if the chips do not open, or open as
    another number of devices — it never carries on on the CPU. (The CPU
    backend stays available for explicit host-side `jax.devices("cpu")`
    work.) A grant smaller than the host narrows libtpu to the granted ids
    so several such workers share the host; a grant of the whole host
    leaves libtpu's own topology untouched."""
    ids = sorted(chip_ids)
    os.environ["JAX_PLATFORMS"] = "tpu,cpu"
    if len(ids) < host_chips:
        bounds = _SUBSET_BOUNDS.get(len(ids))
        if bounds is None:
            raise ValueError(
                f"cannot bind {len(ids)} of {host_chips} chips to one "
                f"process: ask for 1, 2 or all {host_chips}")
        os.environ["TPU_VISIBLE_CHIPS"] = ",".join(map(str, ids))
        # libtpu reads the process-level names; TPU VM images preset the
        # older host-level ones, so both must agree
        for name in ("TPU_CHIPS_PER_PROCESS_BOUNDS",
                     "TPU_CHIPS_PER_HOST_BOUNDS"):
            os.environ[name] = bounds
        for name in ("TPU_PROCESS_BOUNDS", "TPU_HOST_BOUNDS"):
            os.environ[name] = "1,1,1"
    import jax
    import jax.extend.backend
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        # an earlier CPU-only task used JAX in this pooled worker
        jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "tpu,cpu")
    registration = xla_bridge._backend_factories["tpu"]
    open_backend = registration.factory

    def open_granted_backend():
        refusal = (f"this worker was granted TPU chips {ids} and runs JAX "
                   f"on nothing else")
        try:
            backend = open_backend()
        except Exception as e:
            raise RuntimeError(f"{refusal}: {e}") from e
        n = backend.device_count() if backend is not None else 0
        if n != len(ids):
            raise RuntimeError(
                f"{refusal}, but the TPU backend opened {n} device(s)")
        return backend

    registration.factory = open_granted_backend
