"""Train configuration dataclasses.

Parity with the reference's AIR/Train v2 configs
(`python/ray/train/v2/api/config.py` ScalingConfig incl. `use_tpu`/`topology`,
`python/ray/air/config.py` RunConfig/FailureConfig/CheckpointConfig).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional


@dataclasses.dataclass
class ElasticConfig:
    """Elastic fault-tolerance policy for a worker group with an elastic
    range (`min_workers` < `num_workers`).

    The controller subscribes to the head's death-event plane
    (actor_state / node_state pubsub, the push side of the flight
    recorder's lease-event stream) so a daemon or worker kill interrupts
    the run in event time, not at the next poll timeout; the group is
    fenced by the cluster epoch + a per-start generation, reshaped to
    the surviving capacity, restored from the latest (resharding-capable)
    checkpoint, and — once capacity returns — grown back to
    `num_workers` at the next checkpoint boundary.
    """

    # how long a restart may wait for min_workers' worth of resources to
    # appear before giving up to the normal failure path
    schedule_wait_s: float = 60.0
    # capacity-watcher cadence while running below num_workers
    scale_up_check_interval_s: float = 2.0
    # after a graceful-stop (resize) request, how long workers get to
    # reach their next checkpoint boundary before being restarted anyway
    resize_grace_s: float = 60.0
    # grow back to num_workers at the next checkpoint boundary when the
    # cluster regains capacity (False: finish the run at reduced size)
    regrow: bool = True
    # fenced restarts (cluster-epoch changed under the group — e.g. a
    # head restart invalidated the grants it ran under) allowed before
    # erroring; these are environmental, not training failures, so they
    # have their own budget separate from FailureConfig.max_failures
    max_fenced_restarts: int = 5


def _chips_per_host() -> int:
    """All chips of one host, as this cluster's nodes advertise them: what
    a TPU worker asks for unless `chips_per_worker` says otherwise (1 on a
    one-chip machine, 4 on a v5e 2x2 host)."""
    import ray_tpu

    chips = int(max((n["resources"].get("TPU", 0) for n in ray_tpu.nodes()
                     if n["alive"]), default=0))
    if not chips:
        raise RuntimeError("ScalingConfig(use_tpu=True): no node of this "
                           "cluster advertises TPU chips")
    return chips


@dataclasses.dataclass
class ScalingConfig:
    """How many workers and what each one holds.

    TPU semantics: `use_tpu=True` + `topology` (e.g. "v5e-16") gang-schedules
    one worker per slice host via the slice-name label (reference
    train/v2/jax flow, SURVEY §3.4); `chips_per_worker` subdivides hosts for
    small jobs.
    """

    num_workers: int = 1
    # elastic range (reference elastic ScalingPolicy): when set, the
    # controller sizes each (re)start to the resources actually
    # available, between min_workers and num_workers — a shrunken
    # cluster restarts smaller instead of waiting, and grows back on the
    # next restart
    min_workers: Optional[int] = None
    use_tpu: bool = False
    topology: Optional[str] = None          # e.g. "v5e-16" (a pod type)
    chips_per_worker: Optional[int] = None  # default: all chips of a host
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # elastic policy knobs; defaults apply whenever min_workers is set
    elastic: Optional[ElasticConfig] = None

    def elastic_config(self) -> ElasticConfig:
        return self.elastic or ElasticConfig()

    @property
    def is_elastic(self) -> bool:
        return bool(self.min_workers) and self.min_workers < self.num_workers

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker or {})
        if self.use_tpu and "TPU" not in res:
            res["TPU"] = float(self.chips_per_worker or _chips_per_host())
        if not self.use_tpu and not res:
            res = {"CPU": 1.0}
        return res


@dataclasses.dataclass
class FailureConfig:
    """max_failures: whole-group restarts allowed before erroring (reference
    v2/_internal/execution/failure_handling/default.py)."""

    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None

    def resolved_storage_path(self) -> str:
        base = self.storage_path or os.path.expanduser("~/ray_tpu_results")
        name = self.name or "train_run"
        return os.path.join(base, name)
