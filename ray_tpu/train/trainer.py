"""Trainers: DataParallelTrainer + JaxTrainer.

Parity with `python/ray/train/v2/api/data_parallel_trainer.py:59` (fit() spawns
a controller actor and waits) and `train/v2/jax/jax_trainer.py:19` +
`config.py:39 _JaxBackend` (per-worker jax.distributed env). The TPU-native
difference: on a single host the worker owns all local chips and the data
plane is one pjit program (ray_tpu.train.spmd); multi-host slices get
coordinator env vars for `jax.distributed.initialize`.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.controller import TrainControllerActor, TrainControllerLogic


@dataclasses.dataclass
class Result:
    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint]
    path: Optional[str]
    error: Optional[str]
    restarts: int = 0
    # elastic lifecycle counters: graceful grow-back restarts and
    # epoch-fence restarts (neither consumes the failure budget)
    resizes: int = 0
    fenced_restarts: int = 0
    final_world_size: Optional[int] = None

    @property
    def best_checkpoints(self) -> List[Checkpoint]:
        return [self.checkpoint] if self.checkpoint else []


class TrainingFailedError(RuntimeError):
    pass


class JaxBackend:
    """Assigns each worker the env for `jax.distributed.initialize`
    (reference train/v2/jax/config.py:24-36: coordinator_address,
    num_processes, process_id). Only engages for multi-worker groups; a
    single worker drives all its chips through one PJRT client."""

    def __init__(self, enable_distributed: Optional[bool] = None):
        self.enable_distributed = enable_distributed

    def worker_envs(self, group) -> List[Dict[str, str]]:
        n = len(group.workers)
        enabled = (self.enable_distributed if self.enable_distributed is not None
                   else n > 1)
        if not enabled:
            return [{} for _ in range(n)]
        # Coordinator = rank 0's reachable address with a port probed free
        # on rank 0's own host (a loopback/controller-probed pair would
        # make non-rank-0 hosts of a multi-host gang connect to themselves).
        host, port = ray_tpu.get(
            group.workers[0].rendezvous_info.remote(), timeout=120)
        coordinator = f"{host}:{port}"
        return [{
            "RAY_TPU_JAX_COORDINATOR": coordinator,
            "RAY_TPU_JAX_NUM_PROCESSES": str(n),
            "RAY_TPU_JAX_PROCESS_ID": str(rank),
        } for rank in range(n)]


def maybe_init_jax_distributed() -> None:
    """Call inside a train loop to join the slice-wide PJRT mesh if the
    backend provisioned one."""
    import os

    coord = os.environ.get("RAY_TPU_JAX_COORDINATOR")
    if not coord:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["RAY_TPU_JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["RAY_TPU_JAX_PROCESS_ID"]))


class DataParallelTrainer:
    """Runs `train_loop_per_worker` on a gang of workers."""

    backend = None

    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[dict] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None,
                 datasets: Optional[dict] = None):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        self.datasets = datasets or {}

    def fit(self, _in_process: bool = False) -> Result:
        fit_ts = time.time()    # where the controller's `train.fit` starts
        resume = (self.resume_from_checkpoint.path
                  if self.resume_from_checkpoint else None)
        if _in_process or not ray_tpu.is_initialized():
            # local/debug mode: controller logic inline (reference
            # local_testing_mode analog); still uses real worker actors
            ray_tpu.init()
            logic = TrainControllerLogic(
                self.train_loop_per_worker, self.train_loop_config,
                self.scaling_config, self.run_config, backend=self.backend,
                resume_from=resume, datasets=self.datasets, fit_ts=fit_ts)
            out = logic.run()
        else:
            controller = TrainControllerActor.options(
                name=f"train-controller-{self.run_config.name or 'run'}"
                     f"-{id(self) & 0xffff:x}").remote()
            out = ray_tpu.get(controller.run.remote(
                self.train_loop_per_worker, self.train_loop_config,
                self.scaling_config, self.run_config, self.backend, resume,
                self.datasets, fit_ts),
                timeout=None)
            ray_tpu.kill(controller)
        result = Result(
            metrics=out["metrics"],
            checkpoint=(Checkpoint(out["checkpoint_path"])
                        if out["checkpoint_path"] else None),
            path=out["storage_path"],
            error=out["error"],
            restarts=out["restarts"],
            resizes=out.get("resizes", 0),
            fenced_restarts=out.get("fenced_restarts", 0),
            final_world_size=out.get("final_world_size"),
        )
        if out["state"] == "ERRORED":
            raise TrainingFailedError(out["error"])
        return result


class JaxTrainer(DataParallelTrainer):
    """SPMD JAX training over TPU workers (reference jax_trainer.py:19).

    With `scaling_config.use_tpu` and a `topology`, reserves a slice and
    gang-places one worker per host; each worker joins the PJRT mesh via
    `maybe_init_jax_distributed()` and runs the same pjit program.
    """

    def __init__(self, *args, jax_backend: Optional[JaxBackend] = None, **kw):
        super().__init__(*args, **kw)
        self.backend = jax_backend or JaxBackend()
