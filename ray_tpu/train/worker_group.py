"""TrainWorker actor + WorkerGroup.

Parity with `python/ray/train/v2/_internal/execution/worker_group/
worker_group.py:103` (actor group creation w/ PGs, poll_status) and
`worker.py`/`thread_runner.py` (train fn runs on a thread inside the actor).
TPU twist: workers of a multi-host job are gang-placed one-per-host on a
reserved slice via the slice-name label selector (SURVEY §3.4).
"""

from __future__ import annotations

import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core import serialization
from ray_tpu.train import session as session_lib
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.util import tracing


@ray_tpu.remote
class TrainWorker:
    """Hosts the user train function on a thread; polled by the controller."""

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._ctx: Optional[session_lib.TrainContext] = None
        self._error: Optional[str] = None
        self._done = False

    @tracing.startup_span("train.worker_setup")
    def setup_and_start(self, train_fn, train_config, rank, world_size,
                        local_rank, node_rank, resume_checkpoint_path,
                        backend_env: Optional[Dict[str, str]] = None,
                        generation: int = 0, run_name: Optional[str] = None,
                        dataset_shards: Optional[dict] = None):
        import os
        import sys

        tracing.startup_attributes(rank=rank, world_size=world_size,
                                   run=run_name)
        if "jax" in sys.modules:
            # a JAX trainer's worker: every program it prepares from here
            # on is in the start-up record by name
            from ray_tpu.utils.platform import watch_compiles

            watch_compiles()
        if backend_env:
            os.environ.update(backend_env)
        # the train thread outlives this call, and the call's arguments
        # alias its payload in the object store, which is released when the
        # call returns: a dataset made `from_numpy` was rewritten under the
        # worker once the store reused the space (from the second epoch on)
        train_fn, train_config, dataset_shards = serialization.owned(
            (train_fn, train_config, dataset_shards))
        resume = (Checkpoint(resume_checkpoint_path)
                  if resume_checkpoint_path else None)
        self._generation = generation
        self._ctx = session_lib.TrainContext(
            rank=rank, world_size=world_size, local_rank=local_rank,
            node_rank=node_rank, resume_checkpoint=resume,
            generation=generation, run_name=run_name,
            dataset_shards=dataset_shards)
        # this actor call's execute span carries the driver's trace when
        # the driver traces: capture it NOW (the train thread outlives the
        # call) so per-step spans join the run's trace
        carrier = tracing.inject_context()

        def _run():
            session_lib._set_context(self._ctx)
            self._ctx.loop_start_ts = time.time()
            try:
                with tracing.adopt_context(carrier):
                    if train_config is None:
                        train_fn()
                    else:
                        train_fn(train_config)
            except StopIteration:
                pass
            except BaseException:
                self._error = traceback.format_exc()
            finally:
                session_lib._set_context(None)
                try:
                    # the controller kills this actor shortly after it
                    # polls done — flush synchronously BEFORE raising
                    # _done so the final steps' spans/telemetry provably
                    # beat the kill (the periodic pusher's next tick, or
                    # a post-done flush, would race it)
                    from ray_tpu.util import metrics as _m

                    _m.flush(wait=True)
                except Exception:
                    pass
                self._done = True

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name=f"train-rank{rank}")
        self._thread.start()
        return True

    def poll(self):
        """Drain new reports; reference worker_group.poll_status :488.
        Reports carry the group generation so a fenced group's late
        reports are distinguishable from the live gang's."""
        with self._ctx.lock:
            reports = self._ctx.reports
            self._ctx.reports = []
        return {"reports": reports, "done": self._done, "error": self._error,
                "generation": getattr(self, "_generation", 0)}

    def request_stop(self):
        if self._ctx is not None:
            self._ctx.stop_requested = True
        return True

    def node_id(self):
        return ray_tpu.get_runtime_context().node_id.hex()

    def node_ip(self):
        """IP other gang members can reach this worker's host on (used by
        backends that rendezvous on rank 0, e.g. torch MASTER_ADDR)."""
        import os
        import socket

        # Route toward the head when it is remote; head-spawned workers
        # have no RAY_TPU_HEAD_HOST (loopback), so fall back to the primary
        # outbound interface (UDP connect sends no packets).
        from ray_tpu.core import config as _config

        for target in (_config.get("head_host"), "8.8.8.8"):
            if not target or target.startswith("127."):
                continue
            try:
                with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
                    s.connect((target, 1))
                    return s.getsockname()[0]
            except OSError:
                continue
        return "127.0.0.1"

    def rendezvous_info(self):
        """(reachable_ip, free_port) probed on THIS host — rendezvous ports
        must be chosen where they will actually be bound (rank 0's node),
        not on the controller."""
        import socket

        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
        return self.node_ip(), port

    def shutdown_worker(self):
        return True


class WorkerGroup:
    """Creates and tracks the gang of TrainWorker actors.

    Each group carries a monotonically increasing `generation` (set by
    the controller) — the train-level half of the fencing story: the
    cluster epoch fences a group against control-plane restarts; the
    generation scopes collective-group rendezvous names and tags every
    polled status, so a zombie member of a killed gang can neither
    rendezvous with its successor nor have its reports mistaken for the
    live gang's (checkpoints only enter run storage via the controller
    draining the group it currently polls).
    """

    def __init__(self, scaling_config, label_selector: Optional[dict] = None,
                 placement_group=None, generation: int = 0,
                 run_name: Optional[str] = None):
        self.scaling = scaling_config
        self.run_name = run_name
        self.label_selector = label_selector
        self.placement_group = placement_group
        self.generation = generation
        self.workers: List[Any] = []
        self.actor_ids: List[str] = []     # hex ids, index == rank
        self.node_ids: List[str] = []      # hex node of each worker

    def start(self, train_fn: Callable, train_config: Any,
              resume_checkpoint: Optional[Checkpoint] = None,
              backend=None, datasets: Optional[dict] = None) -> None:
        n = self.scaling.num_workers
        res = self.scaling.worker_resources()
        opts: Dict[str, Any] = {"resources": res, "num_cpus": res.get("CPU", 0)}
        if self.label_selector:
            opts["label_selector"] = self.label_selector
        if self.placement_group is not None:
            opts["placement_group"] = self.placement_group
        if self.scaling.placement_strategy in ("SPREAD", "STRICT_SPREAD"):
            opts["scheduling_strategy"] = "spread"
        self.workers = [TrainWorker.options(**opts).remote() for _ in range(n)]
        self.actor_ids = [w._actor_id.hex() for w in self.workers]
        backend_envs = (backend.worker_envs(self) if backend is not None
                        else [{} for _ in range(n)])
        from ray_tpu.train.ingest import build_shards

        starts = []
        for rank, w in enumerate(self.workers):
            starts.append(w.setup_and_start.remote(
                train_fn, train_config, rank, n, 0, rank,
                resume_checkpoint.path if resume_checkpoint else None,
                backend_envs[rank], self.generation, self.run_name,
                # per-generation shard map: rebuilt with the CURRENT
                # (rank, world) so an elastic resize re-splits the
                # stream without duplicating or dropping global batches
                build_shards(datasets, rank, n)))
        ray_tpu.get(starts, timeout=120)
        # node placement, recorded for the controller's death watch
        # (a node_state DEAD event for any of these hosts fails the
        # group immediately, without waiting for a poll RPC to time out)
        self.node_ids = ray_tpu.get(
            [w.node_id.remote() for w in self.workers], timeout=60)

    def poll(self) -> List[dict]:
        return ray_tpu.get([w.poll.remote() for w in self.workers], timeout=60)

    def request_stop_all(self) -> None:
        """Ask every worker to stop at its next report — the graceful
        (checkpoint-boundary) half of an elastic resize. Best-effort:
        a worker that died since the last poll is already stopping."""
        refs = []
        for w in self.workers:
            try:
                refs.append(w.request_stop.remote())
            except Exception:
                pass
        try:
            ray_tpu.get(refs, timeout=30)
        except Exception:
            pass

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
