"""In-process multi-node cluster for tests — `cluster_utils.Cluster` parity.

Reference: `python/ray/cluster_utils.py:135` — N node daemons + 1 head as
separate local processes with fake resource dicts, real sockets; the primary
strategy for testing distributed logic on one machine (SURVEY §4.2 pattern 2).
TPU twist: `add_node(num_tpu_chips=8, labels={"ray.io/tpu-slice-name": ...})`
builds fake multi-host slices the way the reference's test_jax_trainer.py
monkeypatches TPU env vars.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from typing import Dict, List, Optional


class Cluster:
    def __init__(self, head_resources: Optional[Dict[str, float]] = None,
                 num_cpus: float = 0, object_store_bytes: int = 1 << 30,
                 labels: Optional[Dict[str, str]] = None,
                 enable_snapshots: bool = False):
        import uuid

        self.session = f"s{uuid.uuid4().hex[:12]}"
        self._head_args = {"num_cpus": num_cpus,
                           "object_store_bytes": object_store_bytes,
                           "head_resources": head_resources,
                           "labels": labels,
                           "enable_snapshots": enable_snapshots}
        self._head = self._spawn_head(port=0, restore=False)
        line = self._head.stdout.readline()
        assert line.startswith("RAY_TPU_HEAD_PORT="), line
        self.port = int(line.split("=", 1)[1])
        self.address = f"127.0.0.1:{self.port}"
        self._nodes: List[subprocess.Popen] = []
        self._node_ids: List[str] = []

    def _spawn_head(self, port: int, restore: bool) -> subprocess.Popen:
        import os

        from ray_tpu.core.resources import strip_device_env

        a = self._head_args
        cmd = [sys.executable, "-m", "ray_tpu.core.head_main",
               "--session", self.session,
               "--port", str(port),
               "--num-cpus", str(a["num_cpus"]),
               "--object-store-bytes", str(a["object_store_bytes"])]
        if a["head_resources"]:
            cmd += ["--resources", json.dumps(a["head_resources"])]
        if a["labels"]:
            cmd += ["--labels", json.dumps(a["labels"])]
        if a["enable_snapshots"]:
            cmd += ["--enable-snapshots"]
        if restore:
            cmd += ["--restore"]
        env = strip_device_env(dict(os.environ))
        env.setdefault("RAY_TPU_NUM_CHIPS", "0")
        return subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                env=env)

    # -------------------------------------------------- head FT drills
    def stop_head(self) -> None:
        """SIGSTOP the head — the mid-burst pause drill: every TCP
        connection stays open but nothing answers. Daemons and clients
        must keep task throughput alive through the peer-spillback mesh
        and reconcile cleanly on `cont_head`."""
        import signal

        self._head.send_signal(signal.SIGSTOP)

    def cont_head(self) -> None:
        """SIGCONT the paused head; queued gossip, releases and head-path
        submissions drain, and the ledgers must reconcile with zero
        double-grants."""
        import signal

        self._head.send_signal(signal.SIGCONT)

    def kill_head(self) -> None:
        """SIGKILL the head process (reference GCS-kill chaos drill).
        Node daemons keep serving warm leases and reconnect when
        `restart_head` brings the control plane back."""
        self._head.kill()
        self._head.wait(timeout=10)

    def restart_head(self, restore: bool = True, timeout: float = 30) -> None:
        """Restart the head on the SAME port/session; daemons, workers
        and drivers reconnect and the pool-reconciliation handshake
        rebuilds the resource ledger from daemon reports."""
        if self._head.poll() is None:
            self.kill_head()
        deadline = time.monotonic() + timeout
        while True:
            proc = self._spawn_head(port=self.port, restore=restore)
            line = proc.stdout.readline()
            if line.startswith("RAY_TPU_HEAD_PORT="):
                assert int(line.split("=", 1)[1]) == self.port, line
                self._head = proc
                return
            # bind race with the dying predecessor: retry until deadline
            proc.kill()
            proc.wait(timeout=10)
            if time.monotonic() > deadline:
                raise TimeoutError(f"head did not restart: {line!r}")
            time.sleep(0.3)

    def add_node(self, num_cpus: float = 1, num_tpu_chips: int = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 env: Optional[Dict[str, str]] = None) -> str:
        """Start a node daemon; returns its node id (hex)."""
        import os

        from ray_tpu.core.resources import strip_device_env

        cmd = [sys.executable, "-m", "ray_tpu.core.node_main",
               "--address", self.address,
               "--num-cpus", str(num_cpus),
               "--num-tpu-chips", str(num_tpu_chips)]
        if resources:
            cmd += ["--resources", json.dumps(resources)]
        if labels:
            cmd += ["--labels", json.dumps(labels)]
        node_env = strip_device_env(dict(os.environ))
        node_env["RAY_TPU_NUM_CHIPS"] = str(num_tpu_chips)
        if env:
            node_env.update(env)
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                                env=node_env)
        line = proc.stdout.readline()
        assert line.startswith("RAY_TPU_NODE_ID="), line
        node_id = line.strip().split("=", 1)[1]
        self._nodes.append(proc)
        self._node_ids.append(node_id)
        return node_id

    def kill_node(self, node_id_or_index) -> None:
        """Simulate node failure, by index or by the node id `add_node`
        returned (reference RayletKiller pattern / `Cluster.remove_node`).
        Targeted kills are what the chaos suite needs: 'kill the node the
        actor landed on', not 'kill some node'."""
        if isinstance(node_id_or_index, int):
            idx = node_id_or_index
        else:
            idx = self._node_ids.index(str(node_id_or_index))
        proc = self._nodes[idx]
        proc.kill()
        proc.wait(timeout=10)

    def stop_node(self, node_id_or_index) -> None:
        """SIGSTOP (hang, don't kill) a node daemon — the hung-process
        case TCP-disconnect detection can't see."""
        import signal

        idx = (node_id_or_index if isinstance(node_id_or_index, int)
               else self._node_ids.index(str(node_id_or_index)))
        self._nodes[idx].send_signal(signal.SIGSTOP)

    def connect(self):
        import ray_tpu

        info = ray_tpu.init(address=self.address)
        return info

    def wait_for_nodes(self, count: int, timeout: float = 30) -> None:
        import ray_tpu

        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["alive"]]
            if len(alive) >= count:
                return
            time.sleep(0.1)
        raise TimeoutError(f"cluster did not reach {count} nodes")

    def shutdown(self) -> None:
        for proc in self._nodes:
            proc.kill()
        self._head.kill()
        for proc in self._nodes + [self._head]:
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass


def carve_pool(client, sched_addr, n, timeout: float = 90,
               selector: Optional[Dict[str, str]] = None) -> None:
    """Warm one daemon's pool to `n` idle workers by leasing directly
    from its scheduler and returning the grants — the carve path the
    client's lease machinery uses, minus the racing head queue. A label
    selector matching only that node keeps the carve from turning into
    a peer referral. Shared by the headless-resilience drills (tests)
    and the soak's head-paused phase."""
    import asyncio

    from ray_tpu.core import protocol

    async def carve():
        conn = await protocol.connect(sched_addr[0], sched_addr[1],
                                      name=f"warm-{sched_addr[1]}")
        try:
            deadline = time.time() + timeout
            wids = []
            while len(wids) < n and time.time() < deadline:
                rep = await conn.request(
                    "lease_grant", resources={"CPU": 1},
                    label_selector=selector,
                    epoch=client.cluster_epoch or None)
                if rep and not rep.get("spill") and not rep.get("peers"):
                    wids.append(rep["worker_id"])
                else:
                    await asyncio.sleep(0.5)
            for w in wids:
                await conn.request("lease_return", worker_id=w)
            return len(wids)
        finally:
            await conn.close()

    got = asyncio.run_coroutine_threadsafe(carve(), client.loop).result(
        timeout=timeout + 10)
    assert got == n, f"carved {got}/{n} at {sched_addr}"


def warm_daemon_lease(client, submit_and_get, timeout=90, idle_wait=1.5):
    """Drive `submit_and_get()` until the driver holds a DAEMON-granted
    lease (two-level warm path). The head may win the cold-grant race;
    when it does, wait `idle_wait` so the head lease idles out, then
    retry — the daemon's node has warm pool workers by then and grants
    instantly. Shared by the chaos/head-FT drills so the known-flaky
    warmup dance has one implementation."""
    deadline = time.time() + timeout
    while (time.time() < deadline
           and client.lease_stats["daemon_grants"] == 0):
        submit_and_get()
        if client.lease_stats["daemon_grants"]:
            break
        time.sleep(idle_wait if client._leases else 0.05)
    assert client.lease_stats["daemon_grants"] >= 1, client.lease_stats


class VirtualNodes:
    """N fake node registrations over real sockets on a private loop —
    the reference cluster_utils strategy scaled past process counts: all
    gossip/view/shard code paths run for real, only worker spawning is
    absent (their resources never fit a task, so nothing schedules to
    them). Shared by the gossip-convergence smokes (tests) and the
    `view_convergence_s` bench row, so both measure the same protocol.

    `interest="auto"` registers each vnode as an interest-scoped view
    subscriber (the sharded plane); None keeps legacy full-fanout."""

    def __init__(self, host: str, port: int, n: int, interest="auto"):
        import asyncio
        import threading

        self.host, self.port, self.n = host, port, n
        self.interest = interest
        self.loop = asyncio.new_event_loop()
        self.conns: List[object] = []
        self.node_ids: List[str] = []
        self.views: List[dict] = []  # per-vnode: last snap + push stats
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="vnodes")

    def _run(self):
        import asyncio

        asyncio.set_event_loop(self.loop)
        self.loop.run_forever()

    def start(self, timeout: float = 120):
        import asyncio

        self._thread.start()
        fut = asyncio.run_coroutine_threadsafe(self._bring_up(), self.loop)
        fut.result(timeout=timeout)

    async def _bring_up(self):
        import asyncio

        from ray_tpu.core import protocol
        from ray_tpu.core.ids import NodeID

        async def _noop(**kwargs):
            return True

        sem = asyncio.Semaphore(64)  # bounded concurrent connects

        async def _one(i: int, slot: dict):
            async with sem:
                from ray_tpu.core.resource_view import ClusterView

                slot["view"] = ClusterView()

                async def _on_view(snap, _slot=slot):
                    _slot["snap"] = snap
                    _slot["pushes"] += 1
                    n_entries = (len(snap.get("nodes") or ())
                                 + sum(len(b.get("nodes") or ())
                                       for b in snap.get("shards") or ()))
                    _slot["entries_rx"] += n_entries
                    _slot["max_push"] = max(_slot["max_push"], n_entries)
                    # real consumer semantics: adopt like a daemon would
                    if "shards" in snap:
                        _slot["view"].adopt_shards(snap)
                    else:
                        _slot["view"].adopt(snap)
                    return True

                conn = await protocol.connect(
                    self.host, self.port,
                    handlers={"cluster_view": _on_view,
                              "health_ping": _noop, "spawn_worker": _noop,
                              "kill_worker": _noop, "shutdown_node": _noop,
                              "free_object": _noop, "adopt_object": _noop,
                              "drop_replica": _noop,
                              "reconcile_request": _noop, "chaos": _noop,
                              "pool_worker_died": _noop},
                    name=f"vnode{i}")
                nid = NodeID.generate()
                await conn.request(
                    "register_node", node_id=nid.binary(),
                    # a resource no task asks for: these nodes exist for
                    # the gossip/view plane only and never win placement
                    resources={"vslot": 1.0}, labels={"vnode": str(i)},
                    max_workers=0, data_port=0, sched_port=0,
                    interest=self.interest)
                slot["conn"] = conn
                slot["node_id"] = nid.hex()

        tasks = []
        for i in range(self.n):
            slot = {"snap": None, "pushes": 0, "entries_rx": 0,
                    "max_push": 0}
            self.views.append(slot)
            tasks.append(_one(i, slot))
        await __import__("asyncio").gather(*tasks)
        self.conns = [s["conn"] for s in self.views]
        self.node_ids = [s["node_id"] for s in self.views]

    def kill(self, i: int):
        import asyncio

        asyncio.run_coroutine_threadsafe(
            self.conns[i].close(), self.loop).result(timeout=10)

    def stop(self):
        import asyncio

        async def _close_all():
            for conn in self.conns:
                try:
                    await conn.close()
                except Exception:
                    pass

        try:
            asyncio.run_coroutine_threadsafe(
                _close_all(), self.loop).result(timeout=30)
        except Exception:
            pass
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)


# `chunk_step_against_decode`'s cases, for a chunk of 8 in 5 slots, where a
# token-wise half takes 13 rows a call, the 5 first lanes and 8 further ones:
# (lengths, active, rounds)
LANES_OF_A_STEP = {
    "none-prefills": ([1, 1, 4, 1, 1], [1, 1, 0, 1, 1], 1),
    "one-prefills": ([6, 1, 4, 1, 0], [1, 1, 0, 1, 1], 1),
    "two-prefill": ([6, 1, 4, 1, 3], [1, 1, 0, 1, 1], 1),
    "three-prefill": ([5, 1, 4, 3, 2], [1, 1, 0, 1, 1], 1),
    "all-prefill-whole-chunks": ([8, 8, 8, 8, 8], [1, 1, 1, 1, 1], 5),
    "two-together-two-alone": ([4, 0, 5, 8, 2], [1, 1, 1, 1, 1], 3),
}


def chunk_step_against_decode(model, cfg, case: str, logit_tol: float,
                              leaf_tol: float, C: int = 8, T: int = 48,
                              seed: int = 0):
    """A serving family's one chunk step against the same tokens a token at
    a time (the family tests' shared check; it lives here because `tests/`
    is no package): slot b takes `lengths[b]` tokens of a chunk of C where
    `active[b]`, behind a history of its own length that `decode_step` fed.
    The chunk program's logits at each such slot's last lane and every leaf
    of its cache are the decode program's within the tolerances, a slot
    that takes none keeps every leaf bit for bit, and the step's lanes go
    through a token-wise half in the case's rounds (`lm.lane_rounds`)."""
    import functools

    import jax
    import numpy as np

    from ray_tpu.models import lm

    lengths, active, rounds = LANES_OF_A_STEP[case]
    B = len(lengths)
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int32)
    active = np.asarray(active, bool)
    takes = np.where(active, lengths, 0)
    history = (3 + 2 * np.arange(B)).astype(np.int32)
    params = model.init_params(jax.random.key(seed), cfg)
    step = jax.jit(functools.partial(model.decode_step, cfg=cfg))
    chunk = jax.jit(functools.partial(model.prefill_chunk, cfg=cfg))
    cache = model.init_cache(cfg, B, T)
    for t in range(int(history.max())):
        _, cache = step(params, cache,
                        rng.integers(1, cfg.vocab_size, B).astype(np.int32),
                        np.full((B,), t, np.int32), t < history)
    before = jax.tree.map(np.asarray, cache)
    tokens = rng.integers(1, cfg.vocab_size, (B, C)).astype(np.int32)
    got, after = chunk(params, cache, tokens, history, lengths, active)
    got, after = np.asarray(got), jax.tree.map(np.asarray, after)
    want = np.zeros_like(got)
    for t in range(int(takes.max(initial=0))):
        logits, cache = step(params, cache, tokens[:, t], history + t,
                             t < takes)
        want = np.where((t == takes - 1)[:, None], np.asarray(logits), want)
    moved = takes > 0
    assert np.abs(got - want)[moved].max(initial=0) <= logit_tol
    for name, leaf in jax.tree.map(np.asarray, cache).items():
        if name == "counts":
            continue
        a, b = (np.moveaxis(x.astype(np.float32), 1, 0)
                for x in (after[name], leaf))
        assert np.abs(a - b).max() <= leaf_tol * max(1, np.abs(b).max()), (
            name, np.abs(a - b).max(), np.abs(b).max())
        np.testing.assert_array_equal(after[name][:, ~moved],
                                      before[name][:, ~moved], err_msg=name)
    further = np.arange(1, C + 1)[None, :] < takes[:, None]
    plan = lm.lane_rounds(further, lm.slots_first(further.any(axis=1)))
    assert int(plan["count"]) == rounds
