"""Head (GCS) fault tolerance: snapshot, SIGKILL, restore.

Mirrors the reference's GCS-FT semantics (Redis-backed tables + GcsActorManager
restart of detached actors): control-plane state survives a head restart;
detached actors are re-created from their stored specs; a fresh driver finds
everything by name.

Partition-tolerant scheduler additions: a head SIGKILLed mid-warm-burst
comes back, node daemons (which kept serving warm leases from their pools
throughout the outage) reconnect and run the pool-reconciliation
handshake, and the rebuilt ledger matches the daemons' reported
carve-outs exactly — no double-grant, no leaked carve-out; stale-epoch
operations are rejected and counted, and retryable tasks submitted
across the outage all complete.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu


def _start_head(session: str, restore: bool = False) -> tuple:
    cmd = [sys.executable, "-m", "ray_tpu.core.head_main",
           "--session", session, "--num-cpus", "4", "--enable-snapshots"]
    if restore:
        cmd.append("--restore")
    from ray_tpu.core.resources import strip_device_env

    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True,
                            env=strip_device_env(dict(os.environ)))
    line = proc.stdout.readline()
    assert line.startswith("RAY_TPU_HEAD_PORT="), line
    port = int(line.strip().split("=")[1])
    if restore:
        line = proc.stdout.readline()
        assert line.strip() == "RAY_TPU_RESTORED=1", line
    return proc, port


def test_head_restart_restores_state(tmp_path):
    session = f"fttest{os.getpid()}"
    proc, port = _start_head(session)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port}")

        @ray_tpu.remote(lifetime="detached", name="ft-counter")
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        h = Counter.remote()
        assert ray_tpu.get(h.incr.remote()) == 1
        client = ray_tpu.core.api._global_client()
        client.head_request("kv_put", ns="app", key=b"cfg",
                            value=b"persisted", overwrite=True)
        # wait for a snapshot cycle to capture the state
        time.sleep(3.0)
        ray_tpu.shutdown()
    finally:
        proc.kill()
        proc.wait()

    # --- head comes back with --restore
    proc2, port2 = _start_head(session, restore=True)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port2}")
        client = ray_tpu.core.api._global_client()
        assert client.head_request("kv_get", ns="app", key=b"cfg") == b"persisted"
        # detached actor was re-created from its spec (fresh state: the
        # process died with the old head, like a GCS-driven actor restart)
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                h = ray_tpu.get_actor("ft-counter")
                assert ray_tpu.get(h.incr.remote(), timeout=15) == 1
                break
            except Exception:
                time.sleep(0.5)
        else:
            pytest.fail("detached actor not restored after head restart")
        ray_tpu.shutdown()
    finally:
        proc2.kill()
        proc2.wait()


def test_head_restart_restores_pg_bound_actor():
    """Regression: restored detached actors bound to a placement group need
    the PG re-created first, or scheduling marks them DEAD."""
    session = f"ftpg{os.getpid()}"
    proc, port = _start_head(session)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port}")
        from ray_tpu.core.placement_group import placement_group

        pg = placement_group([{"CPU": 1}], strategy="PACK")
        assert pg.ready(timeout=30)

        @ray_tpu.remote(lifetime="detached", name="ft-pg-actor",
                        num_cpus=1, placement_group=pg)
        class Svc:
            def ping(self):
                return "pong"

        h = Svc.remote()
        assert ray_tpu.get(h.ping.remote(), timeout=30) == "pong"
        time.sleep(3.0)  # snapshot cycle
        ray_tpu.shutdown()
    finally:
        proc.kill()
        proc.wait()

    proc2, port2 = _start_head(session, restore=True)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port2}")
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                h = ray_tpu.get_actor("ft-pg-actor")
                assert ray_tpu.get(h.ping.remote(), timeout=15) == "pong"
                break
            except Exception:
                time.sleep(0.5)
        else:
            pytest.fail("PG-bound detached actor not restored")
        ray_tpu.shutdown()
    finally:
        proc2.kill()
        proc2.wait()


@pytest.mark.chaos
def test_head_restart_reconciles_daemon_pools_no_double_grant():
    """The partition-tolerance acceptance drill: kill the head
    mid-warm-burst, restart it on the same port, and assert that after
    the reconciliation handshake (1) the head ledger's granted capacity
    equals the union of daemon-reported carve-outs — no double-grant, no
    leaked carve-out; (2) the cluster epoch advanced and stale-epoch RPCs
    are rejected-and-counted rather than applied; (3) retryable tasks
    submitted before, during, and after the outage all complete."""
    from ray_tpu.cluster_utils import Cluster, warm_daemon_lease
    from ray_tpu.util import state

    overrides = {
        # the daemon pool must outlive the restart window...
        "RAY_TPU_POOL_IDLE_S": "60",
        # ...while the driver lease cycles fast (returns workers to the
        # daemon pool, so the pool holds idle carve-outs to reconcile)
        "RAY_TPU_LEASE_IDLE_S": "0.5",
        "RAY_TPU_METRICS_PUSH_INTERVAL_S": "0.5",
    }
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    cluster = Cluster(num_cpus=0, enable_snapshots=True)
    nid = cluster.add_node(num_cpus=4)
    try:
        cluster.connect()
        cluster.wait_for_nodes(2)
        client = ray_tpu.core.api._global_client()
        deadline = time.time() + 30
        while time.time() < deadline and not any(
                e.get("sched_addr")
                for e in client.cluster_view.entries.values()):
            time.sleep(0.1)

        @ray_tpu.remote
        def square(x):
            return x * x

        assert ray_tpu.get([square.remote(i) for i in range(8)],
                           timeout=120) == [i * i for i in range(8)]
        warm_daemon_lease(client,
                          lambda: ray_tpu.get(square.remote(2), timeout=60),
                          idle_wait=1.0)

        def node_row():
            return next(r for r in state.list_scheduler_stats()
                        if r["node_id"] == nid)

        # the daemon holds at least one carve-out (leased or idle)
        deadline = time.time() + 30
        while time.time() < deadline and node_row()["pooled_workers"] == 0:
            time.sleep(0.2)
        row = node_row()
        assert row["pooled_workers"] >= 1, row
        epoch0 = next(r for r in state.list_scheduler_stats()
                      if r.get("is_head"))["epoch"]
        assert epoch0 > 0
        pooled_wid = next(
            w["worker_id"] for w in state.list_workers()
            if not w["is_driver"] and w["node_id"] == nid)

        # in-flight burst across the kill; retryable (default max_retries)
        refs = [square.remote(i) for i in range(16)]
        cluster.kill_head()
        # submissions during the outage: the warm lease keeps serving;
        # anything that needs the head queues client-side for replay
        refs += [square.remote(i) for i in range(16, 24)]
        cluster.restart_head(restore=True)

        # wait for the daemon to reconnect and reconcile
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                if node_row()["reconciled"]:
                    break
            except (StopIteration, Exception):
                pass
            time.sleep(0.3)
        assert node_row()["reconciled"], node_row()

        # every retryable task submitted across the outage completes
        assert ray_tpu.get(refs, timeout=180) == [i * i for i in range(24)]

        # reconciliation events + epoch bump are visible
        head_row = next(r for r in state.list_scheduler_stats()
                        if r.get("is_head"))
        assert head_row["epoch"] > epoch0, (head_row["epoch"], epoch0)
        assert head_row["reconciles"] >= 1, head_row
        kinds = {e["kind"] for e in state.list_lease_events()}
        assert "pool_reconcile" in kinds, kinds

        # ledger consistency: once the burst drains and the driver lease
        # idles back into the daemon pool, the head's carved capacity
        # must equal the union of daemon-reported carve-outs, and the
        # node ledger must balance exactly (no double-grant, no leak)
        deadline = time.time() + 45
        consistent = False
        while time.time() < deadline and not consistent:
            row = node_row()
            nodes = {n["node_id"]: n for n in state.list_nodes()}
            n = nodes.get(nid)
            if n is not None and row["alive"]:
                carved = (n["resources"].get("CPU", 0)
                          - n["available"].get("CPU", 0))
                busy = sum(1 for w in state.list_workers()
                           if w["node_id"] == nid and w.get("task"))
                consistent = (
                    row["pooled_workers"] == (row["idle_workers"]
                                              + row["leased_workers"])
                    and row["pooled_workers"] >= 1
                    and abs(carved - (row["pooled_workers"] + busy)) < 1e-6)
            if not consistent:
                time.sleep(0.5)
        assert consistent, (node_row(), state.list_nodes())
        assert n["available"].get("CPU", 0) >= 0, n

        # stale-epoch fencing: an op stamped with the dead epoch is
        # rejected (and counted), never applied to the rebuilt ledger
        before = node_row()["pooled_workers"]
        rep = client.head_request("pool_release",
                                  worker_id=bytes.fromhex(pooled_wid),
                                  epoch=epoch0)
        assert isinstance(rep, dict) and rep.get("stale_epoch"), rep
        assert node_row()["pooled_workers"] == before
        head_row = next(r for r in state.list_scheduler_stats()
                        if r.get("is_head"))
        assert head_row["stale_epoch_rejects"] >= 1, head_row
        kinds = {e["kind"] for e in state.list_lease_events()}
        assert "stale_epoch" in kinds, kinds

        # duplicate-release idempotence (epoch + seq keyed): releasing the
        # same worker twice under the CURRENT epoch applies at most once
        cur_epoch = head_row["epoch"]
        r1 = client.head_request("pool_release",
                                 worker_id=bytes.fromhex(pooled_wid),
                                 grant_seq=-1, epoch=cur_epoch)
        r2 = client.head_request("pool_release",
                                 worker_id=bytes.fromhex(pooled_wid),
                                 grant_seq=-1, epoch=cur_epoch)
        assert r1 is True and r2 is True  # seq mismatch -> no-ops
        assert node_row()["pooled_workers"] == before
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
