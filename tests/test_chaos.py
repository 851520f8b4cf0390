"""Chaos tests: RPC fault injection + worker-kill monkeys under load.

Mirrors the reference's chaos strategy (SURVEY §4.1): config-flag RPC
failure injection (`rpc_chaos.h`, RAY_testing_rpc_failure) and
ResourceKiller-style actors killing workers while a workload runs
(`python/ray/_private/test_utils.py:1283`).
"""

import os
import random
import threading
import time

import pytest

import ray_tpu
from ray_tpu.core import protocol


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=4, num_tpu_chips=0, max_workers=10)
    yield info
    ray_tpu.shutdown()


def test_flight_recorder_warm_burst_and_daemon_death():
    """Flight recorder on a real 2-node cluster, one spin-up for three
    contracts: (a) a warm daemon-granted burst makes ZERO head round
    trips with instrumentation enabled, yet its local-grant events/
    counters still reach the head (they ride the existing gossip);
    (b) freezing the daemon makes the head's cluster_view_staleness_s
    for that node rise (gossip heartbeat stops); (c) killing it expires
    the node's and its workers' _metrics KV snapshots."""
    import signal

    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core import config as _config
    from ray_tpu.util import state

    # tight intervals keep this multi-phase test inside the tier-1 budget:
    # fast lease idle-out (the head-vs-daemon cold-grant race dance) and a
    # fast telemetry heartbeat (the staleness clock under test). Set BEFORE
    # spawning so head/daemon/workers inherit them.
    overrides = {"RAY_TPU_LEASE_IDLE_S": "0.5",
                 "RAY_TPU_METRICS_PUSH_INTERVAL_S": "0.5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    cluster = Cluster(num_cpus=0)  # head schedules nothing itself
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

        @ray_tpu.remote(max_retries=0)
        def worker_ident():
            from ray_tpu.util import metrics as m

            import ray_tpu.core.api as api

            m.Gauge("test_fr_node_worker", "probe").set(1.0)
            m.flush()
            return api._global_client().worker_id.hex(), os.getpid()

        assert ray_tpu.get([square.remote(i) for i in range(10)],
                           timeout=120) == [i * i for i in range(10)]
        # warm a daemon-granted lease (a head-granted one may win the
        # cold race; let it idle out and retry — same dance as
        # test_resource_view.test_daemon_grants_lease_without_head)
        deadline = time.time() + 90
        while (time.time() < deadline
               and client.lease_stats["daemon_grants"] == 0):
            ray_tpu.get(square.remote(2), timeout=60)
            if client.lease_stats["daemon_grants"]:
                break
            if client._leases:
                time.sleep(float(_config.get("lease_idle_s")) + 0.5)
            else:
                time.sleep(0.05)
        assert client.lease_stats["daemon_grants"] >= 1, client.lease_stats

        # (a) warm burst: zero head round trips. With the short lease
        # idle set above the lease can expire between phases, so re-warm
        # and start the burst immediately (an expired lease would route
        # tasks through the head and fail the zero-RPC assertion for the
        # wrong reason)
        deadline = time.time() + 30
        while time.time() < deadline and not client._leases:
            ray_tpu.get(square.remote(0), timeout=30)
        assert client._leases
        events = []

        def hook(conn_name, kind, method):
            if conn_name == "head":
                events.append((kind, method))

        protocol.add_rpc_interposer(hook)
        try:
            refs = [square.remote(i) for i in range(25)]
            out = ray_tpu.get(refs, timeout=60)
        finally:
            protocol.remove_rpc_interposer(hook)
        assert out == [i * i for i in range(25)]
        reqs = [m for k, m in events if k == "req"]
        assert not reqs, f"instrumented warm burst made head RPCs: {reqs}"

        # the daemon's flight-recorder events + counters reach the head
        # via gossip (no new RPCs anywhere to carry them)
        deadline = time.time() + 30
        while time.time() < deadline:
            kinds = {e["kind"] for e in state.list_lease_events()}
            if "local_grant" in kinds:
                break
            time.sleep(0.3)
        assert "local_grant" in kinds, kinds
        row = next(r for r in state.list_scheduler_stats()
                   if r["node_id"] == nid)
        assert row["local_grants"] >= 1, row
        assert row["staleness_s"] < 30, row

        # worker + daemon metrics snapshots are in the KV namespace
        wid, wpid = ray_tpu.get(worker_ident.remote(), timeout=60)
        wkey, nkey = f"proc:{wid}".encode(), f"proc:node-{nid[:12]}".encode()
        deadline = time.time() + 30
        while time.time() < deadline:
            if (client.head_request("kv_get", ns="_metrics", key=wkey)
                    is not None
                    and client.head_request("kv_get", ns="_metrics",
                                            key=nkey) is not None):
                break
            time.sleep(0.3)
        assert client.head_request("kv_get", ns="_metrics",
                                   key=wkey) is not None
        assert client.head_request("kv_get", ns="_metrics",
                                   key=nkey) is not None

        # (b) frozen daemon: heartbeat stops, head-side staleness rises
        cluster.stop_node(nid)
        time.sleep(2.0)  # = 4x the 0.5s heartbeat interval set above
        row = next(r for r in state.list_scheduler_stats()
                   if r["node_id"] == nid)
        assert row["staleness_s"] > 1.0, row

        # (c) killed daemon: its (and its workers') metric keys expire.
        # The daemon's workers survive it and RECONNECT to the live head
        # (head-FT semantics adopt them onto the head node), which would
        # legitimately re-push their snapshots — kill the worker process
        # too so both expiries are observable.
        cluster._nodes[0].send_signal(signal.SIGCONT)
        cluster.kill_node(nid)
        try:
            os.kill(wpid, 9)
        except OSError:
            pass  # already died with its node
        deadline = time.time() + 60
        while time.time() < deadline:
            if (client.head_request("kv_get", ns="_metrics", key=wkey)
                    is None
                    and client.head_request("kv_get", ns="_metrics",
                                            key=nkey) is None):
                break
            time.sleep(0.3)
        assert client.head_request("kv_get", ns="_metrics", key=nkey) \
            is None, "dead daemon's metrics snapshot still scraped"
        assert client.head_request("kv_get", ns="_metrics", key=wkey) \
            is None, "dead node's worker metrics snapshot still scraped"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_rpc_chaos_injection_and_reset(cluster):
    protocol.configure_chaos("kv_put:1.0")
    try:
        client = ray_tpu.core.api._global_client()
        with pytest.raises(protocol.ConnectionLost, match="chaos"):
            client.head_request("kv_put", ns="t", key=b"k", value=b"v",
                                overwrite=True)
    finally:
        protocol.configure_chaos("")
    assert client.head_request("kv_put", ns="t", key=b"k", value=b"v",
                               overwrite=True) is not None


def test_rpc_chaos_env_spec():
    protocol.configure_chaos("a:0.5,b:1.0")
    assert protocol._chaos == {"a": 0.5, "b": 1.0}
    protocol.configure_chaos("")
    assert protocol._chaos == {}


@ray_tpu.remote
def _plus1(x):
    return x + 1


def test_warm_lease_path_makes_zero_head_rpcs(cluster):
    """Two-level scheduling contract: once a lease is warm, a task burst
    is dispatched, executed, and resolved with ZERO head round trips —
    proven by counting head-connection traffic through the RPC
    interposition hook, not by inspecting internals. The only permitted
    head-bound traffic is the refcount tracker's background batch flush
    (a push, not a round trip)."""
    client = ray_tpu.core.api._global_client()
    assert ray_tpu.get(_plus1.remote(0), timeout=30) == 1
    deadline = time.time() + 20
    while time.time() < deadline and not client._leases:
        ray_tpu.get(_plus1.remote(0), timeout=30)
    assert client._leases, "lease never established"
    time.sleep(0.3)  # let registration/refcount stragglers flush

    events = []

    def hook(conn_name, kind, method):
        if conn_name == "head":
            events.append((kind, method))

    protocol.add_rpc_interposer(hook)
    try:
        refs = [_plus1.remote(i) for i in range(25)]
        out = ray_tpu.get(refs, timeout=60)
    finally:
        protocol.remove_rpc_interposer(hook)
    assert out == [i + 1 for i in range(25)]
    reqs = [m for k, m in events if k == "req"]
    assert not reqs, f"warm-path burst made head round trips: {reqs}"
    pushes = {m for k, m in events if k == "push"}
    # permitted head-bound traffic is background telemetry only, and only
    # as pushes: the refcount batch flush and the metrics pusher's
    # periodic snapshot (the flight recorder deliberately rides pushes /
    # existing gossip so the warm path stays RPC-free)
    assert pushes <= {"ref_update", "metrics_push"}, \
        f"warm-path burst pushed more than telemetry batches: {pushes}"


@ray_tpu.remote(max_retries=5)
def _slow_square(x):
    time.sleep(0.2)
    return x * x


def test_worker_kill_monkey_under_load(cluster):
    """Kill random busy workers while 24 tasks run; retries land them all."""
    from ray_tpu.util import state

    stop = threading.Event()
    kills = []

    def monkey():
        rng = random.Random(0)
        while not stop.is_set():
            workers = [w for w in state.list_workers()
                       if not w["is_driver"] and w["task"]]
            if workers:
                victim = rng.choice(workers)
                try:
                    os.kill(victim["pid"], 9)
                    kills.append(victim["pid"])
                except OSError:
                    pass
            time.sleep(0.4)

    t = threading.Thread(target=monkey, daemon=True)
    t.start()
    try:
        refs = [_slow_square.remote(i) for i in range(24)]
        out = ray_tpu.get(refs, timeout=180)
    finally:
        stop.set()
        t.join(timeout=5)
    assert out == [i * i for i in range(24)]
    assert kills, "monkey never killed anything — test proved nothing"


def test_actor_restart_under_repeated_kill(cluster):
    @ray_tpu.remote(max_restarts=3)
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

        def pid(self):
            return os.getpid()

    c = Counter.remote()
    assert ray_tpu.get(c.incr.remote()) == 1
    for round_ in range(2):
        pid = ray_tpu.get(c.pid.remote())
        os.kill(pid, 9)
        deadline = time.time() + 60
        while time.time() < deadline:
            try:
                # state resets on restart (no persistence), process is new
                if ray_tpu.get(c.incr.remote(), timeout=10) >= 1 and \
                        ray_tpu.get(c.pid.remote(), timeout=10) != pid:
                    break
            except Exception:
                time.sleep(0.3)
        else:
            pytest.fail(f"actor did not restart after kill round {round_}")
    ray_tpu.kill(c)


def test_object_pull_survives_owner_node_freeze(cluster):
    """A consumer pulling an object whose host node FREEZES (SIGSTOP'd
    store-serving process) must not hang forever: health checks declare
    the process dead and the consumer surfaces a loss/reconstruction
    outcome instead of stalling (reference: pull retry + health manager
    interplay)."""
    import signal
    import numpy as np

    @ray_tpu.remote
    def make_big():
        return np.ones(300_000, np.uint8)   # > inline: lives in the store

    ref = make_big.remote()
    assert ray_tpu.get(ref, timeout=30).sum() == 300_000
    # find the producing worker and freeze it; the object lives in shm so
    # same-machine reads still work — this asserts the CONTROL plane
    # stays responsive around a frozen peer, and the value stays readable
    from ray_tpu.util import state

    workers = [w for w in state.list_workers() if not w["is_driver"]]
    assert workers
    victim = workers[0]["pid"]
    os.kill(victim, signal.SIGSTOP)
    try:
        got = ray_tpu.get(ref, timeout=60)
        assert got.sum() == 300_000
        # the cluster still schedules new work while the peer is frozen
        @ray_tpu.remote
        def alive():
            return "yes"

        assert ray_tpu.get(alive.remote(), timeout=60) == "yes"
    finally:
        try:
            os.kill(victim, signal.SIGCONT)
        except OSError:
            pass


# ------------------------------------------------- deterministic chaos plane
def test_chaos_plan_determinism_and_triggers():
    """Seeded fault plans are reproducible: the same seed + spec yields
    the same injected-fault sequence; nth/every triggers fire exactly
    where configured; partition windows open and close on time."""
    from ray_tpu.core.protocol import ChaosPlan

    spec = "drop:foo:p=0.5,seed=42"
    p1, p2 = ChaosPlan.parse(spec), ChaosPlan.parse(spec)
    seq1 = [bool(p1.actions("edge", "foo")) for _ in range(200)]
    seq2 = [bool(p2.actions("edge", "foo")) for _ in range(200)]
    assert p1.injected, "p=0.5 over 200 calls injected nothing"
    assert seq1 == seq2 and p1.injected == p2.injected, \
        "same seed+spec diverged"
    p3 = ChaosPlan.parse("drop:foo:p=0.5,seed=43")
    seq3 = [bool(p3.actions("edge", "foo")) for _ in range(200)]
    assert seq3 != seq1, \
        "different seeds produced the identical fault sequence"

    # nth-call trigger: fires exactly once, on the 2nd matching call
    p4 = ChaosPlan.parse("dup:bar:n=2")
    fired = [bool(p4.actions("e", "bar")) for _ in range(5)]
    assert fired == [False, True, False, False, False], fired
    # every-k trigger
    p5 = ChaosPlan.parse("delay:baz:t=0.01:every=3")
    fired = [bool(p5.actions("e", "baz")) for _ in range(7)]
    assert fired == [False, False, True, False, False, True, False], fired
    # method and edge globs
    p6 = ChaosPlan.parse("drop:pool_*@node")
    assert p6.actions("node", "pool_release")
    assert not p6.actions("sched-1", "pool_release")
    assert not p6.actions("node", "lease_grant")

    # timed partition window (after/for, relative to plan creation)
    p7 = ChaosPlan.parse("partition:node:after=0.05:for=0.05")
    assert not p7.partitioned("node")
    p7.t0 -= 0.06  # simulate time passing into the window
    assert p7.partitioned("node") and not p7.partitioned("sched-1")
    p7.t0 -= 0.1   # ...and past it
    assert not p7.partitioned("node")


def test_chaos_dup_request_is_idempotent_at_transport():
    """Duplicate delivery of a request frame (the `dup` fault kind) must
    not run the handler twice: the receiving connection dedupes request
    ids (at-most-once dispatch). Duplicate PUSH frames do reach the
    handler — push handlers on the pool paths are idempotence-keyed
    instead (epoch + grant_seq, covered by the head-FT tests)."""
    import asyncio

    async def run():
        calls = {"req": 0, "push": 0}

        async def bump():
            calls["req"] += 1
            return calls["req"]

        async def poke():
            calls["push"] += 1

        server = protocol.Server({"bump": bump, "poke": poke},
                                 name="dup-srv")
        port = await server.start()
        conn = await protocol.connect("127.0.0.1", port, name="dup-edge")
        protocol.configure_chaos("dup:bump@dup-edge,dup:poke@dup-edge")
        try:
            out = await conn.request("bump")
            conn.push("poke")
        finally:
            protocol.configure_chaos("")
        await asyncio.sleep(0.3)  # let the duplicate frames arrive
        assert out == 1 and calls["req"] == 1, calls
        assert calls["push"] == 2, calls  # pushes have no rid to dedupe
        await conn.close()
        await server.stop()

    asyncio.run(run())


def test_chaos_injected_metric_visible(cluster):
    """Injected faults are observable: every injection feeds the flight
    recorder's chaos_injected_total{method,kind} counter, which reaches
    /metrics via the normal per-process export paths."""
    import urllib.request

    from ray_tpu.util import metrics as _metrics

    client = ray_tpu.core.api._global_client()
    protocol.configure_chaos("drop:kv_put@head:n=1")
    try:
        with pytest.raises(protocol.RpcError):
            client._call(client.conn.request(
                "kv_put", ns="t", key=b"chaosmetric", value=b"v",
                overwrite=True))
    finally:
        protocol.configure_chaos("")
    snap = {m["name"]: m for m in _metrics.snapshot_all()}
    assert "chaos_injected_total" in snap, sorted(snap)
    series = snap["chaos_injected_total"]["series"]
    assert any(s["tags"].get("method") == "kv_put"
               and s["tags"].get("kind") == "drop"
               and s["value"] >= 1 for s in series), series
    # ...and the dashboard scrape exposes it (driver pushes its registry
    # snapshot to the head's _metrics KV on the metrics cadence)
    info = client.head_request("cluster_info")
    dport = info.get("dashboard_port")
    if dport:
        deadline = time.time() + 20
        text = ""
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{dport}/metrics",
                        timeout=5) as r:
                    text = r.read().decode()
            except OSError:
                text = ""
            if "chaos_injected_total" in text:
                break
            time.sleep(0.5)
        assert "chaos_injected_total" in text, \
            "injected fault never reached /metrics"


@pytest.mark.chaos
def test_daemon_partition_warm_path_continues_and_gossip_drains():
    """Partition tolerance (tentpole graceful-degradation contract): a
    timed chaos window severs daemon<->head while client<->daemon and
    worker<->head traffic continues. During the window the daemon keeps
    serving warm-path leases (tasks complete), the head's view of the
    node goes stale; after heal the daemon's queued flight-recorder
    events drain (delivery acks requeue un-acked batches) and its
    counters catch up at the head."""
    from ray_tpu.cluster_utils import Cluster, warm_daemon_lease
    from ray_tpu.util import state

    overrides = {"RAY_TPU_POOL_IDLE_S": "60",
                 "RAY_TPU_LEASE_IDLE_S": "1.0",
                 "RAY_TPU_METRICS_PUSH_INTERVAL_S": "0.5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    ray_tpu.shutdown()  # detach from any module-fixture cluster first
    cluster = Cluster(num_cpus=0)
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
                          lambda: ray_tpu.get(square.remote(2), timeout=60))

        def node_row():
            return next(r for r in state.list_scheduler_stats()
                        if r["node_id"] == nid)

        # park the lease back into the daemon pool, so the burst below
        # must RE-GRANT daemon-locally DURING the partition — producing
        # local_grant events inside the severed window
        with client._lease_lock:
            for lease in client._leases.values():
                lease.dead = True
        deadline = time.time() + 30
        while time.time() < deadline and node_row()["idle_workers"] < 1:
            time.sleep(0.3)
        assert node_row()["idle_workers"] >= 1, node_row()
        grants_before = node_row().get("local_grants", 0)

        # sever daemon<->head for 4s via the chaos control plane
        assert client.head_request(
            "set_node_chaos", node_id=bytes.fromhex(nid),
            spec="partition:node:for=4") is True
        time.sleep(0.5)  # inside the window

        # warm path serves THROUGH the partition: the daemon re-grants
        # from its pool with zero daemon<->head traffic possible
        out = ray_tpu.get([square.remote(i) for i in range(20)],
                          timeout=90)
        assert out == [i * i for i in range(20)]

        # the head's gossip view of the node went stale meanwhile
        row = node_row()
        assert row["staleness_s"] > 0.5, row

        # heal: wait past the window, then the queued events drain —
        # the in-window local_grant reaches the head only via the
        # ack-tracked resend (a severed delta cannot drop its batch)
        deadline = time.time() + 60
        caught_up = False
        while time.time() < deadline and not caught_up:
            row = node_row()
            caught_up = (row["staleness_s"] < 1.5
                         and row.get("local_grants", 0) > grants_before)
            if not caught_up:
                time.sleep(0.5)
        assert caught_up, (row, grants_before)
        kinds = {e["kind"] for e in state.list_lease_events()}
        assert "local_grant" in kinds, kinds
        assert "chaos_config" in kinds, kinds
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_no_test_imports_from_a_module_called_conftest():
    """Two modules are called `conftest` (this directory's and
    `chip_bench/`'s, with no package between them), so in a worker that
    collected a `chip_bench` file first a name imported from `conftest`
    comes from the wrong one: the two drills that did so failed for five
    PRs. Shared helpers live in `ray_tpu.cluster_utils`."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    needle = "from conftest " + "import"
    found = []
    for root, dirs, files in os.walk(tests_dir):
        dirs[:] = [d for d in dirs if d != "chip_bench"]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    if needle in f.read():
                        found.append(os.path.join(root, name))
    assert found == []
