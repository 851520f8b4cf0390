"""Chaos soak: warm-burst + elastic-train drill under RAY_TPU_CHAOS.

Single-command CI soak (marked `slow` via tests/test_soak.py) that drives
the two acceptance workloads through the deterministic chaos plane with a
FIXED seed, so a failure replays identically:

  phase 1 — warm-burst: a 2-node cluster where one daemon runs a seeded
  delay/dup plan on its control-plane edges; pipelined task bursts must
  all complete (the two-level warm path absorbs injected gossip delay and
  duplicated frames without dropping work).

  phase 1b — head-paused burst: SIGSTOP the head mid warm+cold burst on
  a 2-node cluster; task completions must continue through the
  peer-spillback mesh (daemon-local + epoch-fenced peer-referred grants,
  cold tasks parked in client-local dispatch queues) and the pool
  ledgers must reconcile on SIGCONT with zero double grants.

  phase 2 — large-object data plane: an isolation-mode 2-node cluster
  where the consumer node's processes run a seeded drop plan on their
  data edges; workers repeatedly consume large remote objects, so every
  round exercises the daemon pull manager's chunk retry + the gossiped
  object directory under injected faults, bit-exactness asserted.

  phase 2b — shuffle node kill: a distributed hash shuffle lands every
  map sub-block on one isolated node, which is SIGKILLed before the
  reduce stage consumes them; lineage reconstruction must re-run exactly
  the lost map tasks on a replacement node, the reduce output must be
  byte-identical to the in-process reference, and
  data_blocks_reconstructed_total must count the rebuilt sub-blocks.

  phase 3 — serve plane: an autoscaled deployment behind the HTTP proxy
  takes sustained multi-client load; mid-load a replica arms a seeded
  `kill:*:n=1` chaos plan in its own process and SIGKILLs itself on its
  next outbound telemetry push. The proxy's failover retry, admission
  control, and the controller's health loop must hold ZERO non-shed
  failures (429s are allowed and counted; 5xx are not).

  phase 3b — compiled serve chain: sustained load through a
  CompiledServeChain (pre-negotiated channel rings; zero per-request
  control-plane RPCs) while the chain's replica chaos-self-kills
  mid-load: the generation must fence, in-flight ring entries drain or
  fail over to the dynamic handle path with ZERO failures, and the
  chain must recompile over the replacement replica and serve compiled
  traffic again before the phase ends.

  phase 3c — external HTTP over the compiled ingress: a `compiled=True`
  two-replica deployment behind the HTTP proxy (the proxy writes request
  batches straight into its CompiledServeChain rings, lanes spread over
  both replicas); mid-load one replica chaos-self-kills. ZERO non-shed
  HTTP failures may surface to the external clients, and the proxy's
  chain must recompile its lanes over the replacement replica
  (generation bump observed via `proxy.chain_status`).

  phase 3d — cold-model burst (ISSUE 20): two tenants behind the HTTP
  proxy — a warm always-on deployment under sustained load, and a
  second model PARKED AT ZERO (`min_replicas=0`, slow replica init
  standing in for a checkpoint/weight-plane load). A client burst hits
  the parked model's route mid-phase: the proxy must QUEUE (never 500),
  push demand to the controller, and the first replica must wake and
  answer within the cold-start SLO — while the warm tenant's latency
  holds and ZERO non-shed failures surface on either route.

  phase 4 — elastic-train drill: a 2-worker GPT-2-DDP run
  (`_elastic_train_loop`); once the gang makes progress, a
  `kill:*:n=1` plan is injected into one daemon over the chaos control
  plane (`set_node_chaos`), so the daemon SIGKILLs itself on its next
  outbound call — a chaos-injected daemon kill, not a test harness kill.
  The controller must shrink to the surviving worker, restore the
  resharded checkpoint, and FINISH.

Run: `python benchmarks/soak.py [--seed 7] [--out soak.json]`
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def warm_burst_soak(seed: int, rounds: int = 6, burst: int = 40) -> dict:
    """Task bursts against a daemon running a seeded delay/dup chaos plan."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    chaos = (f"seed={seed},"
             "delay:resource_view_delta@node:p=0.3:t=0.05,"
             "dup:lease_return@*:p=0.2")
    cluster = Cluster(num_cpus=0)
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2, env={"RAY_TPU_CHAOS": chaos})
    try:
        cluster.connect()
        cluster.wait_for_nodes(3)

        @ray_tpu.remote
        def square(x):
            return x * x

        t0 = time.perf_counter()
        done = 0
        for _ in range(rounds):
            out = ray_tpu.get([square.remote(i) for i in range(burst)],
                              timeout=120)
            assert out == [i * i for i in range(burst)]
            done += burst
        elapsed = time.perf_counter() - t0
        return {"tasks_completed": done, "elapsed_s": round(elapsed, 2),
                "tasks_per_s": round(done / elapsed, 1), "chaos": chaos}
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def head_paused_burst(seed: int, shapes: int = 4, per_shape: int = 8) -> dict:
    """SIGSTOP the head mid warm+cold burst: task completions must
    CONTINUE through the peer-spillback mesh (daemon-local grants +
    epoch-fenced peer-referred grants, cold tasks parked in the client's
    local dispatch queues), and on SIGCONT the pool ledgers must
    reconcile with zero double grants and zero stale-epoch rejects."""
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster, carve_pool

    overrides = {"RAY_TPU_LEASE_IDLE_S": "0.5",
                 "RAY_TPU_POOL_IDLE_S": "60",
                 "RAY_TPU_POOL_ACQUIRE_TIMEOUT_S": "2",
                 "RAY_TPU_METRICS_PUSH_INTERVAL_S": "0.5"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    cluster = Cluster(num_cpus=0)
    cluster.add_node(num_cpus=2, labels={"zone": "a"})
    cluster.add_node(num_cpus=2, labels={"zone": "b"})
    paused = False
    try:
        cluster.connect()
        cluster.wait_for_nodes(3)
        client = ray_tpu.core.api._global_client()
        deadline = time.time() + 30
        while time.time() < deadline and sum(
                1 for e in client.cluster_view.entries.values()
                if e.get("sched_addr")) < 2:
            time.sleep(0.2)
        for e in list(client.cluster_view.entries.values()):
            if e.get("sched_addr"):
                carve_pool(client, tuple(e["sched_addr"]), 2,
                           selector={"zone": e["labels"]["zone"]})

        fns = []
        for i in range(shapes):
            exec(f"@ray_tpu.remote\ndef _soak_g{i}(x):\n"
                 f"    return x * {i + 2}\nfns.append(_soak_g{i})",
                 {"ray_tpu": ray_tpu, "fns": fns})

        # warm half the shapes before the pause (their defs + leases have
        # existed; the rest stay cold so the outage window exercises the
        # parked/referral path), then let the warm leases idle back into
        # the pools so the pause catches both daemons at full pools
        warm = fns[: shapes // 2]
        assert ray_tpu.get([f.remote(1) for f in warm], timeout=90)
        deadline = time.time() + 30
        while time.time() < deadline:
            idles = [e.get("idle_workers", 0)
                     for e in client.cluster_view.entries.values()
                     if e.get("sched_addr")]
            if (sum(1 for i in idles if i >= 2) >= 2
                    and not client._leases):
                break
            time.sleep(0.2)
        t_pause = time.perf_counter()
        cluster.stop_head()
        paused = True
        client._head_suspect_until = time.monotonic() + 120
        refs = [f.remote(j) for j in range(per_shape) for f in fns]
        out = ray_tpu.get(refs, timeout=120)
        paused_window_s = time.perf_counter() - t_pause
        expect = [j * (i + 2) for j in range(per_shape)
                  for i in range(shapes)]
        assert out == expect, "burst results corrupted"
        cluster.cont_head()
        paused = False
        client._head_suspect_until = 0.0

        def rows():
            return [r for r in client.head_request(
                "list_state", kind="scheduler_stats")
                if not r.get("is_head")]

        deadline = time.time() + 60
        peer_grants = 0
        while time.time() < deadline:
            rs = rows()
            ok = rs and all(
                r.get("pooled_workers") == (r.get("idle_workers", 0)
                                            + r.get("leased_workers", 0))
                for r in rs)
            peer_grants = sum(r.get("peer_grants", 0) for r in rs)
            if ok and peer_grants >= 1:
                break
            time.sleep(0.5)
        assert peer_grants >= 1, f"no peer grants recorded: {rows()}"
        head_row = next(r for r in client.head_request(
            "list_state", kind="scheduler_stats") if r.get("is_head"))
        assert head_row.get("stale_epoch_rejects", 0) == 0, head_row
        return {"tasks_completed": len(out),
                "paused_window_s": round(paused_window_s, 2),
                "peer_grants": peer_grants,
                "client_peer_grants": client.lease_stats["peer_grants"]}
    finally:
        if paused:
            cluster.cont_head()
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def large_object_soak(seed: int, rounds: int = 4, mb: int = 12) -> dict:
    """Cross-node large-object traffic under a seeded drop/delay plan on
    the data edge. Store isolation forces real transfers; the chaos env
    is inherited by the consumer node's workers, so their pulls (routed
    through the node daemon's pull manager) hit injected fetch_chunk
    drops and must survive via chunk retry/backoff."""
    import numpy as np

    import ray_tpu

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ray_tpu.cluster_utils import Cluster

    chaos = (f"seed={seed},drop:fetch_chunk@data-*:every=4,"
             "delay:fetch_chunk@data-*:p=0.2:t=0.02")
    saved = os.environ.get("RAY_TPU_STORE_ISOLATION")
    os.environ["RAY_TPU_STORE_ISOLATION"] = "1"
    cluster = Cluster(num_cpus=0)
    cluster.add_node(num_cpus=2, resources={"src": 4})
    cluster.add_node(num_cpus=2, resources={"dst": 4},
                     env={"RAY_TPU_CHAOS": chaos})
    try:
        cluster.connect()
        cluster.wait_for_nodes(3)

        @ray_tpu.remote
        def make(mb_, seed_):
            rng = np.random.default_rng(seed_)
            return rng.integers(0, 255, size=(mb_ * 1024 * 1024,),
                                dtype=np.uint8)

        @ray_tpu.remote
        def digest(arr):
            return int(arr[::4096].astype(np.uint64).sum()), arr.shape[0]

        t0 = time.perf_counter()
        moved = 0
        for r in range(rounds):
            ref = make.options(resources={"src": 1}).remote(mb, seed + r)
            got_sum, got_n = ray_tpu.get(
                digest.options(resources={"dst": 1}).remote(ref),
                timeout=180)
            expect = np.random.default_rng(seed + r).integers(
                0, 255, size=(mb * 1024 * 1024,), dtype=np.uint8)
            assert got_n == expect.shape[0]
            assert got_sum == int(expect[::4096].astype(np.uint64).sum())
            moved += mb
            ray_tpu.free([ref])
        elapsed = time.perf_counter() - t0
        return {"rounds": rounds, "mb_moved": moved,
                "elapsed_s": round(elapsed, 2),
                "mb_per_s": round(moved / elapsed, 1), "chaos": chaos}
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
        if saved is None:
            os.environ.pop("RAY_TPU_STORE_ISOLATION", None)
        else:
            os.environ["RAY_TPU_STORE_ISOLATION"] = saved


def serve_soak(seed: int, duration_s: float = 8.0, clients: int = 6) -> dict:
    """Sustained-QPS serve phase: an autoscaled deployment behind the
    HTTP proxy (SLO admission control armed); mid-load one replica arms
    a seeded chaos self-kill via the chaos plane
    (`protocol.configure_chaos("kill:*:n=1")` inside the replica process
    — the replica SIGKILLs itself on its next outbound telemetry push, a
    chaos-injected replica kill, not a harness kill). The proxy's
    failover retry + the controller's health loop must hold ZERO
    non-shed failures while the autoscaler keeps capacity; reports
    rps / p99 / sheds."""
    import json as _json
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8, num_tpu_chips=0, max_workers=16)

    @serve.deployment
    class SoakTarget:
        def __call__(self, request):
            time.sleep(0.02)
            return {"ok": True}

        def arm_chaos(self, spec: str) -> bool:
            from ray_tpu.core import protocol

            protocol.configure_chaos(spec)
            return True

    handle = serve.run(
        SoakTarget.options(
            max_ongoing_requests=16,
            autoscaling_config=serve.AutoscalingConfig(
                min_replicas=2, max_replicas=3, target_ongoing_requests=4),
            slo_config=serve.SLOConfig(slo_s=5.0, max_queue=64,
                                       retry_after_s=1.0)).bind(),
        name="soak-serve", route_prefix="/soak")
    port = serve.start()
    url = f"http://127.0.0.1:{port}/soak"
    codes, lats = [], []
    lock = threading.Lock()
    stop = time.monotonic() + duration_s

    def client():
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url, data=b'{"x": 1}',
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as r:
                    r.read()
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:
                code = -1
            with lock:
                codes.append(code)
                if code == 200:
                    lats.append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s / 3)
    # chaos-inject the replica kill mid-load (whichever replica the
    # handle routes this to dies within one telemetry-push interval)
    assert handle.arm_chaos.remote(
        f"seed={seed},kill:*:n=1").result(timeout=30) is True
    for t in threads:
        t.join(duration_s + 60)
    elapsed = time.perf_counter() - t_start
    served = sum(1 for c in codes if c == 200)
    shed = sum(1 for c in codes if c == 429)
    failed = len(codes) - served - shed
    try:
        final = serve.status().get("soak-serve", {})
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert failed == 0, f"{failed} non-shed failures (codes={set(codes)})"
    assert served > 0
    return {"duration_s": round(elapsed, 2), "served": served,
            "shed": shed, "failed": failed,
            "rps": round(served / elapsed, 1),
            "p99_s": round(float(np.percentile(lats, 99)), 4),
            "final_replicas": final.get("running"),
            "chaos": f"seed={seed},kill:*:n=1 (replica self-kill)"}


def cold_model_burst_soak(seed: int, duration_s: float = 12.0,
                          warm_clients: int = 4,
                          burst_clients: int = 4) -> dict:
    """Cold-model burst phase (ISSUE 20): a warm tenant under sustained
    load plus a second model PARKED AT ZERO replicas (min_replicas=0;
    its replica init sleeps, standing in for the checkpoint/weight-plane
    load a real model pays). Mid-phase a burst hits the parked model's
    route: the proxy queues the burst (zero 500s), pushes queue depth to
    the controller as demand, and the woken replica answers the whole
    burst within the cold-start SLO — while the warm tenant keeps
    serving. Reports wake latency + per-tenant rps/p99."""
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8, num_tpu_chips=0, max_workers=16)

    @serve.deployment
    class WarmTenant:
        def __call__(self, request):
            time.sleep(0.02)
            return {"ok": True, "tenant": "warm"}

    @serve.deployment
    class ColdModel:
        def __init__(self):
            # stand-in for a replica cold start's weight materialization
            time.sleep(1.5)

        def __call__(self, request):
            time.sleep(0.02)
            return {"ok": True, "tenant": "cold"}

    serve.run(WarmTenant.options(
        num_replicas=1, max_ongoing_requests=16,
        slo_config=serve.SLOConfig(slo_s=5.0, max_queue=64,
                                   retry_after_s=1.0)).bind(),
        name="soak-warm", route_prefix="/warm")
    serve.run(ColdModel.options(
        max_ongoing_requests=16,
        autoscaling_config=serve.AutoscalingConfig(
            min_replicas=0, max_replicas=1,
            target_ongoing_requests=8)).bind(),
        name="soak-cold", route_prefix="/coldmodel")
    port = serve.start()
    stop = time.monotonic() + duration_s
    lock = threading.Lock()
    stats = {"warm": {"codes": [], "lats": []},
             "cold": {"codes": [], "lats": []}}
    first_cold_ok = []

    def client(route: str, tenant: str, until: float):
        url = f"http://127.0.0.1:{port}{route}"
        while time.monotonic() < until:
            t0 = time.perf_counter()
            try:
                req = urllib.request.Request(
                    url, data=b'{"x": 1}',
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    r.read()
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:
                code = -1
            with lock:
                stats[tenant]["codes"].append(code)
                if code == 200:
                    stats[tenant]["lats"].append(time.perf_counter() - t0)
                    if tenant == "cold" and not first_cold_ok:
                        first_cold_ok.append(time.monotonic())

    threads = [threading.Thread(target=client,
                                args=("/warm", "warm", stop), daemon=True)
               for _ in range(warm_clients)]
    for t in threads:
        t.start()
    time.sleep(duration_s / 3)           # warm tenant in steady state
    burst_t0 = time.monotonic()
    burst = [threading.Thread(target=client,
                              args=("/coldmodel", "cold", stop),
                              daemon=True)
             for _ in range(burst_clients)]
    for t in burst:
        t.start()
    for t in threads + burst:
        t.join(duration_s + 120)
    try:
        cold_final = serve.status().get("soak-cold", {})
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    report = {}
    for tenant in ("warm", "cold"):
        codes, lats = stats[tenant]["codes"], stats[tenant]["lats"]
        served = sum(1 for c in codes if c == 200)
        shed = sum(1 for c in codes if c == 429)
        failed = len(codes) - served - shed
        assert failed == 0, \
            f"{tenant}: {failed} non-shed failures (codes={set(codes)})"
        assert served > 0, f"{tenant} tenant served nothing"
        report[tenant] = {
            "served": served, "shed": shed, "failed": failed,
            "p99_s": round(float(np.percentile(lats, 99)), 4)}
    assert first_cold_ok, "burst on the parked model never completed"
    wake_s = first_cold_ok[0] - burst_t0
    # cold-start SLO: replica init (1.5s) + autoscaler wake detection
    assert wake_s < 30.0, f"cold model took {wake_s:.1f}s to wake"
    # tenant isolation: the cold wake must not melt the warm tenant
    assert report["warm"]["p99_s"] < 5.0, report["warm"]
    report["cold_wake_s"] = round(wake_s, 2)
    report["cold_final_replicas"] = cold_final.get("running")
    return report


def compiled_chain_soak(seed: int, duration_s: float = 8.0,
                        clients: int = 6) -> dict:
    """Compiled serve chain phase (ISSUE 14): sustained load through a
    CompiledServeChain (pre-negotiated channel rings, zero per-request
    control-plane RPCs) while a chain replica chaos-self-kills mid-load
    (`protocol.configure_chaos("kill:*:n=1")` armed inside the replica —
    it SIGKILLs itself on its next outbound telemetry push). Acceptance:
    the generation fences, in-flight ring entries drain or fail over to
    the dynamic handle path, ZERO request failures, and the chain
    recompiles and serves compiled traffic again before the phase ends."""
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.compiled_chain import CompiledServeChain

    ray_tpu.init(num_cpus=8, num_tpu_chips=0, max_workers=16)

    @serve.deployment
    class ChainTarget:
        def __call__(self, v):
            time.sleep(0.02)
            return {"ok": True, "x": v.get("x")}

        def arm_chaos(self, spec: str) -> bool:
            from ray_tpu.core import protocol

            protocol.configure_chaos(spec)
            return True

    handle = serve.run(ChainTarget.options(max_ongoing_requests=16).bind(),
                       name="soak-chain")
    chain = CompiledServeChain(["soak-chain"], lanes=2, max_inflight=2,
                               batch_max=8, entry_timeout_s=60,
                               recompile_timeout_s=120).start()
    ok, failed, lats = [], [], []
    lock = threading.Lock()
    stop = time.monotonic() + duration_s

    def client():
        i = 0
        while time.monotonic() < stop:
            i += 1
            t0 = time.perf_counter()
            try:
                out = chain.call({"x": i}, timeout=90)
                assert out["ok"] and out["x"] == i
                with lock:
                    ok.append(i)
                    lats.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                with lock:
                    failed.append(repr(e))

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s / 3)
    # chaos-inject the replica kill mid-load (the dynamic handle routes
    # the arm call to the same single replica the chain compiled over)
    assert handle.arm_chaos.remote(
        f"seed={seed},kill:*:n=1").result(timeout=30) is True
    for t in threads:
        t.join(duration_s + 120)
    elapsed = time.perf_counter() - t_start
    recompiled = chain.wait_compiled(120)
    # compiled traffic resumes on the replacement replica
    before = chain.stats["compiled"]
    post = [chain.submit({"x": -i}) for i in range(1, 9)]
    post_ok = all(r.result(60)["ok"] for r in post)
    stats = dict(chain.stats)
    try:
        chain.shutdown()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    assert not failed, f"{len(failed)} chain request failures: {failed[:3]}"
    assert stats["fenced"] >= 1, f"chaos kill never fenced: {stats}"
    assert recompiled, f"chain never recompiled: {stats}"
    assert post_ok and stats["compiled"] > before, \
        f"compiled traffic did not resume: {stats}"
    return {"duration_s": round(elapsed, 2), "served": len(ok),
            "failed": len(failed),
            "rps": round(len(ok) / elapsed, 1),
            "p99_s": round(float(np.percentile(lats, 99)), 4),
            "fenced": stats["fenced"],
            "dynamic_fallback": stats["dynamic_fallback"],
            "recompiles": stats["recompiles"],
            "chaos": f"seed={seed},kill:*:n=1 (replica self-kill)"}


def proxy_compiled_soak(seed: int, duration_s: float = 10.0,
                        clients: int = 6) -> dict:
    """External-HTTP-over-compiled-path phase (ISSUE 19): a
    `compiled=True` deployment with TWO replicas behind the HTTP proxy —
    the proxy writes request batches straight into its per-deployment
    CompiledServeChain rings (lanes spread across both replicas) —
    while one replica chaos-self-kills mid-load
    (`protocol.configure_chaos("kill:*:n=1")` armed inside the replica).
    Acceptance: ZERO non-shed HTTP failures (the chain fences and fails
    in-flight entries over to the dynamic handle path; no external
    client ever sees a 500), the proxy chain recompiles its lanes over
    the replacement replica (generation bump), and compiled traffic
    resumes before the phase ends."""
    import json as _json
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=8, num_tpu_chips=0, max_workers=16)

    @serve.deployment
    class ProxySoakTarget:
        def __call__(self, request):
            time.sleep(0.005)
            return {"ok": True, "pid": os.getpid()}

        def arm_chaos(self, spec: str) -> bool:
            from ray_tpu.core import protocol

            protocol.configure_chaos(spec)
            return True

    handle = serve.run(
        ProxySoakTarget.options(num_replicas=2, max_ongoing_requests=16,
                                chain_config={"lanes": 2, "max_inflight": 2,
                                              "batch_max": 8,
                                              "entry_timeout_s": 60,
                                              "recompile_timeout_s": 120}
                                ).bind(),
        name="soak-proxy", route_prefix="/soakproxy", compiled=True)
    port = serve.start()
    url = f"http://127.0.0.1:{port}/soakproxy"
    proxy = ray_tpu.get_actor("serve-proxy")

    def chain_state():
        return ray_tpu.get(proxy.chain_status.remote("soak-proxy"),
                           timeout=30)

    # one request primes the router; then wait for the chain to go live
    urllib.request.urlopen(urllib.request.Request(
        url, data=b'{"x": 0}',
        headers={"Content-Type": "application/json"}), timeout=60).read()
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        st = chain_state()
        if st.get("live"):
            break
        time.sleep(0.25)
    else:
        raise AssertionError(f"proxy chain never went live: {st}")
    gen0 = st["generation"]

    codes, lats, pids = [], [], []
    lock = threading.Lock()
    stop = time.monotonic() + duration_s

    def client():
        while time.monotonic() < stop:
            t0 = time.perf_counter()
            pid = None
            try:
                req = urllib.request.Request(
                    url, data=b'{"x": 1}',
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    pid = _json.loads(r.read()).get("pid")
                    code = r.status
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception:
                code = -1
            with lock:
                codes.append(code)
                if code == 200:
                    lats.append(time.perf_counter() - t0)
                    pids.append(pid)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s / 3)
    # chaos-inject the replica kill mid-load (the dynamic handle routes
    # the arm call to ONE of the two spread replicas; it SIGKILLs itself
    # on its next outbound telemetry push)
    assert handle.arm_chaos.remote(
        f"seed={seed},kill:*:n=1").result(timeout=30) is True
    for t in threads:
        t.join(duration_s + 120)
    elapsed = time.perf_counter() - t_start
    # lanes must recompile over the replacement replica
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        st = chain_state()
        if st.get("live") and st["generation"] > gen0:
            break
        time.sleep(0.5)
    stats = dict(st.get("stats") or {})
    served = sum(1 for c in codes if c == 200)
    shed = sum(1 for c in codes if c == 429)
    failed = len(codes) - served - shed
    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    assert failed == 0, f"{failed} non-shed failures (codes={set(codes)})"
    assert served > 0
    assert st.get("live") and st["generation"] > gen0, \
        f"proxy chain never recompiled after the kill: {st}"
    assert stats.get("compiled", 0) > 0, \
        f"no requests rode the compiled path: {stats}"
    return {"duration_s": round(elapsed, 2), "served": served,
            "shed": shed, "failed": failed,
            "rps": round(served / elapsed, 1),
            "p99_s": round(float(np.percentile(lats, 99)), 4),
            "replicas_seen": len(set(pids)),
            "generations": [gen0, st["generation"]],
            "compiled": stats.get("compiled"),
            "dynamic_fallback": stats.get("dynamic_fallback"),
            "chaos": f"seed={seed},kill:*:n=1 (replica self-kill)"}


def shuffle_kill_soak(seed: int, P: int = 4) -> dict:
    """Kill-a-shuffle-node phase (ISSUE 15): an isolation-mode cluster
    lands every map sub-block of a distributed hash shuffle on one node,
    that node is SIGKILLed before the reduce stage consumes them, and the
    shuffle must complete byte-identical to the in-process reference
    through lineage reconstruction of exactly the lost map tasks on a
    replacement node."""
    import numpy as np
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.data import shuffle as shf

    n_blocks = 4
    saved = os.environ.get("RAY_TPU_STORE_ISOLATION")
    os.environ["RAY_TPU_STORE_ISOLATION"] = "1"
    cluster = Cluster(num_cpus=0)
    node_a = cluster.add_node(num_cpus=2, resources={"nodeA": 4})
    cluster.add_node(num_cpus=2, resources={"nodeB": 4})
    try:
        cluster.connect()
        cluster.wait_for_nodes(3)
        rng = np.random.default_rng(seed)
        blocks = [{"k": np.arange(1600, dtype=np.int64) + 1600 * i,
                   "x": rng.random((1600, 64))} for i in range(n_blocks)]
        parts = [shf._map_partition(b, [], P, "hash", "k", None, None)
                 for b in blocks]
        expected = [shf._reduce_concat(*[pp[p] for pp in parts])
                    for p in range(P)]
        map_task = ray_tpu.remote(shf._map_partition).options(
            num_returns=P, name="data_shuffle_map", data_stage=True,
            resources={"nodeA": 1})
        reducer = ray_tpu.remote(shf._reduce_concat).options(
            name="data_shuffle_reduce", lineage=True, data_stage=True,
            resources={"nodeB": 1})
        refs = [map_task.remote(b, [], P, "hash", "k", None, None)
                for b in blocks]
        flat = [r for rs in refs for r in rs]
        ready, _ = ray_tpu.wait(flat, num_returns=len(flat), timeout=120)
        assert len(ready) == len(flat), "map stage never completed"
        cluster.kill_node(node_a)
        t0 = time.perf_counter()
        cluster.add_node(num_cpus=2, resources={"nodeA": 4})
        out = [reducer.remote(*[refs[m][p] for m in range(n_blocks)])
               for p in range(P)]
        got = ray_tpu.get(out, timeout=240)
        recovery_s = time.perf_counter() - t0
        for g, e in zip(got, expected):
            for col in e:
                assert np.array_equal(np.asarray(g[col]),
                                      np.asarray(e[col])), \
                    f"column {col} diverged after reconstruction"
        from ray_tpu.util import state

        recon = 0
        deadline = time.time() + 20
        while time.time() < deadline:
            recon = next((row.get("data_reconstructs", 0)
                          for row in state.list_scheduler_stats()
                          if row.get("is_head")), 0)
            if recon >= n_blocks * P:
                break
            time.sleep(0.2)
        assert recon > 0, "no lineage reconstruction recorded"
        return {"partitions": P, "sub_blocks_lost": n_blocks * P,
                "sub_blocks_reconstructed": recon,
                "recovery_s": round(recovery_s, 2)}
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
        if saved is None:
            os.environ.pop("RAY_TPU_STORE_ISOLATION", None)
        else:
            os.environ["RAY_TPU_STORE_ISOLATION"] = saved


def _elastic_train_loop(config):
    """Tiny GPT-2 DDP loop for the elastic-recovery soak: per-worker
    2-device mesh, cross-worker kv-collective grad sync, sharded
    checkpoint every step (the restore path reshards it to whatever world
    size survives)."""
    import json
    import os as _os
    import tempfile
    import time as _t

    from ray_tpu.utils.platform import ensure_virtual_cpu

    ensure_virtual_cpu(2)
    import jax
    import numpy as _np

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train import Checkpoint
    from ray_tpu.train.spmd import (compile_gpt2_train,
                                    cross_worker_grad_sync,
                                    default_optimizer, restore_state_sharded,
                                    save_state_sharded)
    from ray_tpu.util import collective

    ctx = train.get_context()
    world, rank, gen = (ctx.get_world_size(), ctx.get_world_rank(),
                        ctx.get_generation())
    mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", vocab_size=128, max_seq_len=16,
                                 n_layer=1, n_head=2, d_model=32, d_ff=64)
    prog = compile_gpt2_train(
        cfg, mesh, optimizer=default_optimizer(lr=1e-2, warmup=1,
                                               total_steps=config["steps"]))
    ck = ctx.get_checkpoint()
    if ck is not None:
        state = restore_state_sharded(ck.as_directory(), prog)
        start = int(state.step)
    else:
        state = prog.init_fn(jax.random.key(0))
        start = 0
    group = None
    if world > 1:
        group = f"ddp:{config['run']}:g{gen}"
        collective.rebuild_collective_group(world, rank, backend="kv",
                                            group_name=group)
    rng = _np.random.default_rng(rank)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (4, 17), dtype=_np.int32),
        prog.batch_sharding)
    for step in range(start, config["steps"]):
        loss, grads = prog.grad_fn(state, {"tokens": tokens})
        if world > 1:
            grads = cross_worker_grad_sync(grads, group, world)
        state = prog.apply_fn(state, grads)
        ckpt = None
        if rank == 0:
            d = tempfile.mkdtemp(prefix="bench_ckpt_")
            save_state_sharded(state, d, world_size=world)
            ckpt = Checkpoint(d)
            with open(config["history"], "a") as f:
                f.write(json.dumps({"gen": gen, "step": step,
                                    "world": world, "loss": float(loss),
                                    "ts": _t.time()}) + "\n")
        train.report({"loss": float(loss), "step": step, "world": world},
                     checkpoint=ckpt)
        _t.sleep(config.get("step_s", 0.0))


def read_jsonl_history(path: str) -> list:
    """History lines appended by another process: tolerate a torn
    trailing line mid-append instead of crashing the caller."""
    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def elastic_train_drill(seed: int, steps: int = 30) -> dict:
    """The tentpole acceptance drill as a soak phase: a 2-worker
    GPT-2-DDP run on a head + 2 one-CPU nodes; once the gang makes
    progress the chaos plane delivers the kill — `set_node_chaos` arms a
    seeded `kill:*:n=1` plan, so the victim daemon SIGKILLs ITSELF on its
    next outbound control-plane call (a chaos-injected kill, not a
    harness kill). The drill asserts the controller shrinks to world
    size 1, restores the resharded checkpoint, and FINISHES covering
    every step. Returns {recovery_s, restarts, final_world_size, steps}."""
    import tempfile
    import threading

    import ray_tpu
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import (ElasticConfig, FailureConfig, RunConfig,
                               ScalingConfig)
    from ray_tpu.train.controller import TrainControllerLogic

    run_name = f"soak{seed}"
    storage = tempfile.mkdtemp(prefix=f"{run_name}_")
    history = os.path.join(storage, "history.jsonl")
    cluster = Cluster(num_cpus=0)
    nids = [cluster.add_node(num_cpus=1), cluster.add_node(num_cpus=1)]
    try:
        cluster.connect()
        cluster.wait_for_nodes(3)
        client = ray_tpu.core.api._global_client()
        logic = TrainControllerLogic(
            _elastic_train_loop,
            {"steps": steps, "run": run_name, "history": history,
             "step_s": 0.1},
            ScalingConfig(num_workers=2, min_workers=1,
                          resources_per_worker={"CPU": 1},
                          elastic=ElasticConfig(regrow=False,
                                                schedule_wait_s=30.0)),
            RunConfig(name=run_name, storage_path=storage,
                      failure_config=FailureConfig(max_failures=2)))
        box = {}

        def _run():
            try:
                box["result"] = logic.run()
            except BaseException as e:
                box["error"] = e

        t = threading.Thread(target=_run, daemon=True)
        t.start()
        deadline = time.time() + 180
        while time.time() < deadline:
            if any(e["world"] == 2 and e["step"] >= 3
                   for e in read_jsonl_history(history)):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("2-worker run never made progress")
        t_kill = time.time()
        assert client.head_request(
            "set_node_chaos", node_id=bytes.fromhex(nids[1]),
            spec=f"seed={seed},kill:*:n=1") is True
        deadline = time.time() + 180
        first_post = None
        while time.time() < deadline:
            post = [e for e in read_jsonl_history(history)
                    if e["gen"] >= 1]
            if post:
                first_post = post[0]
                break
            time.sleep(0.05)
        assert first_post is not None, "never recovered after daemon kill"
        t.join(timeout=240)
        assert not t.is_alive(), "controller never finished"
        if "error" in box:
            raise box["error"]
        result = box["result"]
        assert result["state"] == "FINISHED", result["error"]
        assert result["final_world_size"] == 1, result
        entries = read_jsonl_history(history)
        assert {e["step"] for e in entries} == set(range(steps))
        return {"recovery_s": round(first_post["ts"] - t_kill, 2),
                "restarts": result["restarts"],
                "final_world_size": result["final_world_size"],
                "steps": steps}
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def main(seed: int = 7, out: str | None = None, rounds: int = 6,
         steps: int = 30) -> dict:
    report = {"seed": seed}
    print(f"[soak] warm burst under chaos (seed={seed})", file=sys.stderr)
    report["warm_burst"] = warm_burst_soak(seed, rounds=rounds)
    print(f"[soak] head-paused burst via peer spillback (seed={seed})",
          file=sys.stderr)
    report["head_paused"] = head_paused_burst(seed)
    print(f"[soak] large-object data plane under chaos (seed={seed})",
          file=sys.stderr)
    report["large_object"] = large_object_soak(seed)
    print(f"[soak] shuffle node kill mid-shuffle (seed={seed})",
          file=sys.stderr)
    report["shuffle_kill"] = shuffle_kill_soak(seed)
    print(f"[soak] serve plane under replica chaos kill (seed={seed})",
          file=sys.stderr)
    report["serve"] = serve_soak(seed)
    print(f"[soak] cold-model burst on a scaled-to-zero tenant "
          f"(seed={seed})", file=sys.stderr)
    report["cold_model_burst"] = cold_model_burst_soak(seed)
    print(f"[soak] compiled chain under replica chaos kill (seed={seed})",
          file=sys.stderr)
    report["compiled_chain"] = compiled_chain_soak(seed)
    print(f"[soak] external HTTP over compiled ingress under replica "
          f"chaos kill (seed={seed})", file=sys.stderr)
    report["proxy_compiled"] = proxy_compiled_soak(seed)
    print(f"[soak] elastic train drill (seed={seed})", file=sys.stderr)
    report["elastic_train"] = elastic_train_drill(seed, steps=steps)
    print(json.dumps(report, indent=2))
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
    return report


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--out", default=None)
    p.add_argument("--rounds", type=int, default=6)
    p.add_argument("--steps", type=int, default=30)
    a = p.parse_args()
    main(seed=a.seed, out=a.out, rounds=a.rounds, steps=a.steps)
