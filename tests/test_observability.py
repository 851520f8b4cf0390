"""State API, task events, metrics, dashboard, timeline tests.

Mirrors the reference's state-API tests (`python/ray/tests/test_state_api*.py`)
and metrics export path (`dashboard/modules/metrics`).
"""

import json
import time
import urllib.request

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=4, num_tpu_chips=0, max_workers=8)
    yield info
    ray_tpu.shutdown()


@ray_tpu.remote
def _work(x):
    time.sleep(0.05)
    return x + 1


@ray_tpu.remote
def _boom():
    raise ValueError("boom")


@ray_tpu.remote
class _Counter:
    def __init__(self):
        self.n = 0

    def incr(self):
        self.n += 1
        return self.n


def test_list_and_task_events(cluster):
    from ray_tpu.util import state

    refs = [_work.remote(i) for i in range(4)]
    assert ray_tpu.get(refs) == [1, 2, 3, 4]
    events = state.list_task_events()
    states = {e["state"] for e in events}
    assert "RUNNING" in states and "FINISHED" in states
    finished = [e for e in events if e["state"] == "FINISHED"]
    assert all(e["worker_id"] for e in finished)

    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["is_head"]
    workers = state.list_workers()
    assert len(workers) >= 1


def test_failed_task_event(cluster):
    from ray_tpu.util import state

    ref = _boom.remote()
    with pytest.raises(Exception):
        ray_tpu.get(ref)
    # user exceptions are FINISHED (task ran; error is in the object) —
    # FAILED is reserved for system failures. Just check the event exists.
    evs = state.list_task_events(filters=[("name", "=", "_boom")])
    assert evs


def test_state_filters_and_summary(cluster):
    from ray_tpu.util import state

    h = _Counter.remote()
    assert ray_tpu.get(h.incr.remote()) == 1
    actors = state.list_actors(filters=[("state", "=", "ALIVE")])
    assert any(a["actor_id"] == h._actor_id.hex() for a in actors)
    s = state.summarize_actors()
    assert s["by_state"].get("ALIVE", 0) >= 1
    ts = state.summarize_tasks()
    assert ts["total"] >= 4
    with pytest.raises(ValueError):
        state.list_actors(filters=[("state", ">", "ALIVE")])
    ray_tpu.kill(h)


def test_metrics_registry_and_prometheus():
    from ray_tpu.util import metrics as m

    c = m.Counter("test_requests", "total requests", tag_keys=("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    g = m.Gauge("test_inflight", "in flight", tag_keys=())
    g.set(7)
    h = m.Histogram("test_latency", "latency", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    snap = {s["name"]: s for s in m.snapshot_all()}
    assert snap["test_requests"]["series"][0]["value"] == 3.0
    assert snap["test_inflight"]["series"][0]["value"] == 7.0
    hs = snap["test_latency"]["series"][0]["histogram"]
    assert hs["count"] == 3 and hs["buckets"] == [1, 1, 1]

    text = m.render_prometheus({"p0": m.snapshot_all()})
    assert 'ray_tpu_test_requests{proc="p0",route="/a"} 3.0' in text
    assert "# TYPE ray_tpu_test_latency histogram" in text
    assert 'le="+Inf"' in text

    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        c.inc(tags={"bad_key": "x"})


def test_metrics_flush_to_head(cluster):
    from ray_tpu.util import metrics as m

    g = m.Gauge("test_pushed", "pushed gauge")
    g.set(42)
    assert m.flush()
    client = ray_tpu.core.api._global_client()
    raw = client.head_request("kv_get", ns="_metrics",
                              key=f"proc:{client.worker_id.hex()}".encode())
    names = [x["name"] for x in json.loads(raw)]
    assert "test_pushed" in names


def test_dashboard_http(cluster):
    info = ray_tpu.core.api._global_client().head_request("cluster_info")
    port = info["dashboard_port"]
    assert port, "dashboard did not start"

    def fetch(path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=10) as r:
            return r.read().decode()

    cluster_json = json.loads(fetch("/api/cluster"))
    assert cluster_json["num_nodes"] == 1
    nodes = json.loads(fetch("/api/nodes"))
    assert nodes[0]["is_head"]
    summary = json.loads(fetch("/api/summary"))
    assert summary["tasks"]["total"] >= 1
    from ray_tpu.util import metrics as m

    m.Gauge("test_dash", "x").set(1)
    m.flush()
    text = fetch("/metrics")
    assert "ray_tpu_test_dash" in text
    html = fetch("/")
    assert "ray_tpu" in html


def test_timeline(cluster, tmp_path):
    ray_tpu.get([_work.remote(i) for i in range(3)])
    out = tmp_path / "trace.json"
    events = ray_tpu.timeline(str(out))
    complete = [e for e in events if e["ph"] == "X"]
    assert complete and all(e["dur"] > 0 for e in complete)
    assert json.load(open(out))


def test_reporter_stats_and_stacks(cluster):
    """Dashboard reporter analog (reference dashboard/modules/reporter):
    per-process RSS/CPU/thread stats + cooperative py-spy stack dumps."""
    import time

    import ray_tpu

    @ray_tpu.remote
    class Busy:
        def spin_marker_method(self, t):
            time.sleep(t)
            return 1

    a = Busy.remote()
    ray_tpu.get(a.spin_marker_method.remote(0.0), timeout=60)
    from ray_tpu.core.api import _global_client

    c = _global_client()
    rows = c.head_request("reporter_stats")
    live = [r for r in rows if r["alive"] and not r["is_driver"]]
    assert live, rows
    assert all(r["rss_bytes"] > 1 << 20 for r in live)   # real RSS
    assert all(r["num_threads"] >= 1 for r in live)

    # stack dump of the actor's worker while a method sleeps shows the
    # method frame (the py-spy use case: where is this worker stuck?)
    ref = a.spin_marker_method.remote(3.0)
    time.sleep(0.5)
    actor_row = next(r for r in rows if r["actor"])
    text = c.head_request("worker_stacks",
                          worker_id=bytes.fromhex(actor_row["worker_id"]))
    assert text and "spin_marker_method" in text, text[:500]
    assert ray_tpu.get(ref, timeout=60) == 1
    ray_tpu.kill(a)


def test_pubsub_public_subscribe(cluster):
    """Public pubsub surface: node/actor/object state events reach
    subscribers (reference src/ray/pubsub channels)."""
    import numpy as np

    from ray_tpu.util import state

    obj_q = state.subscribe("object_state")
    actor_q = state.subscribe("actor_state")

    ref = ray_tpu.put(np.zeros(200_000, np.uint8))  # > inline threshold
    evt = obj_q.get(timeout=15)
    assert evt["state"] == "SEALED" and evt["size"] > 0

    @ray_tpu.remote
    class A:
        def hi(self):
            return "hi"

    a = A.remote()
    assert ray_tpu.get(a.hi.remote()) == "hi"
    deadline = time.time() + 15
    states = []
    while time.time() < deadline:
        try:
            states.append(actor_q.get(timeout=1)["state"])
        except Exception:
            pass
        if "ALIVE" in states:
            break
    assert "ALIVE" in states, states

    # eviction event when the ref is dropped (zero-grace refcounting)
    del ref
    deadline = time.time() + 20
    got_evict = False
    while time.time() < deadline and not got_evict:
        try:
            got_evict = obj_q.get(timeout=1)["state"] == "EVICTED"
        except Exception:
            pass
    assert got_evict, "eviction event never published"
    ray_tpu.kill(a)


def test_render_prometheus_family_grouping():
    """Exposition format: ALL samples of a metric family must sit under a
    single # TYPE block — the pre-fix renderer iterated per-process and
    re-interleaved families, which strict Prometheus parsers reject."""
    from ray_tpu.util import metrics as m

    def snap(val):
        return [{"name": "fam_x", "kind": "counter", "description": "x",
                 "series": [{"tags": {}, "value": val}]},
                {"name": "fam_y", "kind": "gauge", "description": "y",
                 "series": [{"tags": {}, "value": val}]}]

    text = m.render_prometheus({"p0": snap(1.0), "p1": snap(2.0)})
    assert text.count("# TYPE ray_tpu_fam_x counter") == 1
    assert text.count("# TYPE ray_tpu_fam_y gauge") == 1
    lines = text.splitlines()
    ix = lines.index("# TYPE ray_tpu_fam_x counter")
    block = []
    for line in lines[ix + 1:]:
        if line.startswith("#"):
            break
        block.append(line)
    # both processes' fam_x samples are contiguous inside the family block
    assert any('proc="p0"' in l for l in block), block
    assert any('proc="p1"' in l for l in block), block


def _warm_lease(client):
    deadline = time.time() + 30
    while time.time() < deadline and not client._leases:
        ray_tpu.get(_work.remote(0), timeout=30)
    assert client._leases, "lease never established"


def test_scheduler_observability_surface(cluster):
    """Flight recorder tentpole: lease grants show up in the merged
    state-API event stream, per-node scheduler stats, /api/scheduler and
    the new Prometheus series (incl. the protocol-interposer RPC latency
    histogram)."""
    from ray_tpu.util import state

    client = ray_tpu.core.api._global_client()
    _warm_lease(client)

    events = state.list_lease_events()
    assert any(e["kind"] == "head_grant" for e in events), events[-5:]
    rows = state.list_scheduler_stats()
    head_row = next(r for r in rows if r["is_head"])
    assert head_row["head_grants"] >= 1
    assert head_row["staleness_s"] == 0.0

    from ray_tpu.util import metrics as m

    assert m.flush()
    time.sleep(0.3)
    info = client.head_request("cluster_info")
    port = info["dashboard_port"]
    sched = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/api/scheduler", timeout=10).read())
    assert sched["stats"] and any(r["is_head"] for r in sched["stats"])
    assert any(e["kind"] == "head_grant" for e in sched["recent_events"])
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    for series in ("ray_tpu_lease_local_grants_total",
                   "ray_tpu_lease_spillbacks_total",
                   "ray_tpu_lease_head_grants_total",
                   "ray_tpu_cluster_view_staleness_s",
                   "ray_tpu_rpc_latency_seconds_bucket",
                   "ray_tpu_rpc_requests_total"):
        assert series in body, f"missing {series}\n{body[:800]}"
    # exposition stays family-grouped with many processes reporting
    assert body.count("# TYPE ray_tpu_rpc_latency_seconds histogram") == 1


def test_metrics_kv_expires_on_worker_death(cluster):
    """Satellite regression: a dead worker's proc:<id> snapshot must leave
    the _metrics KV namespace (pre-fix it was scraped forever)."""
    import os

    @ray_tpu.remote(max_retries=0)
    def ident_and_flush():
        from ray_tpu.util import metrics as m

        import ray_tpu.core.api as api

        m.Gauge("test_fr_worker_alive", "probe").set(1.0)
        m.flush()
        c = api._global_client()
        return c.worker_id.hex(), os.getpid()

    wid, pid = ray_tpu.get(ident_and_flush.remote(), timeout=60)
    client = ray_tpu.core.api._global_client()
    key = f"proc:{wid}".encode()
    deadline = time.time() + 20
    while time.time() < deadline:
        if client.head_request("kv_get", ns="_metrics", key=key) is not None:
            break
        time.sleep(0.2)
    assert client.head_request("kv_get", ns="_metrics", key=key) is not None
    os.kill(pid, 9)
    deadline = time.time() + 30
    while time.time() < deadline:
        if client.head_request("kv_get", ns="_metrics", key=key) is None:
            break
        time.sleep(0.2)
    assert client.head_request("kv_get", ns="_metrics", key=key) is None, \
        "dead worker's metrics snapshot still scraped"


def test_timeline_scheduling_phases(cluster, tmp_path):
    """Tentpole acceptance: with tracing on, a task's timeline row shows
    submit → lease-acquire[mode] → dispatch → run as distinct sub-spans
    plus flow arrows keyed by task id."""
    from ray_tpu.core import config as _config
    from ray_tpu.util import tracing

    tracing.enable_tracing()
    try:
        _run_timeline_phase_checks(tmp_path, _config, tracing)
    finally:
        # leave the (process-global) tracer off for later test modules
        tracing._enabled = False


def _run_timeline_phase_checks(tmp_path, _config, tracing):
    client = ray_tpu.core.api._global_client()
    # leases warmed by earlier (untraced) tests must idle out so a fresh
    # acquisition — and its lease-acquire phase — happens under tracing
    deadline = time.time() + 30
    while time.time() < deadline and client._leases:
        time.sleep(float(_config.get("lease_idle_s")) / 2)
    _warm_lease(client)
    assert ray_tpu.get([_work.remote(i) for i in range(5)],
                       timeout=60) == [i + 1 for i in range(5)]
    out = tmp_path / "sched_trace.json"
    events = ray_tpu.timeline(str(out))
    sched = [e for e in events if e.get("cat") == "sched"]
    names = {e["name"] for e in sched if e["ph"] == "X"}
    assert any(n.startswith("lease-acquire[") for n in names), names
    assert {"submit", "dispatch", "run"} <= names, names
    # flow arrows: a start ("s") and an end ("f") bound to the same task
    flow_ids = {e["id"] for e in sched if e["ph"] == "s"}
    assert flow_ids & {e["id"] for e in sched if e["ph"] == "f"}
    # lease-acquire mode is one of the three defined grant paths
    acquires = [e for e in sched
                if e["ph"] == "X" and e["name"].startswith("lease-acquire")]
    assert all(e["args"]["mode"] in ("local", "spillback", "head")
               for e in acquires)
    assert json.load(open(out))
    # tracing spans recorded the acquisition too
    span_names = {s.name for s in tracing.get_finished_spans()}
    assert "lease_acquire" in span_names


def test_core_metrics_exported(cluster):
    """Head-computed core gauges reach /metrics (reference
    metric_defs.cc series behind the shipped Grafana dashboard)."""
    info = ray_tpu.core.api._global_client().head_request("cluster_info")
    port = info["dashboard_port"]

    @ray_tpu.remote
    class Holder:
        def ok(self):
            return True

    h = Holder.remote()
    assert ray_tpu.get(h.ok.remote())
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    for series in ("ray_tpu_nodes_alive", "ray_tpu_workers_total",
                   "ray_tpu_tasks_queued", "ray_tpu_resource_total",
                   "ray_tpu_actors{"):
        assert series in body, f"missing {series}\n{body[:800]}"
    assert 'state="ALIVE"' in body
    ray_tpu.kill(h)


def test_timeline_reconcile_and_train_phases(cluster, tmp_path):
    """`ray_tpu.timeline()` renders head-side reconciliation phases from
    the merged lease-event stream: train controller lifecycle spans (via
    the train_event RPC) and epoch/reconcile markers land on the
    head-reconcile row."""
    client = ray_tpu.core.api._global_client()
    t0 = time.time()
    # a span-shaped phase (t0/t1) and an instant one, as the controller
    # emits them
    assert client.head_request(
        "train_event", run="tl-run", phase="group_start",
        t0=t0, t1=t0 + 0.25,
        detail={"world": 2, "generation": 0}) is True
    assert client.head_request(
        "train_event", run="tl-run", phase="death_detected",
        detail={"cause": "drill"}) is True
    out = tmp_path / "trace.json"
    events = ray_tpu.timeline(str(out))
    train_rows = [e for e in events if e.get("cat") == "train"]
    assert {e["name"] for e in train_rows} >= {"train_group_start",
                                               "train_death_detected"}
    span = next(e for e in train_rows if e["name"] == "train_group_start")
    assert span["ph"] == "X" and span["pid"] == "head-reconcile"
    assert span["args"]["world"] == 2
    assert abs(span["dur"] - 0.25e6) < 1e3
    inst = next(e for e in train_rows if e["name"] == "train_death_detected")
    assert inst["ph"] == "i" and inst["args"]["cause"] == "drill"
    # the events also surface through the state API (flight recorder)
    from ray_tpu.util import state

    kinds = {e["kind"] for e in state.list_lease_events()}
    assert {"train_group_start", "train_death_detected"} <= kinds
    assert json.load(open(out))


def test_default_histogram_boundaries_start_sub_ms():
    """Warm-path RPC and span latencies sit well under 1 ms; the default
    buckets must resolve them instead of collapsing everything into the
    first bucket (satellite: sub-millisecond histogram boundaries)."""
    from ray_tpu.util import metrics

    b = metrics.DEFAULT_HISTOGRAM_BOUNDARIES
    assert b[:3] == [0.0001, 0.00025, 0.0005]
    assert 0.001 in b and 100.0 in b  # legacy boundaries kept compatible
    h = metrics.Histogram("test_subms_hist", "t")
    h.observe(0.0002)
    h.observe(0.0004)
    snap = h._snapshot()[0]
    # the two observations land in DIFFERENT buckets now
    assert snap["histogram"]["buckets"][1] == 1
    assert snap["histogram"]["buckets"][2] == 1


def test_push_payload_reserved_families_skip_prometheus():
    """Workload rows and drained spans ride the metrics push as reserved
    `__`-prefixed families; the Prometheus renderer must not leak them
    as (invalid) metric families."""
    from ray_tpu.util import metrics, tracing

    metrics.Counter("test_payload_counter", "t").inc()
    metrics.publish_workload("serve_replica", "r#1", {"queue_depth": 3})
    tracing.enable_tracing()
    with tracing.start_span("payload-span"):
        pass
    payload = metrics.push_payload()
    names = {m["name"] for m in payload}
    assert "__workloads__" in names and "__spans__" in names
    wl = next(m for m in payload if m["name"] == "__workloads__")
    # by key: other tests of this worker process may have published rows
    row = next(r for r in wl["series"] if r["key"] == "r#1")
    assert row["stats"]["queue_depth"] == 3
    text = metrics.render_prometheus({"p1": payload})
    assert "__workloads__" not in text and "__spans__" not in text
    assert "test_payload_counter" in text
    # spans drain exactly once per push
    assert not any(m["name"] == "__spans__"
                   for m in metrics.push_payload())


def test_workload_watchdog_scan_policies():
    """Pure-policy unit for the head's anomaly pass: straggler outliers
    (median_low so a 2-gang can flag), slow pulls delta-counted from
    histogram buckets, p99-over-SLO routes, and re-flag rate limiting."""
    from ray_tpu.core import workload_watchdog as wd

    now = 1000.0

    def train_row(rank, ewma, run="r1"):
        return {"kind": "train_worker", "key": f"{run}:rank{rank}",
                "ts": now - 1,
                "stats": {"run": run, "rank": rank, "ewma_step_s": ewma}}

    rows = [train_row(0, 0.05), train_row(1, 0.5)]
    anomalies, state = wd.scan(rows, {}, now, slow_pull_s=5.0,
                               straggler_factor=2.0, p99_slo_s=0.0)
    assert [a["anomaly"] for a in anomalies] == ["train_straggler"]
    assert anomalies[0]["rank"] == 1

    # re-flag rate limit: the same straggler is not flagged again within
    # the interval, and IS after it
    again, state = wd.scan(rows, {}, now + 5, slow_pull_s=5.0,
                           straggler_factor=2.0, p99_slo_s=0.0, state=state)
    assert not again
    t_later = now + wd.REFLAG_INTERVAL_S + 6
    fresh_rows = [dict(r, ts=t_later - 1) for r in rows]
    later, state = wd.scan(fresh_rows, {}, t_later,
                           slow_pull_s=5.0, straggler_factor=2.0,
                           p99_slo_s=0.0, state=state)
    assert len(later) == 1

    # stale rows are never judged
    stale = [dict(r, ts=now - 2 * wd.FRESH_S) for r in rows]
    none, _ = wd.scan(stale, {}, now, slow_pull_s=5.0,
                      straggler_factor=2.0, p99_slo_s=0.0)
    assert not none

    # slow pulls: delta-counted from histogram buckets above threshold.
    # A FRESH state's first pass only baselines (a restarted head must
    # not re-flag the workers' whole cumulative history)...
    hist = {"tags": {"role": "node"},
            "boundaries": [1.0, 5.0, 10.0],
            "histogram": {"buckets": [4, 0, 2, 1], "sum": 40.0,
                          "count": 7}}
    anomalies, pstate = wd.scan([], {"object_pull_seconds": [("p", hist)]},
                                now, slow_pull_s=5.0, straggler_factor=2.0,
                                p99_slo_s=0.0)
    assert not anomalies  # baseline pass
    # ...a NEW slow pull after the baseline flags with its exact delta
    hist2 = {**hist, "histogram": {"buckets": [4, 0, 3, 1], "sum": 48.0,
                                   "count": 8}}
    more, pstate = wd.scan([], {"object_pull_seconds": [("p", hist2)]},
                           now + 1, slow_pull_s=5.0, straggler_factor=2.0,
                           p99_slo_s=0.0, state=pstate)
    assert len(more) == 1 and more[0]["count"] == 1
    assert more[0]["anomaly"] == "slow_pull"
    # unchanged counts on the next pass -> no re-flag
    again, pstate = wd.scan([], {"object_pull_seconds": [("p", hist2)]},
                            now + 2, slow_pull_s=5.0, straggler_factor=2.0,
                            p99_slo_s=0.0, state=pstate)
    assert not again

    # p99-over-SLO route: judged over the WINDOW between passes (a
    # recovered route must not keep flagging on cumulative counts), and
    # only when the SLO is configured
    def route_hist(slow_count, fast_count):
        return {"tags": {"route": "/slow", "code": "200"},
                "boundaries": [0.1, 0.5, 2.0],
                "histogram": {"buckets": [fast_count, 0, slow_count, 0],
                              "sum": 0.0,
                              "count": slow_count + fast_count}}

    fams0 = {"serve_request_seconds": [("p", route_hist(0, 0))]}
    fams1 = {"serve_request_seconds": [("p", route_hist(100, 0))]}
    off, _ = wd.scan([], fams1, now, slow_pull_s=5.0, straggler_factor=2.0,
                     p99_slo_s=0.0)
    assert not off  # SLO disabled
    _, rstate = wd.scan([], fams0, now, slow_pull_s=5.0,
                        straggler_factor=2.0, p99_slo_s=1.0)
    on, rstate = wd.scan([], fams1, now + 1, slow_pull_s=5.0,
                         straggler_factor=2.0, p99_slo_s=1.0, state=rstate)
    assert [a["anomaly"] for a in on] == ["slo_route"]
    assert on[0]["route"] == "/slow" and on[0]["p99_s"] == 2.0
    assert on[0]["window_requests"] == 100
    # the route recovers: later windows are fast (or empty) -> no
    # re-flag even though the cumulative buckets still hold the burst
    fams2 = {"serve_request_seconds": [("p", route_hist(100, 1000))]}
    rec, rstate = wd.scan([], fams2,
                          now + 2 * wd.REFLAG_INTERVAL_S, slow_pull_s=5.0,
                          straggler_factor=2.0, p99_slo_s=1.0, state=rstate)
    assert not rec


def test_workload_watchdog_hotpath_regression_policies():
    """Pure-policy unit for the hot-path regression watch: compiled-chain
    p99 and ring stall ratio judged against their own rolling EWMA
    baselines (warm-up, floor, freeze-while-regressed), re-flag rate
    limiting, and hotpath_drift=0 backward compatibility."""
    from ray_tpu.core import workload_watchdog as wd

    now = 2000.0
    kw = dict(slow_pull_s=5.0, straggler_factor=2.0, p99_slo_s=0.0,
              hotpath_drift=1.5)

    def chain_row(p99, ts):
        return {"kind": "serve_chain", "key": "pre+main", "ts": ts,
                "stats": {"generation": 1, "p99_s": p99}}

    def ring_row(cum_stall, ts):
        return {"kind": "hotpath", "key": "serve_chain:pre+main", "ts": ts,
                "stats": {"plane": "serve_chain", "occupancy": 1.0,
                          "writer_stall_s": cum_stall,
                          "reader_stall_s": 0.0}}

    # warm the baselines: 4 healthy passes (chain p99 steady at 0.30s,
    # the ring stalling 0.01 s per wall second — under the 0.05 floor)
    state = None
    for i in range(4):
        t = now + i
        anomalies, state = wd.scan(
            [chain_row(0.30, t - 0.1), ring_row(0.01 * i, t - 0.1)],
            {}, t, state=state, **kw)
        assert not anomalies, anomalies

    # regression pass: p99 trebles and the ring spends 90% of the wall
    # window stalled -> both flagged against their OWN baselines
    t = now + 4
    anomalies, state = wd.scan(
        [chain_row(0.95, t - 0.1), ring_row(0.03 + 0.9, t - 0.1)],
        {}, t, state=state, **kw)
    by_metric = {a["metric"]: a for a in anomalies}
    assert set(by_metric) == {"chain_p99_s", "ring_stall_ratio"}
    assert all(a["anomaly"] == "hotpath_regression"
               for a in anomalies)
    assert by_metric["chain_p99_s"]["chain"] == "pre+main"
    assert by_metric["chain_p99_s"]["baseline"] == pytest.approx(0.30)
    assert by_metric["ring_stall_ratio"]["value"] == pytest.approx(0.9)

    # re-flag rate limit: the still-regressed next pass is silent...
    again, state = wd.scan(
        [chain_row(0.95, t + 0.9), ring_row(0.93 + 0.9, t + 0.9)],
        {}, t + 1, state=state, **kw)
    assert not again
    # ...but after the interval the SAME sustained regression flags
    # again — still judged against the FROZEN healthy baseline (updating
    # it would absorb the regression and silence the next pass)
    t2 = t + wd.REFLAG_INTERVAL_S + 2
    later, state = wd.scan([chain_row(0.95, t2 - 0.1)], {}, t2,
                           state=state, **kw)
    assert [a["metric"] for a in later] == ["chain_p99_s"]
    assert later[0]["baseline"] == pytest.approx(0.30)

    # hotpath_drift left at its 0 default -> the watch is off entirely
    off, _ = wd.scan([chain_row(9.9, now - 0.1)], {}, now,
                     slow_pull_s=5.0, straggler_factor=2.0, p99_slo_s=0.0)
    assert not off


def test_workload_watchdog_flags_fused_phase_straggler():
    """A synthetic fused-step phase straggler: rank 3's step time blows
    past the gang median and the watchdog names the guilty PHASE (its
    inter-host allreduce), not just the rank."""
    from ray_tpu.core import workload_watchdog as wd

    now = 3000.0

    def phase_row(rank, step, compute, ar):
        return {"kind": "train_phase", "key": f"run1:{rank}", "ts": now - 1,
                "stats": {"rank": rank, "step_s": step,
                          "compute_s": compute, "rs_s": 0.01,
                          "ar_s": ar, "ag_s": 0.01, "apply_s": 0.01}}

    rows = [phase_row(0, 0.10, 0.05, 0.02),
            phase_row(1, 0.11, 0.05, 0.02),
            phase_row(2, 0.10, 0.05, 0.02),
            phase_row(3, 1.20, 0.20, 0.95)]
    anomalies, _ = wd.scan(rows, {}, now, slow_pull_s=5.0,
                           straggler_factor=2.0, p99_slo_s=0.0,
                           hotpath_drift=1.5)
    assert [a["anomaly"] for a in anomalies] == ["hotpath_regression"]
    a = anomalies[0]
    assert a["metric"] == "train_phase_step_s"
    assert a["rank"] == 3 and a["run"] == "run1"
    assert a["phase"] == "ar"       # slowest-vs-median phase named
    assert a["gang_median_s"] == pytest.approx(0.10)


def test_workload_rows_and_serve_stats_surface(cluster):
    """publish_workload rows reach state.list_workload_stats (and the
    serve-scoped list_serve_stats view) via the ordinary metrics push."""
    from ray_tpu.util import metrics, state

    metrics.publish_workload("serve_replica", "obs#1",
                             {"deployment": "obs", "queue_depth": 2,
                              "inflight": 1, "ewma_latency_s": 0.01})
    metrics.publish_workload("custom_kind", "k1", {"x": 1})
    assert metrics.flush()
    deadline = time.time() + 15
    rows = []
    while time.time() < deadline:
        rows = state.list_workload_stats()
        if {"obs#1", "k1"} <= {r["key"] for r in rows}:
            break
        time.sleep(0.3)
    keys = {r["key"] for r in rows}
    assert {"obs#1", "k1"} <= keys, keys
    serve_rows = state.list_serve_stats()
    serve_keys = {r["key"] for r in serve_rows}
    assert "obs#1" in serve_keys and "k1" not in serve_keys
    row = next(r for r in serve_rows if r["key"] == "obs#1")
    assert row["stats"]["queue_depth"] == 2 and row["ts"] > 0
