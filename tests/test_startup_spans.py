"""The start-up record: `tracing.startup_span` / `record_startup`, the spans
the runtime, the engine and the train path leave in
`<STATE_DIR>/<session>/logs/startup-<role>-<pid>.jsonl`, and JAX's compiles
by name (`utils/platform.watch_compiles`). All on the CPU backend."""

import glob
import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.util import tracing
from ray_tpu.utils import platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def record(monkeypatch, tmp_path):
    """A process that has recorded nothing yet, knows no session and does
    not trace, with its state directory under `tmp_path`."""
    monkeypatch.setattr(tracing, "_startup", [])
    monkeypatch.setattr(tracing, "_startup_lines", [])
    monkeypatch.setattr(tracing, "_startup_session", None)
    monkeypatch.setattr(tracing, "_startup_role", "process")
    monkeypatch.setattr(tracing, "_enabled", False)
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    monkeypatch.setattr(platform, "STATE_DIR", str(tmp_path))
    return tmp_path


def _lines(path) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f]


# ------------------------------------------------------------- the record

def test_startup_span_records_with_tracing_off(record):
    assert not tracing.is_recording()
    with tracing.start_span("ordinary") as ordinary:
        assert ordinary is None
    with tracing.startup_span("stage", worker_id="w1") as span:
        time.sleep(0.01)
    (kept,) = tracing.startup_spans()
    assert kept is span and kept.name == "stage"
    assert kept.duration_s >= 0.01 and kept.parent_id is None
    assert kept.attributes == {"worker_id": "w1", "role": "process",
                               "pid": os.getpid()}


def test_startup_spans_nest_and_explicit_times_do_not_become_current(record):
    with tracing.startup_span("outer") as outer:
        done = tracing.record_startup("crossed", 10.0, 12.5, actor_id="a")
        with tracing.startup_span("inner") as inner:
            tracing.startup_attributes(rank=3)
        # an ordinary span opened under a start-up span records nothing:
        # start-up is not a trace context
        assert tracing.current_span() is None
    assert done.parent_id == inner.parent_id == outer.span_id
    assert done.trace_id == inner.trace_id == outer.trace_id
    assert (done.start_ts, done.end_ts) == (10.0, 12.5)
    assert inner.attributes["rank"] == 3 and "rank" not in outer.attributes
    assert [s.name for s in tracing.startup_spans()] == [
        "crossed", "inner", "outer"]
    tracing.startup_attributes(ignored=True)        # no span open: nothing


def test_startup_span_decorates_a_function_each_call_a_span(record):
    @tracing.startup_span("whole")
    def build(x):
        return x + 1

    assert build(1) == 2 and build(2) == 3
    first, second = tracing.startup_spans()
    assert first.name == second.name == "whole"
    assert first.span_id != second.span_id


def test_startup_record_is_bounded(record, monkeypatch):
    monkeypatch.setattr(tracing, "STARTUP_SPANS_MAX", 5)
    tracing.startup_identity("worker", "s0123456789ab")
    for i in range(9):
        tracing.record_startup(f"stage{i}", 1.0, 2.0)
    assert [s.name for s in tracing.startup_spans()] == [
        f"stage{i}" for i in range(5)]
    assert len(_lines(tracing.startup_file())) == 5


@pytest.mark.parametrize("traced", [False, True])
def test_startup_reaches_the_timeline_only_when_tracing_records(
        record, monkeypatch, traced):
    monkeypatch.setattr(tracing, "_finished", [])
    monkeypatch.setattr(tracing, "_push_queue", [])
    monkeypatch.setattr(tracing, "_enabled", traced)
    with tracing.startup_span("stage"):
        pass
    assert [s.name for s in tracing.get_finished_spans()] == (
        ["stage"] if traced else [])
    assert [d["name"] for d in tracing.drain_push_spans()] == (
        ["stage"] if traced else [])
    assert len(tracing.startup_spans()) == 1


def test_a_process_buffers_until_it_knows_its_session(record):
    tracing.record_startup("early", 1.0, 2.0)
    assert tracing.startup_file() is None
    assert not glob.glob(str(record / "*"))
    tracing.startup_identity("driver", "s0123456789ab")
    path = tracing.startup_file()
    assert path == str(record / "s0123456789ab" / "logs"
                       / f"startup-driver-{os.getpid()}.jsonl")
    tracing.record_startup("late", 3.0, 4.0)
    early, late = _lines(path)
    assert early["name"] == "early" and late["name"] == "late"
    # a line is `Span.to_dict()`
    assert set(late) == {"name", "trace_id", "span_id", "parent_id",
                         "start_ts", "end_ts", "attributes"}
    # a second session in the process starts the record anew
    tracing.startup_identity("driver", "sffffffffffff")
    assert tracing.startup_spans() == []
    tracing.record_startup("again", 5.0, 6.0)
    assert [d["name"] for d in _lines(tracing.startup_file())] == ["again"]
    assert len(_lines(path)) == 2


def test_process_start_is_before_now_and_after_boot():
    born = tracing.process_start_ts()
    assert born is not None and 0 < time.time() - born < 24 * 3600


# ---------------------------------------------------- a local cluster

def _startup_files(session: str) -> dict:
    """role -> the spans of every process of that role."""
    out: dict = {}
    root = os.path.join(platform.STATE_DIR, session, "logs")
    for path in glob.glob(os.path.join(root, "**", "startup-*.jsonl"),
                          recursive=True):
        role = os.path.basename(path).split("-")[1]
        out.setdefault(role, []).extend(_lines(path))
    return out


@ray_tpu.remote(num_tpu_chips=1)
class ChipHolder:
    def __init__(self):
        time.sleep(0.05)

    def pid(self):
        return os.getpid()


@pytest.fixture(scope="module")
def cluster_record():
    """One cluster with two described chips, an actor that asks for one, a
    deployment behind the proxy; then what every process wrote."""
    from ray_tpu import serve

    info = ray_tpu.init(num_cpus=4, num_tpu_chips=2, max_workers=8)
    holder = ChipHolder.remote()
    holder_pid = ray_tpu.get(holder.pid.remote(), timeout=60)

    @serve.deployment
    class Echo:
        def __call__(self, x):
            return x

    handle = serve.run(Echo.bind(), route_prefix="/echo")
    serve.start()
    assert handle.remote(3).result(timeout=60) == 3
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        files = _startup_files(info["session"])
        if any(s["name"] == "serve.deploy" for s in files.get("worker", [])):
            break
        time.sleep(0.1)
    serve.shutdown()
    ray_tpu.shutdown()
    return {"files": _startup_files(info["session"]),
            "holder_pid": holder_pid}


@pytest.mark.parametrize("role,name", [
    ("driver", "startup.init"), ("driver", "startup.head"),
    ("driver", "startup.connect"), ("driver", "serve.proxy_start"),
    ("head", "startup.node"), ("head", "sched.place"),
    ("head", "sched.spawn"), ("worker", "worker.boot"),
    ("worker", "worker.actor_init"), ("worker", "serve.deploy"),
    ("worker", "replica.init")])
def test_a_local_cluster_leaves_the_span(cluster_record, role, name):
    spans = [s for s in cluster_record["files"][role] if s["name"] == name]
    assert spans, f"no {name} in the {role}'s file"
    for s in spans:
        assert s["end_ts"] >= s["start_ts"] > 0
        assert s["attributes"]["role"] == role


def test_the_drivers_stages_are_children_of_init(cluster_record):
    by_name = {s["name"]: s for s in cluster_record["files"]["driver"]}
    init = by_name["startup.init"]
    for child in ("startup.head", "startup.connect"):
        assert by_name[child]["parent_id"] == init["span_id"]
        assert init["start_ts"] <= by_name[child]["start_ts"]
        assert by_name[child]["end_ts"] <= init["end_ts"]
    assert by_name["serve.proxy_start"]["parent_id"] is None


def test_worker_boot_lies_inside_its_sched_spawn(cluster_record):
    files, pid = cluster_record["files"], cluster_record["holder_pid"]
    (spawn,) = [s for s in files["head"] if s["name"] == "sched.spawn"
                and s["attributes"]["worker_pid"] == pid]
    (place,) = [s for s in files["head"] if s["name"] == "sched.place"
                and s["attributes"]["worker_pid"] == pid]
    (boot,) = [s for s in files["worker"] if s["name"] == "worker.boot"
               and s["attributes"]["pid"] == pid]
    (init,) = [s for s in files["worker"] if s["name"] == "worker.actor_init"
               and s["attributes"]["pid"] == pid]
    assert spawn["start_ts"] <= boot["attributes"]["proc_start_ts"] + 0.05
    assert spawn["start_ts"] <= boot["start_ts"]
    assert boot["end_ts"] <= spawn["end_ts"] + 0.05
    # the join keys: the head's spans name the actor and the worker
    assert place["attributes"]["chips"] == 1
    assert place["attributes"]["actor_id"] == init["attributes"]["actor_id"]
    assert place["attributes"]["worker_id"] == boot["attributes"]["worker_id"]
    assert init["attributes"]["actor_class"] == "ChipHolder"
    assert init["attributes"]["chips"] == 1
    assert place["end_ts"] <= init["start_ts"] + 0.05
    # the head's spans hang under none of its own (its server's tasks
    # inherit no open span from the head's start)
    assert place["parent_id"] is None and spawn["parent_id"] is None


def test_only_requests_for_chips_leave_scheduler_spans(cluster_record):
    places = [s for s in cluster_record["files"]["head"]
              if s["name"] == "sched.place"]
    assert len(places) == 1     # the controller, proxy and replica: none


def test_serve_deploy_covers_the_replicas_constructor(cluster_record):
    workers = cluster_record["files"]["worker"]
    (deploy,) = [s for s in workers if s["name"] == "serve.deploy"]
    (replica,) = [s for s in workers if s["name"] == "replica.init"]
    assert deploy["attributes"]["ready"] is True
    assert deploy["attributes"]["deployment"] == "Echo"
    assert replica["attributes"]["replica"] == deploy["attributes"]["replica"]
    assert deploy["start_ts"] <= replica["start_ts"]
    assert replica["end_ts"] <= deploy["end_ts"]


# ------------------------------------------------------ compiles by name

COMPILES = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu.util import metrics, tracing
from ray_tpu.utils.platform import watch_compiles

watch = watch_compiles()
assert all(watch_compiles() is watch for _ in range(3))
import jax._src.monitoring as m
registered = [len(m.get_event_listeners()),
              len(m.get_event_duration_listeners()),
              len(m.get_event_time_span_listeners())]

@jax.jit
def seeded_program(x):
    return jnp.tanh(x) @ x

seeded_program(jnp.ones((16, 16))).block_until_ready()
seeded_program(jnp.ones((16, 16))).block_until_ready()     # no new shape
(counter,) = [v for (name, _), v in metrics._REGISTRY.items()
              if name == "jax_compiles_total"]
print(json.dumps({
    "registered": registered, "count": watch.count, "last": watch.last,
    "spans": [s.to_dict() for s in tracing.startup_spans()],
    "counted": {f"{s['tags']['fun']}|{s['tags']['cache']}": s["value"]
                for s in counter._snapshot()}}))
"""


@pytest.fixture(scope="module")
def two_processes(tmp_path_factory):
    """The same program prepared by two processes that share a fresh
    cache directory."""
    cache = tmp_path_factory.mktemp("jax_cache")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "JAX_ENABLE_COMPILATION_CACHE": "1",
           "JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    out = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", COMPILES], env=env,
                           capture_output=True, text=True, timeout=180)
        assert p.returncode == 0, p.stderr[-2000:]
        out.append(json.loads(p.stdout.strip().splitlines()[-1]))
    return out


@pytest.mark.parametrize("process,cache", [(0, "miss"), (1, "hit")])
def test_watch_compiles_names_a_program_and_what_the_cache_did(
        two_processes, process, cache):
    said = two_processes[process]
    (span,) = [s for s in said["spans"]
               if s["name"] == "compile.seeded_program"]
    assert span["attributes"]["cache"] == cache
    assert span["attributes"]["trace_s"] > 0
    assert span["attributes"]["lower_s"] > 0
    assert span["end_ts"] > span["start_ts"]
    assert said["last"]["fun"] == "seeded_program"
    assert said["last"]["cache"] == cache
    assert said["last"]["seconds"] == pytest.approx(
        span["end_ts"] - span["start_ts"])
    assert said["counted"][f"seeded_program|{cache}"] == 1.0
    # every program has a span, and the count is theirs
    assert said["count"] == len(
        [s for s in said["spans"] if s["name"].startswith("compile.")])
    assert sum(said["counted"].values()) == said["count"]


def test_watch_compiles_registers_once_however_often_it_is_called(
        two_processes):
    assert two_processes[0]["registered"] == [1, 1, 1]


def test_a_listener_that_fails_does_not_fail_the_compile(monkeypatch):
    watch = platform.CompileWatch()
    monkeypatch.setattr(watch, "_compiled",
                        lambda *a: (_ for _ in ()).throw(RuntimeError("x")))
    watch.on_span(watch.COMPILE, 1.0, 2.0, fun_name="jit(f)")


def test_past_its_bound_a_watch_counts_and_keeps_no_span(record, monkeypatch):
    watch = platform.CompileWatch()
    monkeypatch.setattr(watch, "SPANS_MAX", 2)
    for i in range(4):
        watch.on_span(watch.COMPILE, 1.0, 2.0, fun_name=f"jit(f{i})")
    assert watch.count == 4 and watch.last["fun"] == "f3"
    assert [s.name for s in tracing.startup_spans()] == [
        "compile.f0", "compile.f1"]


# ------------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def engine_record():
    from ray_tpu.serve.llm import LLMEngine

    before = len(tracing.startup_spans())
    t0 = time.time()
    engine = LLMEngine(preset="gpt2-tiny", max_batch=2, max_seq_len=64)
    took = time.time() - t0
    spans = tracing.startup_spans()[before:]
    yield engine, spans, took
    engine.shutdown() if hasattr(engine, "shutdown") else None


def test_the_engine_leaves_init_with_its_four_children(engine_record):
    _, spans, took = engine_record
    (init,) = [s for s in spans if s.name == "engine.init"]
    children = [s for s in spans if s.parent_id == init.span_id
                and s.name.startswith("engine.")]
    assert [s.name for s in children] == [
        "engine.weights", "engine.resident", "engine.cache", "engine.place"]
    assert sum(s.duration_s for s in children) <= init.duration_s <= took
    for earlier, later in zip(children, children[1:]):
        assert earlier.end_ts == later.start_ts
    assert children[0].attributes["source"] == "seed"
    assert children[0].attributes["preset"] == "gpt2-tiny"
    # the programs the constructor prepared are its children too
    assert any(s.name.startswith("compile.") and s.parent_id == init.span_id
               for s in spans)


def test_engine_stats_counts_a_new_shape_and_not_a_repeated_one(
        engine_record):
    engine, _, _ = engine_record
    at_rest = engine.engine_stats()["compiles"]
    engine.generate("hello", max_tokens=4)
    warm = engine.engine_stats()
    assert warm["compiles"] > at_rest
    assert warm["last_compile"]["fun"] and warm["last_compile"]["seconds"] > 0
    assert warm["last_compile"]["at"] <= time.time()
    engine.generate("hello", max_tokens=4)
    assert engine.engine_stats()["compiles"] == warm["compiles"]
    assert engine.engine_stats()["last_compile"] == warm["last_compile"]


# -------------------------------------------------------------- the trainer

def _tiny_loop(config):
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.spmd import compile_gpt2_train

    cfg = gpt2.GPT2Config.preset("gpt2-tiny")
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    prog = compile_gpt2_train(cfg, mesh)
    jax.eval_shape(prog.init_fn, jax.random.key(0))     # not the first call
    state = prog.init_fn(jax.random.key(0))
    state = prog.init_fn(jax.random.key(1))             # nor is the second
    tokens = jnp.zeros((2, 17), jnp.int32)
    batch = jax.device_put({"tokens": tokens}, prog.batch_sharding)
    state, metrics = prog.step_fn(state, batch)
    train.report({"loss": float(metrics["loss"])})


@pytest.fixture(scope="module")
def trainer_record():
    from ray_tpu.train import JaxTrainer, ScalingConfig

    info = ray_tpu.init(num_cpus=4, num_tpu_chips=0, max_workers=8)
    result = JaxTrainer(_tiny_loop, train_loop_config={},
                        scaling_config=ScalingConfig(num_workers=1)).fit()
    assert result.metrics["loss"] > 0
    ray_tpu.shutdown()
    return _startup_files(info["session"])


@pytest.mark.parametrize("name", [
    "train.fit", "train.worker_setup", "train.loop_prelude", "train.compile",
    "train.init_state", "compile._init", "compile._step"])
def test_a_jax_trainer_run_leaves_the_span(trainer_record, name):
    found = [s for s in trainer_record["worker"] if s["name"] == name]
    assert len(found) == 1, [s["name"] for s in trainer_record["worker"]]


def test_the_trainers_stages_follow_one_another(trainer_record):
    workers = trainer_record["worker"]
    by_name = {s["name"]: s for s in workers if s["name"].startswith("train.")}
    setup, prelude, compile_, init = (
        by_name[n] for n in ("train.worker_setup", "train.loop_prelude",
                             "train.compile", "train.init_state"))
    assert len({s["attributes"]["pid"] for s in (setup, prelude, compile_,
                                                 init)}) == 1
    assert setup["attributes"]["rank"] == 0
    assert setup["end_ts"] <= prelude["end_ts"] <= compile_["start_ts"] + 0.01
    assert compile_["end_ts"] <= init["start_ts"]
    # `fit()` -> the loops running: it ends where the worker's set-up does
    fit = by_name["train.fit"]
    assert fit["start_ts"] <= setup["start_ts"]
    assert setup["end_ts"] <= fit["end_ts"] <= init["end_ts"]
    # the head wrote the controller's `group_start` beside its own spans
    (group,) = [s for s in trainer_record["head"]
                if s["name"] == "train.group_start"]
    assert "event_lost" not in group["attributes"]
    # the state's program was prepared inside `train.init_state`
    (made,) = [s for s in workers if s["name"] == "compile._init"]
    assert made["parent_id"] == init["span_id"]


def test_a_lost_train_event_is_counted_in_the_start_up_record(
        record, monkeypatch):
    from ray_tpu.train.config import RunConfig, ScalingConfig
    from ray_tpu.train.controller import TrainControllerLogic

    class Down:
        def head_request(self, *args, **kwargs):
            raise ConnectionError("the head is gone")

    logic = TrainControllerLogic(lambda: None, None, ScalingConfig(),
                                 RunConfig(name="lost"))
    monkeypatch.setattr(logic, "_client", lambda: Down())
    logic._emit_event("group_start", t0=5.0, t1=7.0, world=1)   # no raise
    (span,) = tracing.startup_spans()
    assert span.name == "train.group_start"
    assert (span.start_ts, span.end_ts) == (5.0, 7.0)
    assert span.attributes["event_lost"] is True
    assert span.attributes["run"] == "lost"


# --------------------------------------------- who must not import JAX

@pytest.mark.parametrize("module", [
    "ray_tpu.serve.proxy", "ray_tpu.core.head_main", "ray_tpu.core.node_main",
    "ray_tpu.core.worker_main", "ray_tpu.util.tracing",
    "ray_tpu.utils.platform"])
def test_importing_the_module_leaves_jax_out(module):
    p = subprocess.run(
        [sys.executable, "-c",
         f"import sys, {module}; sys.exit(int('jax' in sys.modules))"],
        env={**os.environ, "PYTHONPATH": REPO}, capture_output=True,
        text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
