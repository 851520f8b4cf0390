"""The hot paths name their own time: the serving engine's phase timers
and request-lifecycle histograms, `record_span`/`annotate`, the named
scopes of the step programs. CPU, `gpt2-tiny`, no cluster."""

import re
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.util import tracing

TRACEPARENT = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"


# ------------------------------------------------------------ util/tracing

@pytest.fixture
def fresh_spans(monkeypatch):
    """No span left over, and the process-wide switch off: another module
    of the same test process may have latched it."""
    monkeypatch.setattr(tracing, "_enabled", False)
    monkeypatch.delenv("RAY_TPU_TRACING", raising=False)
    tracing.get_finished_spans(clear=True)
    yield
    tracing.get_finished_spans(clear=True)


def test_record_span_keeps_the_carriers_trace_and_parent(fresh_spans):
    sp = tracing.record_span("late", 10.0, 12.5,
                             carrier={"traceparent": TRACEPARENT},
                             attributes={"k": 1})
    assert (sp.trace_id, sp.parent_id) == ("ab" * 16, "cd" * 8)
    assert (sp.start_ts, sp.end_ts, sp.duration_s) == (10.0, 12.5, 2.5)
    assert tracing.get_finished_spans() == [sp]
    assert tracing.current_span() is None        # never becomes current
    # the same path to the head as a span opened with start_span
    assert sp.to_dict() in tracing.drain_push_spans()


def test_record_span_parents_to_the_current_span(fresh_spans):
    with tracing.start_span("root",
                            carrier={"traceparent": TRACEPARENT}) as root:
        sp = tracing.record_span("child", 1.0, 2.0)
    assert (sp.trace_id, sp.parent_id) == (root.trace_id, root.span_id)


@pytest.mark.parametrize("carrier", [
    None, {"traceparent": "garbage"},
    {"traceparent": TRACEPARENT[:-2] + "00"}])       # not sampled
def test_record_span_records_nothing_when_nothing_traces(carrier,
                                                         fresh_spans):
    assert not tracing.is_enabled()
    assert tracing.record_span("late", 1.0, 2.0, carrier=carrier) is None
    assert tracing.get_finished_spans() == []


def test_annotate_is_a_profiler_span_here_and_null_without_jax():
    import jax

    ann = tracing.annotate("engine.fetch")
    assert isinstance(ann, jax.profiler.TraceAnnotation)
    with ann:
        pass
    # the proxy and the head never import JAX, and must not for this
    code = ("import sys, contextlib\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.annotate('x') as a:\n"
            "    pass\n"
            "assert isinstance(tracing.annotate('x'), "
            "contextlib.nullcontext)\n"
            "assert 'jax' not in sys.modules\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


# ------------------------------------------------------------- the engine

@pytest.fixture(scope="module")
def engine():
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(preset="gpt2-tiny", max_batch=4, max_seq_len=128,
                    prefill_chunk_size=16, kv_block_size=8)
    yield eng
    eng.shutdown()


def _generate(eng, n: int, **kw) -> None:
    threads = [threading.Thread(
        target=eng.generate,
        kwargs={"prompt_ids": list(range(1 + i, 30 + i)), "max_tokens": 12,
                **kw}) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()


def _grown(before, after, key):
    return {k: after[key][k] - before[key][k] for k in after[key]}


def test_engine_phase_timers_account_for_the_loops_busy_time(engine):
    from ray_tpu.serve.llm import ENGINE_PHASES

    _generate(engine, 1)        # the first request prepares the programs
    before, t0 = engine.engine_stats(), time.perf_counter()
    for _ in range(3):          # more than slots, for a second or more
        _generate(engine, 6, temperature=0.7, top_p=0.9)
    after, t1 = engine.engine_stats(), time.perf_counter()
    assert (set(after["phase_s"]) == set(after["phase_cpu_s"])
            == set(ENGINE_PHASES))
    d, cpu = (_grown(before, after, key)
              for key in ("phase_s", "phase_cpu_s"))
    assert all(v >= 0 for v in d.values())
    # `dispatch` is the step program's launch and `fetch` the wait for its
    # [B] ids, a step late; `sample` is no longer the host choosing tokens
    # in numpy but what it costs to launch the selection on the device,
    # which is waited for in `fetch` with the step itself
    assert d["dispatch"] > 0 and d["fetch"] > 0
    assert 0 < d["sample"] < d["fetch"]
    # the thread is always in one phase: together they are the loop's wall
    # time, but for the lap that was open at either reading
    assert sum(d.values()) == pytest.approx(t1 - t0, rel=0.01)
    # a phase's CPU seconds are part of its wall seconds, but for the
    # moment between a boundary's two clock readings
    for k in ENGINE_PHASES:
        assert 0 <= cpu[k] <= d[k] + 0.002, (k, cpu[k], d[k])
    # waiting for the device and sleeping are not running
    assert cpu["empty"] < 0.5 * d["empty"] or d["empty"] < 0.01
    # the passes that ran a step, tails and all, and no other
    busy = after["loop_busy_s"] - before["loop_busy_s"]
    assert 0 < busy <= sum(d.values()) - d["empty"] + 1e-6
    assert busy >= sum(d.values()) - 2 * d["empty"] - 0.05 * (t1 - t0)
    assert after["engine_steps"] > before["engine_steps"]


def test_the_lap_timer_charges_a_reading_to_the_phase_that_ends_there():
    from ray_tpu.serve.llm import ENGINE_PHASES, _Phases

    laps = _Phases()
    assert list(laps.wall) == list(laps.cpu) == list(ENGINE_PHASES)
    t0 = time.perf_counter()
    laps.start("calls")
    c0 = laps.c
    time.sleep(0.05)
    at = laps.to("fetch")
    _spin(0.05)
    laps.to("release")
    t1 = time.perf_counter()
    assert at == pytest.approx(t0 + 0.05, abs=0.04) and laps.t <= t1
    # asleep in the one, running in the other; `release` is still open
    assert laps.wall["calls"] >= 0.05 > 0.01 > laps.cpu["calls"]
    assert laps.wall["fetch"] >= laps.cpu["fetch"] - 0.002 >= 0.045
    assert laps.wall["release"] == laps.cpu["release"] == 0.0
    assert sum(laps.wall.values()) == pytest.approx(laps.t - t0, abs=0.001)
    assert sum(laps.cpu.values()) == pytest.approx(laps.c - c0, abs=1e-6)


def test_cpu_seconds_of_the_thread_and_the_process_are_read_at_the_call(
        engine):
    before = engine.engine_stats()
    _generate(engine, 2)
    after = engine.engine_stats()
    cpu = _grown(before, after, "phase_cpu_s")
    c0, c1 = before["cpu_s"], after["cpu_s"]
    assert set(c1) == {"engine_thread", "process"}
    # the thread's own clock at its newest boundary: what its phases sum to
    thread = c1["engine_thread"] - c0["engine_thread"]
    assert thread == pytest.approx(sum(cpu.values()), abs=0.02)
    # and part of the whole process's, which other threads add to
    assert 0 < thread <= c1["process"] - c0["process"] + 0.02


def _slow_passes_since(engine, before):
    """(how many more slow passes `count` says, the records kept since)."""
    after = engine.engine_stats()["slow_passes"]
    seen = {(r["step"], r["t_end"]) for r in before["newest"]}
    return (after["count"] - before["count"],
            after["seconds"] - before["seconds"],
            [r for r in after["newest"]
             if (r["step"], r["t_end"]) not in seen])


def _spin(seconds: float) -> None:
    # so much of this thread's own CPU time, however loaded the machine
    until = time.thread_time() + seconds
    while time.thread_time() < until:
        pass


@pytest.mark.parametrize("call,running", [
    (lambda: time.sleep(0.4), False), (lambda: _spin(0.4), True)],
    ids=["sleeps", "spins"])
def test_a_slow_pass_is_kept_by_name_and_says_whether_the_thread_ran(
        engine, call, running):
    before = engine.engine_stats()["slow_passes"]
    t0 = time.time()
    engine._on_engine_thread(call, 30.0)
    _generate(engine, 1)             # the pass has ended by now
    count, seconds, kept = _slow_passes_since(engine, before)
    (slow,) = [r for r in kept if r["phases"].get("calls", 0) >= 0.4]
    assert count == len(kept) and seconds == pytest.approx(
        sum(r["wall_s"] for r in kept))
    assert set(slow) == {"step", "t_end", "wall_s", "cpu_s", "phases",
                         "live", "admitted", "chunked", "compiles"}
    assert slow["wall_s"] >= slow["phases"]["calls"] >= 0.4
    assert sum(slow["phases"].values()) == pytest.approx(slow["wall_s"],
                                                         abs=0.011)
    if running:
        assert slow["cpu_s"] >= 0.3
    else:
        assert slow["cpu_s"] < 0.1
    # on the clock of a client's log, and nothing else happened in it
    assert t0 + 0.4 <= slow["t_end"] <= time.time()
    assert slow["step"] <= engine.engine_stats()["engine_steps"]
    assert (slow["live"], slow["admitted"], slow["chunked"],
            slow["compiles"]) == (0, 0, False, 0)


def test_the_newest_sixteen_slow_passes_are_kept_and_all_are_counted(
        engine, monkeypatch):
    from ray_tpu.serve import llm

    monkeypatch.setattr(llm, "SLOW_PASS_S", 0.1)
    before = engine.engine_stats()["slow_passes"]
    started = []
    for _ in range(20):
        started.append(time.time())
        engine._on_engine_thread(lambda: time.sleep(0.12), 30.0)
        time.sleep(0.04)             # the pass ends before the next starts
    count, _, kept = _slow_passes_since(engine, before)
    newest = engine.engine_stats()["slow_passes"]["newest"]
    assert len(newest) == 16 and kept == newest
    assert count >= 20
    # in the order they ended, and (if no other pass was slow) none older
    # than the fifth call's
    assert [r["t_end"] for r in newest] == sorted(r["t_end"] for r in newest)
    mine = [r for r in newest if r["phases"].get("calls", 0) >= 0.12]
    assert len(mine) >= 16 - (count - 20)
    assert all(r["t_end"] > started[4] for r in mine)


def test_release_and_put_grow_under_load_and_an_idle_engine_dispatches_nothing(
        engine):
    _generate(engine, 1)
    before = engine.engine_stats()
    _generate(engine, 3)
    loaded = engine.engine_stats()
    d = _grown(before, loaded, "phase_s")
    assert d["release"] > 0 and d["put"] > 0
    time.sleep(0.3)                  # some fifty passes with nothing to run
    idle = _grown(loaded, engine.engine_stats(), "phase_s")
    for k in ("put", "dispatch", "sample", "fetch", "publish", "notify"):
        assert idle[k] == 0, k
    for k in ("calls", "admit", "plan", "empty", "release"):
        assert idle[k] > 0, k
    assert sum(idle.values()) == pytest.approx(0.3, abs=0.05)


def test_a_pass_opens_its_phases_in_the_loops_order_each_closed_first(
        engine, monkeypatch):
    log = []

    class Recorded:
        # this engine's thread alone: an engine that another file's test
        # left running in the process annotates its own passes too
        def __init__(self, name):
            self.name = name
            self.mine = threading.current_thread() is engine._thread

        def __enter__(self):
            if self.mine:
                log.append(("open", self.name))

        def __exit__(self, *exc):
            if self.mine:
                log.append(("close", self.name))

    monkeypatch.setattr(tracing, "annotate", Recorded)
    _generate(engine, 1)
    time.sleep(0.05)                 # a few passes with nothing to run
    monkeypatch.undo()
    # from the first pass that opened under the recorder
    events = log[log.index(("open", "engine.calls")):]
    assert all(name.startswith("engine.") for _, name in events)
    # each phase is closed before the next opens
    assert all(a == ("open", b[1]) and b[0] == "close"
               for a, b in zip(events[::2], events[1::2]))
    opened = [name[len("engine."):] for kind, name in events
              if kind == "open"]
    passes, at = [], [i for i, n in enumerate(opened) if n == "calls"]
    for i, j in zip(at, at[1:]):
        passes.append(opened[i:j])
    # a pass that dispatched a step and read the one before it
    stepping = [p for p in passes if "dispatch" in p and "fetch" in p]
    assert stepping
    for p in stepping:
        assert [n for n in p if n != "publish"] == [
            "calls", "admit", "plan", "put", "dispatch", "sample", "fetch",
            "notify", "release"]
        assert p[p.index("dispatch") - 1] == "put"
    # and one with nothing to run
    assert ["calls", "admit", "plan", "empty", "release"] in passes


def test_engine_histograms_count_each_finished_request_once(engine):
    before = engine.engine_stats()
    _generate(engine, 5)
    after = engine.engine_stats()
    for key in ("queue_wait_s", "ttft_s"):
        b, a = before[key], after[key]
        assert a["count"] - b["count"] == 5
        assert a["sum"] > b["sum"]
    # a request waits for a slot before it is prefilled
    assert after["ttft_s"]["sum"] > after["queue_wait_s"]["sum"]
    # a mean, or one request's, is the count's and the sum's to give
    assert "ttft_avg_s" not in after and "last_ttft_s" not in after
    ttft = after["ttft_s"]
    assert 0 < ttft["sum"] / ttft["count"] < 60


def test_engine_counters_only_grow(engine):
    readings = [engine.engine_stats()]
    for _ in range(3):
        _generate(engine, 2)
        readings.append(engine.engine_stats())
    for a, b in zip(readings, readings[1:]):
        assert b["loop_busy_s"] > a["loop_busy_s"]
        assert all(b["phase_s"][k] >= a["phase_s"][k] for k in a["phase_s"])
        # a step dispatched with the one before it unread, and a lane run
        # for a request its EOS had ended: cumulative, read as deltas
        assert b["engine_steps"] > a["engine_steps"]
        assert b["steps_dispatched_ahead"] > a["steps_dispatched_ahead"]
        assert b["overrun_lane_steps"] >= a["overrun_lane_steps"]
        assert (b["steps_dispatched_ahead"] - a["steps_dispatched_ahead"]
                < b["engine_steps"] - a["engine_steps"])


@pytest.mark.parametrize("name", ["steps_dispatched_ahead",
                                  "overrun_lane_steps"])
def test_the_run_ahead_counters_are_whole_numbers_in_engine_stats(engine,
                                                                  name):
    stats = engine.engine_stats()
    assert isinstance(stats[name], int) and stats[name] >= 0
    assert stats[name] <= stats["engine_steps"] * engine.max_batch


def test_engine_histograms_reach_the_metrics_registry(engine):
    from ray_tpu.util import metrics

    _generate(engine, 1)
    stats = engine.engine_stats()
    by_name = {m["name"]: m for m in metrics.snapshot_all()}
    for name, key in (("serve_engine_queue_wait_seconds", "queue_wait_s"),
                      ("serve_engine_ttft_seconds", "ttft_s")):
        # one series for the process, whatever engines it has held
        (series,) = by_name[name]["series"]
        h = series["histogram"]
        assert h["count"] >= stats[key]["count"] >= 1
        assert sum(h["buckets"]) == h["count"]


def test_a_traceparent_follows_the_request_through_the_engine(fresh_spans):
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(preset="gpt2-tiny", max_batch=2, max_seq_len=128,
                       prefill_chunk_size=16, kv_block_size=8)
    try:
        body = {"prompt_ids": list(range(1, 40)), "max_tokens": 6}
        server(body)                         # not traced: no engine span
        assert tracing.get_finished_spans() == []
        # as ReplicaActor.handle_request runs the callable
        with tracing.start_span(
                "serve.replica",
                carrier={"traceparent": TRACEPARENT}) as replica:
            out = server(body)
    finally:
        server.engine.shutdown()
    spans = {s.name: s for s in tracing.get_finished_spans()}
    stretches = ["engine.queue_wait", "engine.prefill", "engine.decode"]
    assert set(spans) == {"serve.replica", *stretches}
    for name in stretches:
        sp = spans[name]
        assert sp.trace_id == "ab" * 16 and sp.parent_id == replica.span_id
        assert sp.attributes == {
            "prompt_tokens": 39, "generated": 6,
            # the first, untraced request pooled the prompt's full blocks
            "reused_tokens": 32}
        assert replica.start_ts <= sp.start_ts <= sp.end_ts <= replica.end_ts
    assert (spans["engine.queue_wait"].end_ts
            == spans["engine.prefill"].start_ts)
    assert spans["engine.prefill"].end_ts == spans["engine.decode"].start_ts
    assert len(out["choices"][0]["token_ids"]) == 6


# ----------------------------------------------- the step programs' scopes

MODEL_SCOPES = {"embed", "ln", "attn", "mlp", "unembed_loss", "weights_cast",
                "layers"}
ALL_SCOPES = MODEL_SCOPES | {"optimizer", "kv_update", "prefix_pool"}


def _scopes_in(lowered) -> set:
    words = set()
    for loc in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        words.update(re.findall(r"[A-Za-z_]\w*", loc))
    return words & ALL_SCOPES


def _lower(program: str):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.preset("gpt2-tiny")
    params = jax.eval_shape(lambda: gpt2.init_params(jax.random.key(0), cfg))
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    on = jax.ShapeDtypeStruct((4,), jnp.bool_)
    cache = jax.eval_shape(lambda: gpt2.init_cache(cfg, 4, 64))
    if program in ("loss_fn", "grad_of_chunked_loss"):
        batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
        fn = lambda p, b: gpt2.loss_fn(p, b, cfg)        # noqa: E731
        if program == "grad_of_chunked_loss":
            fn = jax.value_and_grad(fn)
        return jax.jit(fn).lower(params, batch)
    if program == "decode_step":
        return jax.jit(lambda p, c, t, pos, a: gpt2.decode_step(
            p, c, t, pos, a, cfg)).lower(params, cache, ints, ints, on)
    return jax.jit(lambda p, c, t, p0, n, a: gpt2.prefill_chunk(
        p, c, t, p0, n, a, cfg)).lower(
            params, cache, jax.ShapeDtypeStruct((4, 8), jnp.int32), ints,
            ints, on)


@pytest.mark.parametrize("program,want", [
    ("loss_fn", MODEL_SCOPES), ("grad_of_chunked_loss", MODEL_SCOPES),
    ("decode_step", MODEL_SCOPES | {"kv_update"}),
    ("prefill_chunk", MODEL_SCOPES | {"kv_update"})])
def test_every_scope_is_in_the_lowered_program(monkeypatch, program, want):
    if program == "grad_of_chunked_loss":
        # the fused loss in two chunks, and its backward pass, which only
        # scales what the forward made, under the same scope
        from ray_tpu.models import lm

        monkeypatch.setattr(lm, "LOGITS_CHUNK_BYTES", 2 * 16 * 512 * 4)
    lowered = _lower(program)
    assert _scopes_in(lowered) == want
    if program == "grad_of_chunked_loss":
        assert "transpose(jvp(unembed_loss))" in lowered.as_text(
            debug_info=True)


@pytest.fixture(scope="module")
def compiled_train():
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import compile_gpt2_train

    cfg = gpt2.GPT2Config.preset("gpt2-tiny")
    mesh = build_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    return compile_gpt2_train(cfg, mesh)


def _lower_train_step(compiled):
    import jax
    import jax.numpy as jnp

    state = jax.eval_shape(compiled.init_fn, jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 33), jnp.int32)}
    return compiled.step_fn.lower(state, batch)


def test_the_train_step_adds_the_optimizer_scope(compiled_train):
    assert _scopes_in(_lower_train_step(compiled_train)) == (
        MODEL_SCOPES | {"optimizer"})


def test_the_prefix_pools_copies_are_scoped():
    import jax.numpy as jnp

    from ray_tpu.serve.kv_cache import PagedKVCache

    kv = PagedKVCache(n_layer=2, n_head=2, head_dim=4, num_blocks=8,
                      block_size=4)
    cache = jnp.zeros((2, 2, 2, 32, 4), jnp.float32)
    plan = kv._plan({"k": cache}, slot=0, rows=[(1, 0), (2, 3)])
    out = kv._copy_out.lower(kv.pool_k, cache, plan)
    back = kv._copy_in.lower(cache, kv.pool_k, plan)
    assert _scopes_in(out) == _scopes_in(back) == {"prefix_pool"}


def test_the_jitted_programs_keep_the_names_the_benchmark_matches(
        engine, compiled_train):
    """`decode_device_ms`, `prefill_device_ms` and `train_step_ms` find
    their XLA modules as `jit__step` / `jit__chunk`."""
    import numpy as np

    ints, on = np.zeros((4,), np.int32), np.zeros((4,), bool)
    step = engine._step.lower(engine.params, engine.cache, ints, ints, on)
    chunk = engine._chunk_step.lower(
        engine.params, engine.cache, np.zeros((4, 16), np.int32), ints,
        ints, on)
    assert "module @jit__step " in step.as_text()
    assert "module @jit__chunk " in chunk.as_text()
    assert "module @jit__step " in _lower_train_step(compiled_train).as_text()


# ------------------------------------------------------ train loop, ingest

def test_ingest_yields_a_ranks_slices_from_the_start_batch():
    import numpy as np

    from ray_tpu.train.ingest import DatasetShard

    class Rows:
        def iter_batches(self, *, batch_size, batch_format, drop_last):
            rows = np.arange(22)
            for i in range(0, len(rows) - batch_size + 1, batch_size):
                yield {"x": rows[i:i + batch_size]}

    shard = DatasetShard(Rows(), rank=1, world_size=2)
    got = list(shard.iter_global_batches(batch_size=4, start_batch=2))
    assert [gi for gi, _ in got] == [2, 3, 4]
    assert [b["x"].tolist() for _, b in got] == [[10, 11], [14, 15],
                                                 [18, 19]]
    assert list(shard.iter_global_batches(batch_size=4,
                                          start_batch=9)) == []


def test_report_records_the_step_window_as_a_span(fresh_spans):
    from ray_tpu.train import session

    ctx = session.TrainContext(rank=0, world_size=1, run_name="r")
    session._set_context(ctx)
    try:
        with tracing.start_span("run", carrier={"traceparent": TRACEPARENT}):
            session.report({"loss": 1.0})      # set-up: no step yet
            t_first = ctx._step_wall_t0
            session.report({"loss": 0.9})
    finally:
        session._set_context(None)
    (step,) = [s for s in tracing.get_finished_spans()
               if s.name == "train.step"]
    assert step.trace_id == "ab" * 16 and step.attributes["step"] == 1
    assert step.start_ts == pytest.approx(t_first) and step.end_ts >= t_first
    assert len(ctx.reports) == 2
