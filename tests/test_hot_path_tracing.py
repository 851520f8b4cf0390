"""The hot paths name their own time: the serving engine's phase timers
and request-lifecycle histograms, `record_span`/`annotate`, the named
scopes of the step programs. CPU, `gpt2-tiny`, no cluster."""

import re
import subprocess
import sys
import threading

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


def test_engine_phase_timers_account_for_the_loops_busy_time(engine):
    from ray_tpu.serve.llm import ENGINE_PHASES

    _generate(engine, 1)        # the first request prepares the programs
    before = engine.engine_stats()
    _generate(engine, 6, temperature=0.7, top_p=0.9)   # more than slots
    after = engine.engine_stats()
    assert set(after["phase_s"]) == set(ENGINE_PHASES)
    d = {k: after["phase_s"][k] - before["phase_s"][k]
         for k in ENGINE_PHASES}
    assert all(v >= 0 for v in d.values())
    # `dispatch` is the step program's launch and `fetch` the wait for its
    # [B] ids, a step late; `sample` is no longer the host choosing tokens
    # in numpy but what it costs to launch the selection on the device,
    # which is waited for in `fetch` with the step itself
    assert d["dispatch"] > 0 and d["fetch"] > 0
    assert 0 < d["sample"] < d["fetch"]
    busy = after["loop_busy_s"] - before["loop_busy_s"]
    phased = sum(v for k, v in d.items() if k != "empty")
    assert busy > 0 and abs(phased - busy) <= 0.05 * busy, (phased, busy)
    assert after["engine_steps"] > before["engine_steps"]


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
    # the older readings are derived from the same count and sum
    ttft = after["ttft_s"]
    assert after["ttft_avg_s"] == pytest.approx(
        ttft["sum"] / ttft["count"], abs=1e-5)
    assert after["last_ttft_s"] > 0


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
