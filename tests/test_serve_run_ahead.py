"""The engine loop runs one step ahead of what it has read
(`serve/llm.py`): the replies are those of each request served alone, an
EOS costs one lane-step and leaks nothing, a slot freed by length serves
the next request on the very next step, and every program the loop can
dispatch is prepared by the first requests. CPU, `gpt2-tiny`."""

import threading
import time

import jax
import numpy as np
import pytest

from ray_tpu.serve.llm import LLMEngine

CHUNK = 16


def _weights():
    """`gpt2-tiny` in float32 with its blocks' matrices scaled up: at its
    initial scale the tied table outweighs the layers and every greedy
    reply repeats the prompt's last token, which would show nothing."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.preset("gpt2-tiny", max_seq_len=128,
                                 dtype=jnp.float32)
    params = gpt2.init_params(jax.random.key(7), cfg)
    params["blocks"] = jax.tree.map(
        lambda a: a * 8.0 if a.ndim >= 3 else a, params["blocks"])
    return dict(params_override=params, cfg_override=cfg)


KW = dict(preset="gpt2-tiny", max_seq_len=128, prefill_chunk_size=CHUNK,
          kv_block_size=8, weights_id="run-ahead-test", **_weights())


class Tokens:
    """Ids in, ids out, and an EOS the test moves."""

    def __init__(self, eos_id=-1):
        self.eos_id = eos_id

    def encode(self, text):
        return [ord(c) % 251 + 1 for c in text]

    def decode(self, ids):
        return " ".join(map(str, ids))


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 250, n).tolist()


LONG = _prompt(0, 3 * CHUNK + 5)
# (prompt, max_tokens): shorter and longer than a chunk, one a chunk to the
# token, one sharing three pooled blocks with LONG, more than there are slots
REQUESTS = [
    (_prompt(1, 5), 9), (LONG, 14), (_prompt(2, CHUNK), 3),
    (_prompt(3, 2 * CHUNK + 1), 11), (LONG[:24] + _prompt(4, 9), 12),
    (_prompt(5, 1), 6), (_prompt(6, 20), 1), (_prompt(7, 33), 17),
]


@pytest.fixture(scope="module")
def alone():
    """Each request's greedy reply, served alone by an engine with no
    prefix pool and an EOS no token is."""
    eng = LLMEngine(max_batch=1, enable_prefix_caching=False,
                    tokenizer=Tokens(), **KW)
    try:
        return [eng.generate(prompt_ids=p, max_tokens=m)["token_ids"]
                for p, m in REQUESTS]
    finally:
        eng.shutdown()


@pytest.fixture
def engine():
    eng = LLMEngine(max_batch=3, kv_blocks=32, tokenizer=Tokens(), **KW)
    yield eng
    eng.shutdown()


def _serve_together(eng, requests, **kw):
    out = [None] * len(requests)

    def one(j, prompt, max_tokens):
        out[j] = eng.generate(prompt_ids=prompt, max_tokens=max_tokens, **kw)

    threads = [threading.Thread(target=one, args=(j, p, m))
               for j, (p, m) in enumerate(requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    return out


def test_a_batch_gives_each_requests_reply_alone(engine, alone):
    assert [len(r) for r in alone] == [m for _, m in REQUESTS]
    before = engine.engine_stats()
    first = _serve_together(engine, REQUESTS[:4])
    # LONG's blocks are pooled now: the fifth request starts from them
    rest = _serve_together(engine, REQUESTS[4:])
    after = engine.engine_stats()
    got = [r["token_ids"] for r in first + rest]
    assert got == alone
    assert engine.kv.stats()["prefix_hits"] >= 1
    assert engine.kv.stats()["tokens_reused"] >= 24
    assert after["chunk_steps"] > before["chunk_steps"]
    # no reply ended by EOS: no lane ran for a request that had ended
    assert after["overrun_lane_steps"] == before["overrun_lane_steps"]


def test_a_slot_freed_by_length_serves_the_next_request_on_the_next_step(
        alone):
    """One slot, three requests queued behind each other: a request's
    last step is known before it runs, so the next request's first chunk
    is dispatched right behind it. Every step but the first finds the
    step before it unread."""
    eng = LLMEngine(max_batch=1, kv_blocks=32, tokenizer=Tokens(), **KW)
    try:
        picks = [1, 0, 3]
        sids = [eng.start_stream(prompt_ids=REQUESTS[j][0],
                                 max_tokens=REQUESTS[j][1]) for j in picks]
        replies = [_read_stream(eng, sid)[0] for sid in sids]
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert replies == [alone[j] for j in picks]
    assert stats["steps_dispatched_ahead"] == stats["engine_steps"] - 1
    assert stats["overrun_lane_steps"] == 0


def _read_stream(eng, sid):
    """Every token a stream ever showed, in order, and how it ended."""
    seen, cursor = [], 0
    deadline = time.time() + 120
    while time.time() < deadline:
        out = eng.stream_next(sid, cursor=cursor, timeout=0.5)
        seen += out["token_ids"]
        cursor = out["cursor"]
        if out["done"]:
            return seen, out["finish_reason"]
    raise AssertionError("the stream never ended")


def _an_eos_for(alone, picks, first):
    """(request, position, token): a token of one of the `first` replies
    that ends it early and that no other picked reply holds."""
    for j in first:
        others = {t for o in picks if o != j for t in alone[o]}
        reply = alone[j]
        for at in range(1, len(reply) - 2):
            if reply[at] not in others and reply[at] not in reply[:at]:
                return j, at, reply[at]
    raise AssertionError("no reply has a token of its own: change a seed")


def test_an_eos_costs_one_lane_step_and_leaks_nothing(engine, alone):
    picks = [1, 3, 7, 4]                 # three slots and one that waits
    j, at, eos = _an_eos_for(alone, picks, picks[:3])
    engine.tokenizer.eos_id = eos
    before = engine.engine_stats()
    sids = {k: engine.start_stream(prompt_ids=REQUESTS[k][0],
                                   max_tokens=REQUESTS[k][1]) for k in picks}
    shown = {k: _read_stream(engine, sid) for k, sid in sids.items()}
    after = engine.engine_stats()
    # it ends at the EOS, and its stream never showed a later token
    assert shown[j] == (alone[j][:at + 1], "stop")
    # its neighbours, and the request that took its slot, are untouched
    for k in picks:
        if k != j:
            assert shown[k] == (alone[k], "length"), k
    assert after["overrun_lane_steps"] - before["overrun_lane_steps"] == 1
    assert (after["total_generated"] - before["total_generated"]
            == sum(len(s[0]) for s in shown.values()))
    # the slot is free again: nothing is live, nothing is dispatched
    assert engine._slots == [None] * 3


def test_steps_are_dispatched_ahead_under_load_and_not_when_idle(engine):
    before = engine.engine_stats()
    _serve_together(engine, REQUESTS)
    after = engine.engine_stats()
    steps = after["engine_steps"] - before["engine_steps"]
    ahead = after["steps_dispatched_ahead"] - before["steps_dispatched_ahead"]
    # every step but the first after an empty engine
    assert 0.8 * steps <= ahead < steps
    time.sleep(0.3)
    idle = engine.engine_stats()
    for name in ("engine_steps", "steps_dispatched_ahead",
                 "overrun_lane_steps", "total_generated"):
        assert idle[name] == after[name], name
    assert idle["phase_s"]["empty"] > after["phase_s"]["empty"]


class Prepared:
    """Counts the programs this process prepares to run, as
    `benchmarks/chip/harness/replica_probe.CompileCounter` does."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            self.count += 1

    def close(self):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self._on)


def test_nothing_is_prepared_after_the_warm_up_whatever_comes_later():
    """The serving cells' own check: their warm-up is greedy two-token
    requests sent one at a time, the last one a prompt seen before
    (`generators/open_loop_sessions.py`), so it never shows the loop a
    sampling slot, nor a chunk step with a decoding lane beside it."""
    prepared = Prepared()
    eng = LLMEngine(max_batch=3, kv_blocks=32, tokenizer=Tokens(), **KW)
    try:
        for prompt in (LONG, LONG):
            eng.generate(prompt_ids=prompt, max_tokens=2)
        assert eng.kv.stats()["prefix_hits"] == 1
        warm = prepared.count
        assert warm > 0
        hits = eng.kv.stats()["prefix_hits"]
        # a decoding slot, then prompts that prefill beside it: sampling
        # ones of every kind, a greedy one, a prefix hit
        sid = eng.start_stream(prompt_ids=_prompt(8, 4), max_tokens=40,
                               temperature=0.7, top_p=0.95)
        while not eng.stream_next(sid, cursor=0, timeout=0.5)["token_ids"]:
            pass
        outs = [eng.start_stream(prompt_ids=p, max_tokens=m, **kw)
                for p, m, kw in (
                    (_prompt(9, 3 * CHUNK), 8, dict(temperature=1.1,
                                                    top_k=5)),
                    (LONG[:40] + _prompt(10, 7), 6, dict()),
                    (_prompt(11, 21), 5, dict(temperature=0.5, top_k=3,
                                              top_p=0.5)))]
        for s in [sid, *outs]:
            _read_stream(eng, s)
        assert eng.kv.stats()["prefix_hits"] > hits
        assert eng.chunk_steps > 0
        assert prepared.count == warm
    finally:
        prepared.close()
        eng.shutdown()


def test_the_benchmarks_probe_still_lowers_both_step_programs(engine):
    """`families/gpt2_server.engine_programs()` passes `_step` five
    arguments and `_chunk_step` six, as numpy arrays beside the engine's
    own params and cache."""
    b, c = engine.max_batch, engine.prefill_chunk_size
    ints, on = np.zeros((b,), np.int32), np.zeros((b,), bool)

    def shapes(*args):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)

    vocab = engine.cfg.vocab_size
    step = engine._step.lower(
        *shapes(engine.params, engine.cache, ints, ints, on))
    chunk = engine._chunk_step.lower(
        *shapes(engine.params, engine.cache, np.zeros((b, c), np.int32),
                ints, ints, on))
    for lowered in (step, chunk):
        logits, cache = lowered.out_info
        assert logits.shape == (b, vocab) and logits.dtype == np.float32
        assert cache["k"].shape == engine.cache["k"].shape
