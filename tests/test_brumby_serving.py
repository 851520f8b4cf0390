"""Brumby through the serving path on the CPU at a tiny size: the two forms
of power retention (chunks, then the one-token recurrence) against the
plain reference's full forward pass; the expansion; the kernel against the
plain form; the state's contract in the engine (zeroed at placement,
untouched where inactive, pooled between two chunk steps, found again); the
pool of snapshots; and what the family refuses by name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
if CHIP_DIR not in sys.path:
    sys.path.insert(0, CHIP_DIR)

from families import brumby as family  # noqa: E402

from ray_tpu.models import brumby, serving_family  # noqa: E402
from ray_tpu.ops import power_retention as pr  # noqa: E402
from ray_tpu.serve.kv_cache import (PagedKVCache, chain_hashes,  # noqa: E402
                                    export_prefix, import_prefix)
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

# the tiny preset in the source's key names, for the reference
MODEL = {"vocab_size": 512, "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "hidden_size": 64,
         "intermediate_size": 128, "rope_theta": 1000000,
         "rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False}
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return brumby.BrumbyConfig.preset(
        "brumby-tiny", **{**family.program_sizes(MODEL), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == brumby.BrumbyConfig.preset("brumby-tiny")


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 3)
    eng = LLMEngine(preset="brumby-tiny", max_batch=3, max_seq_len=96,
                    seed=SEED, model_overrides=dict(compute),
                    kv_block_size=8, prefill_chunk_size=chunk, **kwargs)
    eng.shutdown()              # the loop: the programs are driven by hand
    eng._thread.join()
    return eng


def through_the_programs(eng, prompt, n_decode, slot=1, start=0, forced=None):
    """`prompt` from position `start` in chunks, then `n_decode - 1` decode
    steps, through the engine's own `_chunk_step` and `_step`: (the tokens
    chosen, greedy or `forced`; the logits [n_decode, V])."""
    B, C = eng.max_batch, eng.prefill_chunk_size
    lanes = np.arange(B) == slot
    pos = start
    while pos < len(prompt):
        take = min(C, len(prompt) - pos)
        tokens = np.zeros((B, C), np.int32)
        tokens[slot, :take] = prompt[pos:pos + take]
        logits, eng.cache = eng._chunk_step(
            eng.params, eng.cache, tokens,
            np.where(lanes, pos, 0).astype(np.int32),
            np.where(lanes, take, 0).astype(np.int32), lanes)
        pos += take
    rows, chosen = [np.asarray(logits[slot])], []
    for j in range(n_decode):
        chosen.append(int(rows[-1].argmax()) if forced is None
                      else forced[j])
        if j == n_decode - 1:
            break
        tokens = np.zeros((B,), np.int32)
        tokens[slot] = chosen[-1]
        logits, eng.cache = eng._step(
            eng.params, eng.cache, tokens,
            np.where(lanes, pos, 0).astype(np.int32), lanes)
        pos += 1
        rows.append(np.asarray(logits[slot]))
    return chosen, np.stack(rows)


def reference_logits(cfg, row, at, degrade=None):
    key = jax.random.key(SEED)
    ref = family.Reference(MODEL, lambda l: brumby.init_layer(key, l, cfg),
                           brumby.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


# Float32 compute against the float32 reference: the same sums in another
# order (a state carried across chunks and steps against one quadratic sum),
# 2e-7 on logits of size 0.2 here. bf16 compute against it (the reference
# reads the same bf16 weights, so only the activations' rounding is in it:
# the projections' inputs, the MLP; q, k, v, the gates and the state stay
# float32): 2.2e-3 to 2.9e-3 over chunk sizes. A state held in bfloat16
# moves the float32 logits by 3.4e-2 at the worst position and 4e-4 in the
# mean after 48 tokens, two thousand times the float32 tolerance: that is
# the limit that tells it apart here, and on the chip the cell's own check
# (`families/brumby.py`, PERF.md PR 33).
FLOAT32_LOGIT_TOLERANCE = 2e-5
BF16_LOGIT_TOLERANCE = 8e-3


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no state, no chunks): the logits at
    every generated position. 37 tokens in chunks of 16 and of 7 (which do
    not divide them), of 8 (which ends on a block) and of 64 (one chunk)."""
    eng = engine(compute, chunk=chunk)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))))
    assert got.shape == want.shape == (N_DECODE, 512)
    assert np.abs(got - want).max() <= tolerance
    if compute is F32:
        assert chosen == want.argmax(axis=-1).tolist()


@pytest.mark.parametrize("degrade", family.DEGRADE[1:])
def test_a_lower_precision_state_is_refused_by_the_float32_tolerance(degrade):
    eng = engine()
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    off = reference_logits(eng.cfg, row, at, degrade)
    assert np.abs(got - off).max() > 5 * FLOAT32_LOGIT_TOLERANCE


@pytest.mark.parametrize("d", [16, 128])
def test_the_expansions_product_is_the_products_square(d):
    a, b = jax.random.normal(jax.random.key(0), (2, 5, d))
    pa, pb = pr.phi(a), pr.phi(b)
    assert pa.shape == (5, pr.expanded_width(d)) == (5, (d // 2 + 1) * d)
    np.testing.assert_allclose((pa * pb).sum(-1), (a * b).sum(-1) ** 2,
                               rtol=2e-5)
    # the padding: the last row's second half, and nothing else
    assert pr.expanded_width(d) - pr.content_width(d) == d // 2
    assert not np.asarray(pa[:, -(d // 2):]).any()
    assert np.count_nonzero(np.asarray(pa)) == 5 * pr.content_width(d)
    # the reference's own expansion, in another order, gives the same
    np.testing.assert_allclose(
        (family._second_power(a) * family._second_power(b)).sum(-1),
        (a * b).sum(-1) ** 2, rtol=2e-5)


def test_the_kernel_is_the_plain_form_and_leaves_an_inactive_slot_alone():
    L, B, H, R, d = 2, 3, 2, 2, 128
    W = pr.expanded_width(d)
    ks = jax.random.split(jax.random.key(2), 6)
    state = jax.random.normal(ks[0], (L, B, H, d, W))
    norm = jax.random.normal(ks[1], (L, B, H, W))
    args = (jax.random.normal(ks[3], (B, H, R, d)),
            jax.random.normal(ks[2], (B, H, d)),
            jax.random.normal(ks[4], (B, H, d)),
            jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)) + 4.0),
            jnp.array([1, 0, 1]))
    want = jax.jit(lambda s, z: pr.retention_update(
        s, z, jnp.int32(1), *args, kernel=False))(state, norm)
    got = jax.jit(lambda s, z: pr.retention_update(
        s, z, jnp.int32(1), *args, interpret=True))(state, norm)
    on = np.array([True, False, True])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-5)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(np.asarray(got[2])[on], np.asarray(want[2])[on],
                               rtol=1e-5, atol=2e-3)
    np.testing.assert_array_equal(got[3], want[3])
    for leaf, before in ((got[0], state), (got[1], norm)):
        np.testing.assert_array_equal(leaf[0], before[0])     # other layer
        np.testing.assert_array_equal(leaf[1, 1], before[1, 1])  # inactive


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_an_inactive_lanes_state_is_bit_identical_after_a_step(program):
    """Slot 0 inactive, slot 2 a chunk of no valid lane: whatever they held
    comes back to the bit, while slot 1 moves."""
    eng = engine()
    through_the_programs(eng, PROMPT, 3, slot=0)
    through_the_programs(eng, PROMPT[::-1], 3, slot=2)
    before = jax.tree.map(np.asarray, eng.cache)
    B, C = eng.max_batch, eng.prefill_chunk_size
    if program == "decode":
        _, eng.cache = eng._step(
            eng.params, eng.cache, np.array([3, 4, 5], np.int32),
            np.array([40, 0, 40], np.int32), np.array([False, True, False]))
    else:
        _, eng.cache = eng._chunk_step(
            eng.params, eng.cache, np.full((B, C), 7, np.int32),
            np.array([40, 0, 40], np.int32), np.array([5, 5, 0], np.int32),
            np.array([False, True, True]))
    for name in brumby.CACHE_STATE:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


def test_a_layer_made_alone_is_the_layer_in_the_tree():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    tree = brumby.init_params(key, cfg)
    for l in range(cfg.n_layer):
        jax.tree.map(lambda whole, alone, l=l: np.testing.assert_array_equal(
            np.asarray(whole[l], np.float32), np.asarray(alone, np.float32)),
            tree["blocks"], brumby.init_layer(key, l, cfg))
    gates = np.asarray(tree["blocks"]["attn"]["bg"])
    lo, hi = brumby.GATE_BIAS_RANGE
    assert gates.shape == (2, 2) and (gates >= lo).all() and (gates <= hi).all()
    assert tree["blocks"]["attn"]["wg"].dtype == jnp.float32
    assert tree["blocks"]["mlp"]["wd"].dtype == jnp.bfloat16
    n = sum(a.size for a in jax.tree.leaves(tree))
    assert n == brumby.num_params(cfg)


def test_the_published_sizes_are_the_issues():
    cfg = brumby.BrumbyConfig.preset("brumby-14b", n_layer=8)
    layer = (brumby.num_params(cfg) - 2 * cfg.vocab_size * cfg.d_model
             - cfg.d_model) // 8
    assert round(layer / 1e6, 2) == 330.35
    assert cfg.expanded_width == 8320 and pr.content_width(128) == 8256
    cache = jax.eval_shape(lambda: brumby.init_cache(cfg, 16, 4096))
    assert cache["state"].shape == (8, 16, 8, 128, 8320)
    assert cache["norm"].shape == (8, 16, 8, 8320)
    assert sum(a.size * 4 for a in cache.values()) == 16 * 274_759_680


# -------------------------------------------------------------------- pool

def test_a_snapshot_hit_gives_the_logits_of_a_cold_prefill():
    eng = engine()
    chosen, cold = through_the_programs(eng, PROMPT, 6, slot=0)
    # the donor: the prompt's whole blocks and not a token more, then pooled
    eng.cache = eng._reset_slot(eng.cache, np.int32(1))
    through_the_programs(eng, PROMPT[:32], 1, slot=1)
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 1
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 0    # is there
    n_hit, entry = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(entry) == 1                  # 36 // 8 blocks
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, entry)
    for name in brumby.CACHE_STATE:
        np.testing.assert_array_equal(np.asarray(eng.cache[name][:, 2]),
                                      np.asarray(eng.cache[name][:, 1]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


def test_the_pool_of_snapshots_finds_the_longest_boundary_and_evicts():
    cfg = tiny()
    cache = jax.tree.map(lambda a: a + 1.0, brumby.init_cache(cfg, 2))
    kv = PagedKVCache.for_cache(cache, brumby.CACHE_TOKEN_AXIS, num_blocks=2,
                                block_size=8, state=brumby.CACHE_STATE)
    assert kv.snapshots
    assert {k: v.shape for k, v in kv.pools.items()} == {
        "state": (2, 2, 2, 16, 144), "norm": (2, 2, 2, 144)}
    ids = list(range(100, 140))
    assert kv.store_prefix(ids[:7], cache, 0) == 0          # no whole block
    assert kv.store_prefix(ids[:16], cache, 0) == 1
    assert kv.store_prefix(ids[:32], cache, 1) == 1
    assert list(kv._table) == [h for h, n in chain_hashes(ids, 8)
                               if n in (16, 32)]
    assert kv.peek_prefix_len(ids) == 32 and kv.hits == 0
    assert kv.match_prefix(ids[:31]) == (16, [kv._table[
        chain_hashes(ids, 8)[1][0]]])
    n, entry = kv.match_prefix(ids)
    assert n == 32 and kv.stats()["tokens_reused"] == 48
    # a third prefix takes the least recently matched entry: the 16's
    assert kv.store_prefix(list(range(8)), cache, 0) == 1
    assert kv.stats()["blocks_evicted"] == 1
    assert kv.match_prefix(ids[:24]) == (0, [])
    assert kv.match_prefix(ids)[0] == 32
    out = kv.copy_into_slot(brumby.init_cache(cfg, 2), 1, entry)
    assert float(out["state"][:, 1].min()) == 1.0 == float(out["norm"][:, 1].min())
    assert not np.asarray(out["state"][:, 0]).any()


def test_the_transfers_refuse_a_pool_of_snapshots_by_name():
    eng = engine()
    with pytest.raises(NotImplementedError, match="snapshots"):
        export_prefix(eng.kv, PROMPT)
    with pytest.raises(NotImplementedError, match="snapshots"):
        import_prefix(eng.kv, {"ids": PROMPT, "block_size": 8})
    with pytest.raises(NotImplementedError, match="brumby"):
        eng.export_prefix(prompt_ids=PROMPT)
    with pytest.raises(NotImplementedError, match="brumby"):
        eng.import_prefix({"ids": PROMPT})
    with pytest.raises(NotImplementedError, match="brumby"):
        eng.prefix_model_key
    # rows beside state were refused here until PR 38: a pool of both now
    # (`tests/test_granite_serving.py` holds its rules)
    assert PagedKVCache.for_cache(eng.cache, {"state": 2}, state=("norm",),
                                  num_blocks=2, block_size=8).both


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    for preset in brumby.PRESETS:
        assert serving_family(preset) == ("brumby", brumby,
                                          brumby.BrumbyConfig)
    for name in ("init_params", "resident_params", "resident_specs",
                 "init_cache", "decode_step", "prefill_chunk",
                 "CACHE_TOKEN_AXIS", "CACHE_STATE"):
        assert hasattr(brumby, name), name
    assert brumby.CACHE_TOKEN_AXIS == {}
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        assert "brumby" not in f.read()       # the engine knows the contract


@pytest.mark.parametrize("kwargs,what", [
    (dict(checkpoint="/nowhere"), "checkpoint="),
    (dict(tensor_parallel_size=2), "tensor_parallel_size")])
def test_what_is_gpt2s_refuses_the_family_by_name(kwargs, what):
    with pytest.raises(NotImplementedError, match="brumby") as e:
        LLMEngine(preset="brumby-tiny", **kwargs)
    assert what in str(e.value)


def live_engine(**kwargs):
    kwargs.setdefault("kv_blocks", 3)
    return LLMEngine(preset="brumby-tiny", max_batch=3, max_seq_len=96,
                     seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                     prefill_chunk_size=16, **kwargs)


def greedy_by_hand(prompt, n):
    return through_the_programs(engine(), prompt, n)[0]


def test_the_loop_serves_what_the_programs_give_and_pools_between_chunks():
    """37 tokens: chunks of 16, 16 (the boundary, 32: the snapshot is taken
    here, with 5 tokens still to go) and 5; then the same prompt again and
    one that shares its first 32 tokens, both from the snapshot."""
    want = greedy_by_hand(PROMPT, 8)
    other = PROMPT[:32] + [9, 8, 7]
    want_other = greedy_by_hand(other, 8)
    eng = live_engine()
    try:
        first = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        stats = eng.engine_stats()
        assert first["token_ids"] == want
        assert (stats["slots_reset"], stats["snapshots_pooled"],
                stats["snapshot_hits"]) == (1, 1, 0)
        assert stats["chunk_steps"] == 3 and stats["tokens_prefilled"] == 37
        again = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        shared = eng.generate(prompt_ids=other, max_tokens=8)
        stats = eng.engine_stats()
        assert again["token_ids"] == want
        assert shared["token_ids"] == want_other
        assert (stats["slots_reset"], stats["snapshots_pooled"],
                stats["snapshot_hits"]) == (1, 1, 2)
        assert stats["tokens_prefilled"] == 37 + 5 + 3
        assert eng.kv.stats()["tokens_reused"] == 64
        assert stats["state_bytes_per_slot"] == 2 * 2 * 17 * 144 * 4
        assert "kv_bytes_per_token" not in stats
    finally:
        eng.shutdown()


def test_a_chunk_never_crosses_the_boundary_the_snapshot_is_due_at():
    """Chunks of 16 under a budget of 12 tokens a step: 12, 12, then 8 to
    the boundary at 32 and not 12 past it."""
    eng = live_engine(max_num_batched_tokens=12)
    try:
        out = eng.generate(prompt_ids=PROMPT, max_tokens=4)
        assert out["token_ids"] == greedy_by_hand(PROMPT, 4)
        stats = eng.engine_stats()
        assert stats["snapshots_pooled"] == 1 and stats["chunk_steps"] == 4
        assert eng.generate(prompt_ids=PROMPT, max_tokens=4) == out
        assert eng.engine_stats()["snapshot_hits"] == 1
    finally:
        eng.shutdown()


@pytest.mark.parametrize("first", ["ends by length", "ends by EOS"])
def test_a_reused_slot_gives_what_a_fresh_engine_gives(first):
    """One slot, no pool: the second request takes the slot the first left,
    after its last step (by length) or after the step it over-ran by (an
    EOS is learnt one step late), and reads none of its state."""
    want = greedy_by_hand(PROMPT[::-1], 8)
    eos = greedy_by_hand(PROMPT, 4)[2]

    class Tokens:
        eos_id = eos if first == "ends by EOS" else -1

        def encode(self, text):
            return [1]

        def decode(self, ids):
            return ""

    eng = LLMEngine(preset="brumby-tiny", max_batch=1, max_seq_len=96,
                    seed=SEED, model_overrides=dict(F32),
                    enable_prefix_caching=False, prefill_chunk_size=16,
                    tokenizer=Tokens())
    try:
        out = eng.generate(prompt_ids=PROMPT, max_tokens=6)
        if first == "ends by EOS":
            assert out["token_ids"][-1] == eos and len(out["token_ids"]) == 3
        else:
            assert len(out["token_ids"]) == 6
        got = eng.generate(prompt_ids=PROMPT[::-1], max_tokens=8)
        if eos in want:
            want = want[:want.index(eos) + 1]
        assert got["token_ids"] == want
        stats = eng.engine_stats()
        assert stats["slots_reset"] == 2
        assert stats["overrun_lane_steps"] == (first == "ends by EOS")
    finally:
        eng.shutdown()


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="brumby", preset="brumby-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          model_overrides=dict(F32), kv_blocks=2,
                          kv_block_size=8, prefill_chunk_size=16)
    try:
        body = {"prompt_ids": PROMPT, "max_tokens": 5, "temperature": 0.0,
                "stream": True}
        sid = server(body)["__sse_stream__"]["stream_id"]
        ids, cursor = [], 0
        while True:
            out = server.stream_next(sid, cursor)
            ids += out["token_ids"]
            cursor = out["cursor"]
            if out["done"]:
                break
        assert ids == greedy_by_hand(PROMPT, 5)
        stats = server.stats()
        assert stats["kv_cache"]["blocks_used"] == 1
        assert stats["snapshots_pooled"] == 1
    finally:
        server.engine.shutdown()


def test_gpt2s_engine_has_no_state_and_its_stats_are_what_they_were():
    eng = LLMEngine(preset="gpt2-tiny", max_batch=2, max_seq_len=64)
    try:
        eng.generate(prompt_ids=list(range(1, 40)), max_tokens=3)
        stats = eng.engine_stats()
        assert eng._state_leaves == () and not eng.kv.snapshots
        assert stats["kv_bytes_per_token"] > 0
        assert not {"state_bytes_per_slot", "slots_reset", "snapshots_pooled",
                    "snapshot_hits"} & set(stats)
        assert eng.kv.stats()["blocks_used"] == 2        # 38 // 16 rows
    finally:
        eng.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    eng = engine()
    B, C = eng.max_batch, eng.prefill_chunk_size
    ints, on = np.zeros((B,), np.int32), np.zeros((B,), bool)
    step = eng._step.lower(eng.params, eng.cache, ints, ints, on).as_text(
        debug_info=True)
    chunk = eng._chunk_step.lower(eng.params, eng.cache,
                                  np.zeros((B, C), np.int32), ints, ints,
                                  on).as_text(debug_info=True)
    for text, mixer in ((step, "retention_update"),
                        (chunk, "retention_chunk")):
        for scope in ("attn/retention_project", f"attn/{mixer}", "mlp",
                      "unembed_loss", "embed", "layers"):
            assert scope in text, scope
    assert "retention_chunk" not in step and "retention_update" not in chunk
    reset = eng._reset_slot.lower(eng.cache, np.int32(0)).as_text(
        debug_info=True)
    assert "kv_update" in reset
