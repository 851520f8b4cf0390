"""MiMo-V2 through the serving path on the CPU at a tiny size: sliding-window
layers (a window of 16, 4 key-value heads, a learned sink a head, theta 1e4)
and global ones (2 key-value heads, theta 1e7) whose caches differ in length
and in heads, a key of 24 lanes (the first 8 rotated) held with the positions
on the lanes beside a value of 16 a row, the window layers' rows a ring a slot
that the pool keeps as a snapshot beside the global layers' rows by the block,
two stacks of fused projections, sigmoid-routed experts of which the replica
may hold a share and no shared one, against the plain reference's full
forward pass (no ring, no cache); the rings' plain forms with the keys on the
lanes against the rows' forms; the shares tied to the model; and the preset
through the OpenAI server."""

import dataclasses
import functools
import importlib
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

from families import mimo as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import exaone, lm, mimo, moe, serving_family  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

pieces_module = importlib.import_module("ray_tpu.ops.pieces")

S, G = mimo.SLIDING, mimo.GLOBAL
# the tiny preset in the source's key names, for the reference: G S S G S
# (layer 0 the dense one), a window of 16, 8 query heads, 2 and 4 key-value
# heads, keys of 24 lanes of which 8 rotate and values of 16 (192 : 128 : 64
# is 24 : 16 : 8), 8 experts (all held), 3 a token
MODEL = {"vocab_size": 512, "num_hidden_layers": 5,
         "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1, 1, 0],
         "moe_layer_freq": [0] + [1] * 7,
         "sliding_window": 16, "sliding_window_size": 16,
         "attention_chunk_size": 16, "hidden_size": 64,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_attention_heads": 8, "swa_num_attention_heads": 8,
         "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
         "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
         "swa_v_head_dim": 16, "partial_rotary_factor": 0.334,
         "rope_theta": 10000000, "swa_rope_theta": 10000,
         "rope_scaling": {"rope_type": "default", "type": "default"},
         "attention_value_scale": 0.707, "attention_bias": False,
         "attention_projection_layout": "fused_qkv",
         "add_full_attention_sink_bias": False,
         "add_swa_attention_sink_bias": True,
         "n_routed_experts": 8, "num_experts_per_tok": 3,
         "n_shared_experts": None, "norm_topk_prob": True,
         "routed_scaling_factor": None, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "hidden_act": "silu", "n_group": 1,
         "topk_group": 1, "tie_word_embeddings": False,
         "layernorm_epsilon": 1e-5}
CONFIG = {"model": MODEL,
          "share": {"router_outputs": 8, "first_expert": 0}}
REFERENCE_MODEL = family.reference_model(CONFIG)
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
W = 16
# 70 tokens and 12 more: past five windows' lengths
PROMPT = np.random.default_rng(0).integers(1, 512, 70).tolist()
N_DECODE = 12


def tiny(**extra):
    return mimo.MimoConfig.preset(
        "mimo-tiny", **{**family.program_sizes(CONFIG), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == mimo.MimoConfig.preset("mimo-tiny")
    cfg = tiny()
    assert cfg.sliding_window < cfg.max_seq_len
    assert cfg.layer_types == (G, S, S, G, S) and cfg.n_dense_layer == 1
    assert (cfg.head_dim, cfg.v_head_dim, cfg.rotary_dim) == (24, 16, 8)
    assert cfg.n_experts % 4 == 0           # experts that divide into shares


def test_the_published_sizes_are_the_issues():
    cfg = mimo.MimoConfig.preset("mimo-v2.5")
    assert (cfg.n_layer, cfg.d_model, cfg.vocab_size) == (48, 4096, 152576)
    assert [l for l, t in enumerate(cfg.layer_types) if t == G] == [
        0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (cfg.n_head, cfg.n_kv_head, cfg.swa_n_kv_head, cfg.head_dim,
            cfg.v_head_dim, cfg.rotary_dim) == (64, 4, 8, 192, 128, 64)
    assert cfg.rotary_dim == int(192 * 0.334)
    assert (cfg.sliding_window, cfg.rope_theta, cfg.swa_rope_theta,
            cfg.value_scale, cfg.norm_eps) == (128, 1e7, 1e4, 0.707, 1e-5)
    assert (cfg.d_ff, cfg.d_ff_expert, cfg.n_dense_layer) == (16384, 2048, 1)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == (256, 256, 8, "sigmoid", True, 1.0)
    # the fused projections' shapes, a kind
    assert mimo._qkv_widths(cfg, G) == (12288, 768, 512)
    assert sum(mimo._qkv_widths(cfg, G)) == 13568
    assert sum(mimo._qkv_widths(cfg, S)) == 14848
    # the count that holds the reading of the layers up: the model's name
    whole = mimo.num_params(cfg)
    assert round(whole / 1e9, 1) == 308.8
    a_token = whole - 47 * (256 - 8) * 3 * 4096 * 2048
    assert round(a_token / 1e9, 1) == 15.4
    # the cell's cut: 2.22 B parameters, 4.45 GB with the routers float32
    cut = dataclasses.replace(cfg, layer_types=cfg.layer_types[:7],
                              experts_held=8, vocab_size=19072)
    assert cut.layer_types == (G, S, S, S, S, G, S)
    assert round(mimo.num_params(cut) / 1e9, 2) == 2.22
    float32 = 6 * (4096 * 256 + 256) + 15 * 4096 + 5 * 64
    assert 4.45e9 < 2 * mimo.num_params(cut) + 2 * float32 < 4.46e9
    # the two gauges by hand count: two global layers x 4 heads x (192 +
    # 128) lanes x 2 B a token, five rings of 128 positions x 8 heads a slot
    cache = jax.eval_shape(lambda: mimo.init_cache(cut, 64, 24576))
    assert cache["k"].shape == (2, 64, 4, 192, 24576)
    assert cache["v"].shape == (2, 64, 4, 24576, 128)
    assert cache["wk"].shape == (5, 64, 8, 192, 128)
    assert cache["wv"].shape == (5, 64, 8, 128, 128)
    tokens = sum(cache[n].size * 2 for n in mimo.CACHE_TOKEN_AXIS)
    assert tokens // (64 * 24576) == 5120 == 2 * 4 * (192 + 128) * 2
    state = sum(cache[n].size * 2 for n in mimo.CACHE_STATE)
    assert state // 64 == 3_276_800 == 5 * 8 * 128 * (192 + 128) * 2
    with pytest.raises(NotImplementedError, match="one chip"):
        mimo.resident_specs(cfg)


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 36)
    eng = LLMEngine(preset="mimo-tiny", max_batch=3, max_seq_len=96,
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


def layer_of(params, l, cfg):
    """Layer l of the tree a replica holds, as `init_layer` gives it."""
    E, n = cfg.experts_held, cfg.n_dense_layer
    part, i = mimo._attn_part(cfg.layer_types[l]), mimo._entry(cfg, l)
    out = {part: jax.tree.map(lambda a: a[i], params[part])}
    if l < n:
        return {**out, "dense": jax.tree.map(lambda a: a[l],
                                             params["dense"])}
    j = l - n
    return {**out, "moe": jax.tree.map(lambda a: a[j], params["moe"]),
            "experts": jax.tree.map(lambda a: a[j * E:(j + 1) * E],
                                    params["experts"])}


def reference_logits(cfg, row, at, degrade=None, model=REFERENCE_MODEL):
    key = jax.random.key(SEED)
    return family.Reference(
        model, lambda l: mimo.init_layer(key, l, cfg),
        mimo.init_ends(key, cfg), degrade).logits([row], [at])[0]


def test_a_layer_of_the_tree_is_the_layer_made_alone():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    params = mimo.init_params(key, cfg)
    # two stacks of fused projections: 8 x 24 + 2 x 24 + 2 x 16 columns in
    # a global layer, 8 x 24 + 4 x 24 + 4 x 16 in a sliding one
    assert params["attn_g"]["wqkv"].shape == (2, 64, 272)
    assert params["attn_s"]["wqkv"].shape == (3, 64, 352)
    assert params["attn_s"]["sink"].shape == (3, 8)
    assert params["attn_s"]["sink"].dtype == jnp.float32
    assert "sink" not in params["attn_g"]
    assert np.asarray(params["attn_s"]["sink"]).std() > 0.5   # not zero
    assert params["attn_g"]["wo"].shape == (2, 8 * 16, 64)
    assert params["dense"]["w_in"].shape == (1, 64, 256)
    assert params["moe"]["router"].shape == (4, 64, 8)
    assert params["moe"]["router"].dtype == jnp.float32
    assert params["experts"]["wg"].shape == (32, 64, 32)
    for l in range(cfg.n_layer):
        alone, held = mimo.init_layer(key, l, cfg), layer_of(params, l, cfg)
        assert set(alone) == set(held)
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(held)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == mimo.num_params(cfg)


# Float32 compute against the float32 reference: the same sums in another
# order (attention through rows and rings, the keys' positions on the lanes,
# against one pass over the whole sequence under a mask with the sink a
# column; the experts' rows sorted and summed by gate against a loop over the
# experts): 1.2e-7 on logits of spread 0.16 here, whatever the chunks. bf16
# compute against it (the reference reads the same bf16 weights, holds k and
# v through bfloat16 as the program's cache does, and a product's activation
# goes as the two bf16 pieces that add up to it, the float32 q and its
# probabilities among them, so what is left is the pieces' own remainder):
# TOLERANCE_READINGS. Against the float32 program no sink reads 5.7e-3, a
# sink that weighs the query's own value 2.5e-2, a window one short or one
# over 5.0e-4 and 5.3e-4, all 24 lanes rotated 9.7e-5, the thetas swapped
# 4.1e-5, no value scale 2.2e-3, a global layer's heads grouped the sliding
# layers' way 2.6e-3, a stream through bfloat16 3.1e-3, gates not
# renormalised 2.1e-3 and the reference's own products of one piece 1.5e-5:
# 25 to 40,000 times what the program reads (the CPU's readings of this
# file's PROMPT, PR 62).
FLOAT32_LOGIT_TOLERANCE = 6e-7
BF16_LOGIT_TOLERANCE = 3e-5
REFUSED_ON_THE_CPU = tuple(d for d in family.DEGRADE if d)


@pytest.mark.parametrize("compute,tolerance,chunk", [
    (F32, FLOAT32_LOGIT_TOLERANCE, 16), (F32, FLOAT32_LOGIT_TOLERANCE, 7),
    (F32, FLOAT32_LOGIT_TOLERANCE, 24), (BF16, BF16_LOGIT_TOLERANCE, 7),
    (BF16, BF16_LOGIT_TOLERANCE, 16)],
    ids=["float32-16", "float32-7", "float32-24", "bfloat16-7",
         "bfloat16-16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, rows and rings, against
    the plain reference's full forward pass (no ring, no cache, a banded or
    a causal mask): the logits at every generated position, past five
    windows' lengths, whatever the chunks' boundaries. 70 tokens in chunks
    of 16 (the window: a chunk overwrites the whole ring), of 7 (which
    divides neither), of 24 (longer than the window: a chunk crosses the
    ring's wrap and its first lanes' rows are never written)."""
    eng = engine(compute, chunk=chunk)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    model = {**REFERENCE_MODEL,
             "rows": "float32" if compute is F32 else "bfloat16"}
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))), model=model)
    assert got.shape == want.shape == (N_DECODE, 512)
    assert np.abs(got - want).max() <= tolerance
    if compute is F32:
        assert chosen == want.argmax(axis=-1).tolist()
        # no greedy reply that repeats one token (granite's lesson; at 512
        # ids a greedy chain closes a cycle of some six tokens)
        assert len(set(chosen)) >= N_DECODE // 2


@functools.lru_cache(maxsize=None)
def the_float32_programs_reply():
    """(the config, the greedy tokens, the logits) of the float32 engine on
    `PROMPT`: once for the ten degradations."""
    eng = engine()
    return (eng.cfg, *through_the_programs(eng, PROMPT, N_DECODE))


@pytest.mark.parametrize("degrade", REFUSED_ON_THE_CPU)
def test_a_degraded_reference_is_refused_by_the_float32_tolerance(degrade):
    """No sink, a sink that weighs a value, a window of 15 or 17, all 24
    lanes rotated, the two thetas swapped, no value scale, a global layer's
    heads grouped by the sliding layers' count, gates not renormalised, a
    bfloat16 stream, one-piece products: each is another function, and the
    tolerance the program meets refuses it."""
    cfg, chosen, got = the_float32_programs_reply()
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    off = reference_logits(cfg, row, at, degrade)
    assert np.abs(got - off).max() > 10 * FLOAT32_LOGIT_TOLERANCE


def test_products_of_one_piece_are_refused_by_the_bfloat16_tolerance(
        monkeypatch):
    """`ops/pieces.py` giving the activation's rounding and nothing for what
    the rounding left: every `lm.dot`, the queries and probabilities at the
    rows and the experts' rows as one bf16 piece. The bf16 tolerance, which
    the two pieces meet, refuses it."""
    whole = pieces_module.pieces

    def rounding_alone(x, dtype, n=2, axis=0):
        both = whole(x, dtype, n, axis)
        keep = jnp.arange(n).reshape((n,) + (1,) * (both.ndim - axis - 1))
        return jnp.where(keep == 0, both, jnp.zeros_like(both))

    for module in (pieces_module, moe):
        monkeypatch.setattr(module, "pieces", rounding_alone)
    eng = engine(BF16)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(
        eng.cfg, row, list(range(len(PROMPT) - 1, len(row))),
        model={**REFERENCE_MODEL, "rows": "bfloat16"})
    assert np.abs(got - want).max() > 3 * BF16_LOGIT_TOLERANCE


# ------------------------------------------------------------- the rings

def test_a_ring_of_keys_on_the_lanes_is_written_as_the_rows_form_writes():
    """`lm.ring_write_slot` into a ring [L,B,G,d,W] against the same lanes
    into its transpose [L,B,G,W,d] (which `tests/test_exaone_serving.py`
    holds to a loop): a chunk that crosses the ring's wrap, one longer than
    the ring, one whose slot starts mid-ring, and one of no valid lane."""
    rng = np.random.default_rng(0)
    ring = rng.standard_normal((2, 3, 4, 24, W)).astype(np.float32)
    for pos, n, M in ((10, 9, 12), (3, 24, 24), (21, 5, 8), (40, 0, 8)):
        val = rng.standard_normal((M, 4, 24)).astype(np.float32)
        got = np.asarray(lm.ring_write_slot(
            jnp.asarray(ring), 1, 2, jnp.asarray(val), pos, n))
        want = np.asarray(lm.ring_write_slot(
            jnp.asarray(ring.swapaxes(3, 4)), 1, 2, jnp.asarray(val), pos,
            n)).swapaxes(3, 4)
        np.testing.assert_array_equal(got, want)
        assert (got != ring).any() == bool(n)


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
def test_the_band_of_a_chunk_over_ring_and_chunk_against_a_loop(sink):
    """`lm.gqa_attend_ring` with the keys' ring on the lanes, keys of 24
    lanes and values of 16: lanes at 21.. against a ring whose newest
    position is 20, and against a ring of a sequence 6 long whose other rows
    are stale: each lane sees the 16 positions that end at its own, no stale
    row, and (with one) its head's sink in the denominator."""
    rng = np.random.default_rng(1)
    dk, dv, M = 24, 16, 10
    b = rng.standard_normal((2, 3)).astype(np.float32) + 1.0
    for pos in (21, 6):
        keys = rng.standard_normal((pos + M, 2, dk)).astype(np.float32)
        vals = rng.standard_normal((pos + M, 2, dv)).astype(np.float32)
        ring_k = rng.standard_normal((1, 1, 2, dk, W)).astype(np.float32) * 9
        ring_v = rng.standard_normal((1, 1, 2, W, dv)).astype(np.float32) * 9
        for t in range(max(0, pos - W), pos):
            ring_k[0, 0, :, :, t % W], ring_v[0, 0, :, t % W] = (keys[t],
                                                                  vals[t])
        q = rng.standard_normal((2, 3 * M, dk)).astype(np.float32)
        at = np.broadcast_to(pos + np.tile(np.arange(M), 3), (2, 3 * M))
        got = np.asarray(lm.gqa_attend_ring(
            jnp.asarray(q), jnp.asarray(ring_k), jnp.asarray(ring_v), 0, 0,
            jnp.asarray(keys[pos:]), jnp.asarray(vals[pos:]),
            jnp.asarray(at), pos, 0.5, jnp.float32,
            sink=jnp.repeat(jnp.asarray(b), M, axis=1) if sink else None))
        assert got.shape == (2, 3 * M, dv)
        for g in range(2):
            for i in range(3 * M):
                p = at[g, i]
                seen = np.arange(max(0, p - W + 1), p + 1)
                s = keys[seen, g] @ q[g, i] * 0.5
                top = max(s.max(), b[g, i // M]) if sink else s.max()
                w = np.exp(s - top)
                total = w.sum() + (np.exp(b[g, i // M] - top) if sink else 0)
                np.testing.assert_allclose(got[g, i], (w / total)
                                           @ vals[seen, g], atol=2e-6)


def test_a_stale_ring_from_an_earlier_request_is_masked_by_age():
    """A slot that served a longer request, taken by a new one with no
    reset (the rings and the rows hold the old request's values): the new
    request's logits are those of a fresh slot, from its first token on."""
    eng = engine()
    _, fresh = through_the_programs(eng, PROMPT[:21], 4, slot=0,
                                    forced=[1, 2, 3, 4])
    through_the_programs(eng, PROMPT[::-1], 9, slot=1)
    assert np.asarray(eng.cache["wk"])[:, 1].all()      # every row written
    _, stale = through_the_programs(eng, PROMPT[:21], 4, slot=1,
                                    forced=[1, 2, 3, 4])
    np.testing.assert_allclose(stale, fresh, atol=FLOAT32_LOGIT_TOLERANCE)
    # one token at a time from position 0: the decode program alone, at
    # positions 0, 1, .. past the ring's first wrap
    eng.cache = {**eng.cache, "wk": eng.cache["wk"] + 5.0,
                 "wv": eng.cache["wv"] - 5.0}
    lanes = np.arange(3) == 2
    rows = []
    for pos, token in enumerate(PROMPT[:W + 3]):
        logits, eng.cache = eng._step(
            eng.params, eng.cache, np.where(lanes, token, 0).astype(np.int32),
            np.where(lanes, pos, 0).astype(np.int32), lanes)
        rows.append(np.asarray(logits[2]))
    want = reference_logits(eng.cfg, PROMPT[:W + 3], list(range(W + 3)))
    np.testing.assert_allclose(np.stack(rows), want,
                               atol=FLOAT32_LOGIT_TOLERANCE)


# ------------------------------------------------------------- the pool

def test_a_pool_hit_restores_rows_and_rings_and_gives_the_cold_logits():
    """The global layers' rows by the block (the keys' along their lanes,
    the values' along their rows) and the three rings as a
    snapshot at position 40 (mid-ring: 40 mod 16 = 8), under one hash, into
    another slot that held another sequence; then the rest of the prompt:
    what a cold prefill of the whole prompt gives, from the next token
    on."""
    eng = engine(chunk=8)
    assert eng.family == "mimo" and eng.kv.both
    assert eng._state_leaves == ("wk", "wv")
    chosen, cold = through_the_programs(eng, PROMPT, 6, slot=0)
    through_the_programs(eng, PROMPT[:40], 1, slot=1)
    assert eng.kv.store_prefix(PROMPT[:40], eng.cache, 1) == 1
    n_hit, entry = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 40
    through_the_programs(eng, PROMPT[::-1], 2, slot=2)
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, entry)
    for name, axis in mimo.CACHE_TOKEN_AXIS.items():
        leaf = np.moveaxis(np.asarray(eng.cache[name], np.float32), axis, 2)
        assert leaf.shape[:2] == (2, 3)                 # the global layers
        assert leaf[:, 1, :40].any()
        np.testing.assert_array_equal(leaf[:, 2, :40], leaf[:, 1, :40])
    for name, axis in (("wk", 4), ("wv", 3)):
        leaf = np.asarray(eng.cache[name], np.float32)
        assert leaf.shape[0] == 3 and leaf.shape[axis] == W
        np.testing.assert_array_equal(leaf[:, 2], leaf[:, 1])
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


@pytest.mark.parametrize("case", [
    "one-prefills", "all-prefill-whole-chunks", "two-together-two-alone"])
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    assert case in LANES_OF_A_STEP
    """The chunk program, whose MLPs take every valid lane of the step in
    one call (`lm.all_lanes`), against `decode_step`: whoever prefills, and
    when the lanes are more than a call's rows."""
    chunk_step_against_decode(mimo, tiny(**F32), case,
                              FLOAT32_LOGIT_TOLERANCE, 1e-6)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_an_inactive_lanes_cache_is_bit_identical_after_a_step(program):
    """Slot 0 inactive, slot 2 a chunk of no valid lane: their rows and
    their rings come back to the bit, while slot 1 moves."""
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
    leaves = set(mimo.CACHE_TOKEN_AXIS) | set(mimo.CACHE_STATE)
    assert set(before) == leaves | {"counts"}
    for name in leaves:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any(axis=(1, 2, 3)).all()


def test_both_programs_count_the_positions_the_rings_rows_and_the_pairs():
    """`attended_positions` and `read_positions` once a step (a global
    layer's), `window_rows_read` over the three sliding layers (a lane at
    position p reads min(p + 1, 16) rows of each), the pairs 3 a lane a
    sparse layer, of which there are four."""
    eng = engine()
    through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(mimo.COUNTS, row)) for row in np.asarray(
        eng.cache["counts"]).tolist())
    assert chunk["attended_positions"] == sum(range(1, 71))
    assert decode["attended_positions"] == 71 + 72
    assert chunk["window_rows_read"] == 3 * (sum(range(1, 17)) + 54 * 16)
    assert decode["window_rows_read"] == 3 * 2 * 16
    assert decode["read_positions"] == 2 * 96
    # five chunk steps of 16, 16, 16, 16, 6: the first lanes all T, the
    # further lanes a block (the whole of these 96 positions)
    assert chunk["read_positions"] == 5 * 96 + 5 * 96
    assert decode["expert_rows_all"] == 2 * 4 * 3       # steps, layers, K
    assert decode["expert_layer_steps"] == 2 * 4
    assert chunk["expert_rows_all"] == 70 * 4 * 3
    assert chunk["expert_rows"] == chunk["expert_rows_all"]   # all held


# ------------------------------------------------------------- the share

def sparse_mlp(cfg, key, x, first, held):
    """Layer 1's MLP (router over all 8, the experts first..first + held
    held) on the stream x, and what it counted."""
    share = dataclasses.replace(cfg, first_expert=first, experts_held=held)
    layer = mimo.init_layer(key, 1, share)
    given = jnp.zeros((cfg.n_experts,), jnp.int32)
    out, given = mimo._expert_mlp(
        x, layer["moe"], layer["experts"], 0, share, given,
        jnp.ones(x.shape[:2], bool))
    return out - x, dict(zip(mimo.COUNTS, np.asarray(
        mimo._expert_counts(given, share)).tolist())), layer


@pytest.mark.parametrize("compute", [F32, BF16], ids=["float32", "bfloat16"])
def test_the_four_shares_of_a_sparse_layer_add_up_to_the_uncut_layer(
        compute):
    """The share tied to the model (the cell's is 32 shares of 8 experts;
    the tiny preset's 4 of 2): what the four shares give (`first_expert` 0,
    2, 4, 6 of 8 experts, two held each; there is no shared expert, so
    nothing is counted twice) adds up to what the uncut reference gives for
    the whole layer, and every expert is in one of them."""
    cfg = tiny(**compute)
    key = jax.random.key(SEED)
    x = jax.random.normal(jax.random.key(1), (2, 6, 64), jnp.float32)
    whole, counts, layer = sparse_mlp(cfg, key, x, 0, 8)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    h = family._rms_norm(x, f32["moe"]["norm"]["scale"], 1e-5).reshape(12, 64)
    with jax.default_matmul_precision("highest"):
        want, chosen = family._expert_block(h, f32["moe"], f32["experts"],
                                            REFERENCE_MODEL)
    want, chosen = np.asarray(want).reshape(2, 6, 64), np.asarray(chosen)
    assert np.abs(want).max() > 1e-3
    tolerance = 2e-6 if compute is F32 else 1e-4
    np.testing.assert_allclose(whole, want, atol=tolerance)
    assert counts["expert_rows_all"] == counts["expert_rows"] == 2 * 6 * 3
    parts, held_rows, held = [], [], set()
    for first in (0, 2, 4, 6):
        part, counts, mine = sparse_mlp(cfg, key, x, first, 2)
        # a share holds the very experts the whole layer has there
        np.testing.assert_array_equal(
            np.asarray(mine["experts"]["wu"], np.float32),
            np.asarray(layer["experts"]["wu"][first:first + 2], np.float32))
        parts.append(part)
        held |= {first, first + 1}
        assert counts["expert_rows_all"] == 36
        held_rows.append(counts["expert_rows"])
        assert counts["expert_rows"] == (
            (chosen >= first) & (chosen < first + 2)).sum()
    assert held == set(range(cfg.n_experts))
    assert sum(held_rows) == 36 and min(held_rows) > 0
    np.testing.assert_allclose(sum(parts), want, atol=4 * tolerance)


def test_a_share_of_the_experts_serves_the_references_logits():
    """The engine told that it holds experts 4..5 of the 8: the logits of
    the reference that is given the same share, and not the whole
    model's."""
    share = {"first_expert": 4, "experts_held": 2}
    eng = engine(compute={**F32, **share})
    chosen, got = through_the_programs(eng, PROMPT, 6)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    model = {**REFERENCE_MODEL, "n_routed_experts": 2, "first_expert": 4}
    want = reference_logits(eng.cfg, row, at, model=model)
    assert np.abs(got - want).max() <= FLOAT32_LOGIT_TOLERANCE
    whole = reference_logits(tiny(**F32), row, at)
    assert np.abs(got - whole).max() > 100 * FLOAT32_LOGIT_TOLERANCE
    counts = eng.engine_stats()
    assert 0 < counts["moe_expert_rows"] < counts["moe_expert_rows_all"]
    assert counts["moe_expert_rows_all"] == 3 * 4 * (70 + 5)


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    name, module, config = serving_family("mimo-v2.5")
    assert (name, module, config) == ("mimo", mimo, mimo.MimoConfig)
    assert mimo.CACHE_TOKEN_AXIS == {"k": 4, "v": 3}
    assert mimo.CACHE_STATE == ("wk", "wv")
    assert mimo.COUNTS == exaone.COUNTS


def test_the_loop_serves_what_the_programs_give_with_prefix_caching_on():
    """Through `generate`: greedy tokens of the running loop are the
    programs' own by hand, and a second request over the same prefix is a
    hit of rows and of the rings' snapshot with the same reply."""
    eng = LLMEngine(preset="mimo-tiny", max_batch=3, max_seq_len=96,
                    seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                    kv_blocks=36, prefill_chunk_size=16)
    try:
        first = eng.generate(prompt_ids=PROMPT, max_tokens=6,
                             temperature=0.0)
        again = eng.generate(prompt_ids=PROMPT, max_tokens=6,
                             temperature=0.0)
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    by_hand, _ = through_the_programs(engine(), PROMPT, 6)
    assert first["token_ids"] == again["token_ids"] == by_hand
    assert eng.kv.stats()["tokens_reused"] == 64        # 69 // 8 blocks
    assert eng.kv.stats()["rows_without_snapshot_tokens"] == 0
    assert (stats["snapshots_pooled"], stats["snapshot_hits"]) == (1, 1)
    # two global layers x 2 heads x (24 + 16) lanes x 4 bytes a token,
    # three rings of 16 positions x 4 heads a slot
    assert stats["kv_bytes_per_token"] == 2 * 2 * (24 + 16) * 4
    assert stats["state_bytes_per_slot"] == 3 * 4 * 16 * (24 + 16) * 4
    # live positions over the rows the slots hold, a step: one slot of
    # three at 1..75 of 96 positions
    assert stats["rows_live_pct"] == pytest.approx(
        100 * stats["positions_attended"] / (stats["engine_steps"] * 3 * 96))
    assert 5 < stats["rows_live_pct"] < 30
    assert stats["step_counts"]["chunk"]["window_rows_read"] > 0


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="mimo", preset="mimo-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          model_overrides=dict(F32), kv_blocks=24,
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
        assert ids == through_the_programs(engine(), PROMPT, 5)[0]
        assert server.stats()["kv_cache"]["snapshots_used"] == 1
    finally:
        server.engine.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    cfg = tiny()
    params = jax.eval_shape(lambda: mimo.init_params(
        jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: mimo.init_cache(cfg, 2, 96))
    ints, flags = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    decode = jax.jit(lambda p, c: mimo.decode_step(
        p, c, ints, ints, flags, cfg)).lower(params, cache).as_text(
            debug_info=True)
    chunk = jax.jit(lambda p, c: mimo.prefill_chunk(
        p, c, jnp.zeros((2, 16), jnp.int32), ints, ints + 9, flags,
        cfg)).lower(params, cache).as_text(debug_info=True)
    for scope in ("attn/gqa_project", "attn/kv_update", "attn/gqa_attend",
                  "attn/swa_attend", "mlp/mlp_dense", "mlp/moe_router",
                  "mlp/moe_dispatch", "mlp/moe_experts", "layers"):
        assert scope in decode and scope in chunk, scope
    assert "moe_shared" not in decode + chunk           # there is none
