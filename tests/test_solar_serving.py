"""Solar Open2 through the serving path on the CPU at a tiny size: the two
forms of the delta rule at a write strength up to 2 beside gated, un-rotated
grouped-head attention over keys and values by head (the plain form a lane,
a loop over blocks of positions for a chunk's further lanes) and a share of
the routed experts, against the plain reference's full forward pass; the
kernels at this family's sizes against their plain forms; the pool's
snapshot and rows of `k`, `v` into another slot; the shares tied to the
model; and the preset through the OpenAI server."""

import dataclasses
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

from families import solar as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import kimi, lm, moe, serving_family, solar  # noqa: E402
from ray_tpu.ops import kda_update as ku  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

em = importlib.import_module("ray_tpu.ops.expert_mlp")
rw = importlib.import_module("ray_tpu.ops.rows_write")

# the tiny preset in the source's key names, for the reference: two periods
# (softmax, KDA, KDA, KDA) x 2, 16 experts all held, F = 40
MODEL = {"vocab_size": 512, "num_hidden_layers": 8,
         "gqa_layers": [0, 4], "first_k_dense_replace": 0,
         "hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 40, "n_routed_experts": 16,
         "num_experts_per_tok": 3, "n_shared_experts": 1,
         "norm_topk_prob": True, "routed_scaling_factor": 1,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "use_rope": False, "use_gqa_gate": True,
         "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
         "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
         "linear_attn_config": {"head_dim": 16, "num_heads": 2,
                                "num_kv_heads": None,
                                "short_conv_kernel_size": 4}}
CONFIG = {"model": MODEL,
          "assumed_sizes": {"kda_gate_rank": 8, "router_scoring": "sigmoid"},
          "share": {"router_outputs": 16, "first_expert": 0}}
REFERENCE_MODEL = family.reference_model(CONFIG)
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return kimi.KimiConfig.preset(
        "solar-tiny", **{**family.program_sizes(CONFIG), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == kimi.KimiConfig.preset("solar-tiny")
    assert tiny().layer_types == ("gqa", "kda", "kda", "kda") * 2
    # F is no multiple of a column tile, nor of a lane tile
    assert tiny().d_ff_expert % 128 and tiny().n_experts // 4 == 4


def test_the_published_sizes_are_the_issues():
    cfg = kimi.KimiConfig.preset("solar-open2-250b")
    assert cfg.layer_types == ("gqa", "kda", "kda", "kda") * 12
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.gqa_head_dim) == (
        4096, 64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (64, 128, 4)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff_expert) == (
        320, 8, 1280)
    # the whole model, with the 64 columns of padding beside W_b a KDA layer
    assert kimi.num_params(cfg) - 36 * 64 * 4096 == 250_288_105_216
    one_chip = dataclasses.replace(
        cfg, n_layer=4, gqa_layers=(0,), experts_held=40, vocab_size=24576)
    assert kimi.num_params(one_chip) - 3 * 64 * 4096 == 3_308_377_920
    cache = jax.eval_shape(lambda: solar.init_cache(one_chip, 1, 25600))
    assert set(cache) == {"kda", "conv", "k", "v", "counts"}
    state = sum(cache[n].size * 4 for n in solar.CACHE_STATE)
    rows = sum(cache[n].size * 2 for n in solar.CACHE_TOKEN_AXIS) // 25600
    assert (state, rows) == (13_467_648, 4096)
    for name, axis in solar.CACHE_TOKEN_AXIS.items():
        assert cache[name].shape[axis] == 25600


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    eng = LLMEngine(preset="solar-tiny", max_batch=3, max_seq_len=96,
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


def reference_logits(cfg, row, at, degrade=None, model=REFERENCE_MODEL):
    key = jax.random.key(SEED)
    ref = family.Reference(model, lambda l: kimi.init_layer(key, l, cfg),
                           kimi.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


# Float32 compute against the float32 reference: the same sums in another
# order (the chunked form's state carried across runs of 16 lanes and chunks
# and the recurrence's across steps, against one recurrence over the whole
# sequence; the triangular solve with b up to 2 as a product of four
# matrices; attention a block of positions at a time with a running maximum
# against the plain softmax over the sequence): 1e-6 on logits of spread 0.16
# here. bf16 compute against it (the reference reads the same bf16 weights,
# and a product's activation goes as the two bf16 pieces that add up to it,
# so what is left is the rounding of q, the cached k and v and attention's
# weights in two layers): 1e-4 over chunk sizes. A state held in bfloat16
# moves the float32 logits by 2e-4 at their worst position, a layer without
# its gate by 2e-3, b without its factor 2 by 3e-2: 100 to 10,000 times what
# the float32 program reads, and the float32 tolerance tells each apart. The
# scores through bfloat16 move them by 2e-7, under that tolerance: at 37
# positions a softmax is all but flat, and it is at 25k positions, on the
# chip, that the cell's own check has to refuse it (`families/solar.py`,
# PERF.md PR 49).
FLOAT32_LOGIT_TOLERANCE = 4e-6
BF16_LOGIT_TOLERANCE = 3e-4
REFUSED_ON_THE_CPU = ("bfloat16_state", "no_gate", "b_in_0_1")


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk, monkeypatch):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no cache, no chunks, the recurrence a
    token at a time): the logits at every generated position, whatever the
    chunks' boundaries. 37 tokens in chunks of 16 and of 7 (which do not
    divide them), of 8 (which ends on a block) and of 64 (one chunk, four
    runs of the solve); attention's blocks are 40 positions of the 96, so
    the last one starts early and a chunk's lanes cross a block's end."""
    monkeypatch.setattr(lm, "GQA_BLOCK", 40)
    eng = engine(compute, chunk=chunk)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))))
    assert got.shape == want.shape == (N_DECODE, 512)
    assert np.abs(got - want).max() <= tolerance
    if compute is F32:
        assert chosen == want.argmax(axis=-1).tolist()


@pytest.mark.parametrize("degrade", REFUSED_ON_THE_CPU)
def test_a_degraded_reference_is_refused_by_the_float32_tolerance(degrade):
    eng = engine()
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    off = reference_logits(eng.cfg, row, at, degrade)
    assert np.abs(got - off).max() > 10 * FLOAT32_LOGIT_TOLERANCE


def test_scores_through_bfloat16_are_another_function():
    """Too near at 37 positions for the tolerance above, and not the same:
    the reference's own scores at T = 256 move."""
    key = jax.random.key(3)
    cfg = tiny(**F32)
    p = jax.tree.map(np.asarray, kimi.init_layer(key, 0, cfg))
    x = jax.random.normal(jax.random.key(4), (1, 256, 64), jnp.float32)
    exact = family.reference_layer(x, p, REFERENCE_MODEL)
    off = family.reference_layer(x, p, REFERENCE_MODEL, "bfloat16_scores")
    assert 1e-7 < np.abs(np.asarray(exact - off)).max() < 1e-2


# ---------------------------------------------------------- the delta rule

def test_the_kernel_at_64_heads_is_the_plain_form():
    """`ops/kda_update.py` interpreted at the published head count (eight
    groups of the columns' operand) and a write strength up to 2, against
    `_update_plain`; an inactive slot and the other layer bit for bit."""
    L, B, H, N = 2, 2, 64, 128
    ks = jax.random.split(jax.random.key(2), 6)
    state = jax.random.normal(ks[0], (L, B, H, N, N))
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    args = (jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, N)) + 3.0),
            unit(jax.random.normal(ks[2], (B, H, N))),
            unit(jax.random.normal(ks[3], (B, H, N))) * N ** -0.5,
            jax.random.normal(ks[4], (B, H, N)),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)) + 2.0),
            jnp.array([1, 0]))
    assert float(args[4].max()) > 1.9
    want = jax.jit(lambda s: ku._update_plain(s, jnp.int32(1), *args))(state)
    got = jax.jit(lambda s: ku.kda_update(
        s, jnp.int32(1), *args, interpret=True))(state)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got[1])[0], np.asarray(want[1])[0],
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_array_equal(got[0][0], state[0])        # other layer
    np.testing.assert_array_equal(got[0][1, 1], state[1, 1])  # inactive


def test_the_chunked_form_is_the_recurrence_at_b_near_2_and_strong_decay():
    """A run of 16 lanes with b in (1.8, 2) and decay rates to 1.6 a token
    (and, apart, to 30): N = -A has entries up to 2 where Kimi's have 1, its
    powers grow, and the product (I + N)(I + N^2)(I + N^4)(I + N^8) is still
    the exact inverse because N^16 = 0 whatever its size."""
    m, H, N = 16, 2, 16
    ks = jax.random.split(jax.random.key(4), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    # keys that are near one another: the entries of A are then near b
    shared = jax.random.normal(ks[0], (1, H, N))
    k = unit(shared + 0.3 * jax.random.normal(ks[1], (m, H, N)))
    q = unit(jax.random.normal(ks[0], (m, H, N)))
    v = jax.random.normal(ks[2], (m, H, N))
    b = 1.8 + 0.2 * jax.random.uniform(ks[4], (m, H))
    s0 = jax.random.normal(ks[5], (H, N, N))
    for most, tolerance in ((1.6, 2e-4), (30.0, 2e-4)):
        log_a = -most * jax.random.uniform(ks[3], (m, H, N))
        o, s = kimi._delta_chunk(q, k, v, log_a, b, s0)
        state, outs = s0[None, None], []
        for t in range(m):
            state, o_t = ku.kda_update(
                state, jnp.int32(0), jnp.exp(log_a[t])[None], k[t][None],
                q[t][None], v[t][None], b[t][None], jnp.array([1]),
                kernel=False)
            outs.append(o_t[0])
        assert np.isfinite(np.asarray(o)).all()
        scale = float(np.abs(np.asarray(state)).max())
        np.testing.assert_allclose(o, jnp.stack(outs),
                                   atol=tolerance * max(1.0, scale))
        np.testing.assert_allclose(s, state[0, 0],
                                   atol=tolerance * max(1.0, scale))


# ------------------------------------------------------ grouped-head rows

@pytest.mark.parametrize("positions_last", [True, False],
                         ids=["granites-leaf", "solars-leaf"])
def test_rows_write_is_its_plain_form_either_way_round(positions_last):
    L, B, G, d, T = 2, 3, 2, 128, 256
    shape = (L, B, G, d, T) if positions_last else (L, B, G, T, d)
    c = jax.random.normal(jax.random.key(0), shape).astype(jnp.bfloat16)
    val = jax.random.normal(jax.random.key(1), (B, G, d)).astype(jnp.bfloat16)
    pos, on = jnp.array([5, 130, 255]), jnp.array([True, False, True])
    want = rw.rows_write(c, jnp.int32(1), val, pos, on, kernel=False)
    got = rw.rows_write(c, jnp.int32(1), val, pos, on, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    row = (want[1, 2, :, :, 255] if positions_last else want[1, 2, :, 255])
    np.testing.assert_array_equal(np.asarray(row, np.float32),
                                  np.asarray(val[2], np.float32))
    np.testing.assert_array_equal(np.asarray(want[:, 1], np.float32),
                                  np.asarray(c[:, 1], np.float32))


def test_a_leaf_as_long_as_a_head_is_wide_is_refused():
    """Which way round a leaf lies is read off its shape: where T is d
    nothing can say, and neither the write nor the attention guesses."""
    c = jnp.zeros((1, 2, 2, 16, 16), jnp.bfloat16)
    val = jnp.ones((2, 2, 16), jnp.bfloat16)
    with pytest.raises(AssertionError):
        rw.rows_write(c, jnp.int32(0), val, jnp.array([1, 2]),
                      jnp.array([True, True]), kernel=False)
    with pytest.raises(AssertionError):
        lm.gqa_attend(jnp.ones((2, 3, 16)), c[0, 0], c[0, 0],
                      jnp.zeros((2, 3), jnp.int32), 0.25, jnp.bfloat16)
    assert rw.positions_last((2, 16, 64), 16)
    assert not rw.positions_last((2, 64, 16), 16)


def test_a_configuration_has_one_kind_of_attention():
    """`_read_positions` counts by which rows the cache holds: latent
    attention and grouped-head attention in one model would count wrong,
    and `KimiConfig` refuses it."""
    with pytest.raises(AssertionError):
        tiny(mla_layers=(2,))
    assert tiny().layer_types.count("gqa") == 2


def test_attention_by_blocks_is_the_plain_form(monkeypatch):
    """`lm.gqa_attend_blocks` (blocks of 48 of 128 positions: the third
    starts early) against `lm.gqa_attend` over the same rows, queries at
    positions 70..99 of a slot whose rows past 99 are another sequence's."""
    monkeypatch.setattr(lm, "GQA_BLOCK", 48)
    G, R, d, T, M = 2, 2, 16, 128, 30
    ks = jax.random.split(jax.random.key(7), 3)
    ck, cv = (jax.random.normal(k, (1, 3, G, T, d)) for k in ks[:2])
    q = jax.random.normal(ks[2], (G, R * M, d))
    at = jnp.broadcast_to(70 + jnp.tile(jnp.arange(M), R), (G, R * M))
    want = lm.gqa_attend(q, ck[0, 1], cv[0, 1], at, 0.25, jnp.float32)
    got = jax.jit(lambda: lm.gqa_attend_blocks(
        q, ck, cv, 0, 1, at, 99, 0.25, jnp.float32))()
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert tuple(int(n) for n in lm.gqa_blocks(99, T)) == (3, 48)


def test_both_programs_count_the_positions_read_beside_the_attended(
        monkeypatch):
    """`read_positions` beside `attended_positions`: a prompt of 37 in
    chunks of 16 (its first lane with every slot's reads all T = 96, its
    further lanes the blocks of 40 to the chunk's last lane), then two
    decode steps at positions 37 and 38, all T each."""
    monkeypatch.setattr(lm, "GQA_BLOCK", 40)
    eng = engine()
    through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(kimi.COUNTS, row)) for row in np.asarray(
        eng.cache["counts"]).tolist())
    assert chunk["attended_positions"] == sum(range(1, 38))
    assert decode["attended_positions"] == 38 + 39
    # chunks end at positions 15, 31, 36: 1, 1 and 1 blocks of 40
    assert chunk["read_positions"] == 3 * 96 + 3 * 40
    assert decode["read_positions"] == 2 * 96
    stats = eng.engine_stats()["step_counts"]
    assert stats["decode"]["read_positions"] == 2 * 96


# -------------------------------------------------------------------- pool

def test_a_pool_hit_gives_the_logits_of_a_cold_prefill():
    """The snapshot and the row blocks of `k` and `v` into another slot,
    then the rest of the prompt: what a cold prefill of the whole prompt
    gives."""
    eng = engine()
    assert eng.family == "solar" and eng.kv.both
    chosen, cold = through_the_programs(eng, PROMPT, 6, slot=0)
    # the donor: the prompt's whole blocks and not a token more, then pooled
    eng.cache = eng._reset_slot(eng.cache, np.int32(1))
    through_the_programs(eng, PROMPT[:32], 1, slot=1)
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 1
    n_hit, entry = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(entry) == 4                  # 36 // 8 blocks
    # slot 2 held another sequence: its rows past the hit stay, stale
    through_the_programs(eng, PROMPT[::-1], 2, slot=2)
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, entry)
    for name in solar.CACHE_STATE:
        np.testing.assert_array_equal(np.asarray(eng.cache[name][:, 2]),
                                      np.asarray(eng.cache[name][:, 1]))
    for name in solar.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name][:, 2, :, :32]),
            np.asarray(eng.cache[name][:, 1, :, :32]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose MLPs take every valid lane of the step in
    one call (`lm.all_lanes`), against `decode_step`: whoever prefills, and
    when the lanes are more than a call's rows."""
    chunk_step_against_decode(solar, tiny(**F32), case,
                              FLOAT32_LOGIT_TOLERANCE, 1e-6)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_an_inactive_lanes_cache_is_bit_identical_after_a_step(program):
    """Slot 0 inactive, slot 2 a chunk of no valid lane: their rows, state
    and window come back to the bit, while slot 1 moves."""
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
    leaves = set(solar.CACHE_TOKEN_AXIS) | set(solar.CACHE_STATE)
    assert set(before) == leaves | {"counts"}
    for name in leaves:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


# ------------------------------------------------------------- the share

def expert_layer(cfg, key, x, first, held):
    """Layer 1's expert block (router over all 16, the experts
    first..first + held held) on x, without the residual, and what it
    counted."""
    share = dataclasses.replace(cfg, first_expert=first, experts_held=held)
    layer = kimi.init_layer(key, 1, share)
    given = jnp.zeros((cfg.n_experts,), jnp.int32)
    out, given = kimi._expert_mlp(
        x, layer["moe"], layer["experts"], 0, share, given,
        jnp.ones(x.shape[:2], bool))
    return out - x, kimi._expert_counts(given, share), layer


@pytest.mark.parametrize("compute", [F32, BF16], ids=["float32", "bfloat16"])
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        compute):
    """The share tied to the model: the routed parts that the four shares
    give (`first_expert` 0, 4, 8, 12 of 16 experts, four held each), with
    what every chip computes alike, the shared expert, counted once, add up
    to what the uncut reference gives for the whole layer."""
    cfg = tiny(**compute)
    key = jax.random.key(SEED)
    x = jax.random.normal(jax.random.key(1), (2, 6, 64), jnp.float32)
    whole, counts, layer = expert_layer(cfg, key, x, 0, 16)
    h = family._rms_norm(x, layer["moe"]["norm"]["scale"], 1e-5)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    uncut = jax.vmap(lambda row: family._expert_block(
        row, f32["moe"], f32["experts"], REFERENCE_MODEL))
    with jax.default_matmul_precision("highest"):
        want, chosen = uncut(h)
        shared = family._swiglu(h, f32["moe"]["shared"])
    tolerance = 1e-6 if compute is F32 else 2e-5
    np.testing.assert_allclose(whole, want, atol=tolerance)
    names = dict(zip(kimi.COUNTS, np.asarray(counts).tolist()))
    assert names["expert_rows"] == names["expert_rows_all"] == 2 * 6 * 3
    parts, held_rows = [], []
    for first in (0, 4, 8, 12):
        part, counts, mine = expert_layer(cfg, key, x, first, 4)
        # a share holds the very experts the whole layer has there
        np.testing.assert_array_equal(
            np.asarray(mine["experts"]["wg"], np.float32),
            np.asarray(layer["experts"]["wg"][first:first + 4], np.float32))
        parts.append(part - shared)
        names = dict(zip(kimi.COUNTS, np.asarray(counts).tolist()))
        assert names["expert_rows_all"] == 36
        held_rows.append(names["expert_rows"])
        in_share = (np.asarray(chosen) >= first) & (np.asarray(chosen)
                                                    < first + 4)
        assert names["expert_rows"] == in_share.sum()
    assert sum(held_rows) == 36
    np.testing.assert_allclose(sum(parts) + shared, want,
                               atol=4 * tolerance)


def test_a_share_of_the_experts_serves_the_references_logits():
    """The engine told that it holds experts 4..7 of the 16: the logits of
    the reference that is given the same share, and not the whole
    model's."""
    share = {"first_expert": 4, "experts_held": 4}
    eng = engine(compute={**F32, **share})
    chosen, got = through_the_programs(eng, PROMPT, 6)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    model = {**REFERENCE_MODEL, "n_routed_experts": 4, "first_expert": 4}
    want = reference_logits(eng.cfg, row, at, model=model)
    assert np.abs(got - want).max() <= FLOAT32_LOGIT_TOLERANCE
    whole = reference_logits(tiny(**F32), row, at)
    assert np.abs(got - whole).max() > 100 * FLOAT32_LOGIT_TOLERANCE
    counts = eng.engine_stats()
    assert 0 < counts["moe_expert_rows"] < counts["moe_expert_rows_all"]
    assert counts["moe_expert_rows_all"] == 8 * 3 * (37 + 5)


# ------------------------------------------------------------ the experts

@pytest.mark.parametrize("tiles,steps", [(None, 1), ((256, 64, 640), 2),
                                         ((256, 64, 512), 3),
                                         ((256, 64, 256), 5)],
                         ids=["the-default-whole-at-d-256", "640",
                              "the-overhang", "256"])
def test_the_experts_kernel_at_1280_is_the_grouped_matmuls(tiles, steps):
    """`ops/expert_mlp.py` interpreted at F = 1,280 (d cut to 256, six
    experts of a stack of eight with rows), float32 rows in two pieces:
    whatever the column tile, the three grouped matmuls' result."""
    D, F, G = 256, 1280, 8
    ks = jax.random.split(jax.random.key(0), 4)
    wg, wu = (jax.random.normal(k, (G, D, F)).astype(jnp.bfloat16) / 16
              for k in ks[:2])
    wd = jax.random.normal(ks[2], (G, F, D)).astype(jnp.bfloat16) / 36
    sizes = jnp.array([3, 0, 17, 1, 0, 30, 9, 4], jnp.int32)
    xs = jax.random.normal(ks[3], (64, D), jnp.float32)
    want = moe._three_products(xs, wg, wu, wd, sizes, jnp.int32(0))
    got = em.expert_mlp(xs, wg, wu, wd, sizes, jnp.int32(0), tiles=tiles,
                        interpret=True)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=1e-5)
    _, _, tf = em._tiles(64, D, F, 2, tiles)
    assert -(-F // tf) == steps


@pytest.mark.parametrize("d,f,want", [
    (2048, 768, 512), (2304, 1024, 512), (4096, 1280, 640), (64, 40, 40),
    (8192, 1280, 256), (16384, 1280, 512), (2048, 1408, 1408)])
def test_the_column_tile_by_width(d, f, want):
    """768 and 1,024, four accepted cells' widths, keep what they had (the
    overhang and two whole tiles); 1,280 takes the tile that divides it and
    fits, a smaller one where d is larger, and the overhang where none
    fits."""
    assert em._column_tile(d, f, 2) == want


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    name, module, config = serving_family("solar-open2-250b")
    assert (name, module, config) == ("solar", solar, kimi.KimiConfig)
    assert module.decode_step is kimi.decode_step
    assert serving_family("kimi-tiny")[1] is kimi
    assert kimi.CACHE_TOKEN_AXIS == {"latent": 2, "k_rope": 2}
    with pytest.raises(ValueError, match="no serving family has the preset"):
        serving_family("lunar-tiny")


def test_the_loop_serves_what_the_programs_give_with_prefix_caching_on():
    """Through `generate`: greedy tokens of the running loop are the
    programs' own by hand, and a second request over the same prefix is a
    pool hit (snapshot and rows) with the same reply."""
    eng = LLMEngine(preset="solar-tiny", max_batch=3, max_seq_len=96,
                    seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                    kv_blocks=24, prefill_chunk_size=16)
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
    assert stats["snapshot_hits"] >= 1
    assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert stats["state_bytes_per_slot"] == 6 * (2 * 16 * 16 + 3 * 96) * 4


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="solar", preset="solar-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          model_overrides=dict(F32), kv_blocks=12,
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
        stats = server.stats()
        assert stats["kv_cache"]["blocks_used"] == 4
        assert stats["kv_cache"]["snapshots_used"] == 1
        assert stats["snapshots_pooled"] == 1
    finally:
        server.engine.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    cfg = tiny()
    params = jax.eval_shape(lambda: kimi.init_params(jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: solar.init_cache(cfg, 2, 96))
    ints, flags = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    decode = jax.jit(lambda p, c: kimi.decode_step(
        p, c, ints, ints, flags, cfg)).lower(params, cache).as_text(
            debug_info=True)
    chunk = jax.jit(lambda p, c: kimi.prefill_chunk(
        p, c, jnp.zeros((2, 16), jnp.int32), ints, ints + 9, flags,
        cfg)).lower(params, cache).as_text(debug_info=True)
    for scope in ("attn/gqa_project", "attn/kv_update", "attn/gqa_attend",
                  "attn/kda_project", "attn/kda_update", "moe_router",
                  "moe_experts", "moe_shared"):
        assert scope in decode and scope in chunk, scope
    assert "kda_chunk" in chunk and "kda_chunk" not in decode
