"""Kimi Linear through the serving path on the CPU at a tiny size: the two
forms of the delta rule (the chunked form with its triangular solve, then
the one-token recurrence) beside latent attention without positions and a
share of the routed experts, against the plain reference's full forward
pass; the kernel against the plain form; the cache's contract in the engine
(state zeroed at placement, rows, state and window untouched where
inactive, both pooled between two chunk steps, found again); the share tied
to the model; and what the family refuses by name."""

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

from families import kimi as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import deepseek, kimi, serving_family  # noqa: E402
from ray_tpu.ops import kda_update as ku  # noqa: E402
from ray_tpu.ops import slot_rows  # noqa: E402

ma = importlib.import_module("ray_tpu.ops.mla_attend")
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

# the tiny preset in the source's key names, for the reference: 5 layers
# (KDA, KDA, MLA, KDA, MLA; layer 1's MLP dense), 8 experts all held
MODEL = {"vocab_size": 512, "num_hidden_layers": 5,
         "first_k_dense_replace": 1, "hidden_size": 64,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_experts": 8, "num_experts_per_token": 3,
         "num_shared_experts": 1, "moe_renormalize": True,
         "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
         "routed_scaling_factor": 2.446, "num_expert_group": 1,
         "topk_group": 1, "num_attention_heads": 4, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "q_lora_rank": None, "mla_use_nope": True, "hidden_act": "silu",
         "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
         "num_nextn_predict_layers": 0,
         "linear_attn_config": {"full_attn_layers": [3, 5],
                                "kda_layers": [1, 2, 4], "head_dim": 16,
                                "num_heads": 2, "short_conv_kernel_size": 4}}
CONFIG = {"model": MODEL, "assumed_sizes": {"kda_gate_rank": 8},
          "share": {"router_outputs": 8, "first_expert": 0}}
REFERENCE_MODEL = family.reference_model(CONFIG)
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return kimi.KimiConfig.preset(
        "kimi-tiny", **{**family.program_sizes(CONFIG), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == kimi.KimiConfig.preset("kimi-tiny")
    assert tiny().layer_types == ("kda", "kda", "mla", "kda", "mla")


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    eng = LLMEngine(preset="kimi-tiny", max_batch=3, max_seq_len=96,
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
# order (the chunked form's state carried across runs of 16 lanes and
# chunks, and the recurrence's across steps, against one recurrence over the
# whole sequence; the triangular solve as a product of four matrices; the
# absorbed form of attention over cached rows against the plain form over
# the sequence): 6e-7 on logits of spread 0.16 here. bf16 compute against it
# (the reference reads the same bf16 weights, and a product's activation
# goes as the two bf16 pieces that add up to it, the experts' rows too, so
# what is left is the rounding of the absorbed queries, the cached rows and
# attention's weights in two layers): 4e-5 over chunk sizes. A state held
# in bfloat16 moves the float32 logits by 3e-4 at their worst position, the
# mean of a head's decays in place of the vector by 3e-2 and the rule
# without its delta by 4e-2: 100 to 10,000 times what the float32 program
# reads; rows through float8 by 1e-4. The float32 tolerance tells each apart
# here, and on the chip the cell's own check (`families/kimi.py`, PERF.md
# PR 40).
FLOAT32_LOGIT_TOLERANCE = 3e-6
BF16_LOGIT_TOLERANCE = 2e-4


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no cache, no chunks, the recurrence a
    token at a time): the logits at every generated position, whatever the
    chunks' boundaries (the triangular solve against the recurrence). 37
    tokens in chunks of 16 and of 7 (which do not divide them), of 8 (which
    ends on a block) and of 64 (one chunk, four runs of the solve)."""
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
def test_a_degraded_reference_is_refused_by_the_float32_tolerance(degrade):
    eng = engine()
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    off = reference_logits(eng.cfg, row, at, degrade)
    assert np.abs(got - off).max() > 10 * FLOAT32_LOGIT_TOLERANCE


def test_the_kernel_is_the_plain_form_and_leaves_an_inactive_slot_alone():
    L, B, H, N = 2, 3, 2, 128
    ks = jax.random.split(jax.random.key(2), 6)
    state = jax.random.normal(ks[0], (L, B, H, N, N))
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    args = (jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, N)) + 3.0),
            unit(jax.random.normal(ks[2], (B, H, N))),
            unit(jax.random.normal(ks[3], (B, H, N))) * N ** -0.5,
            jax.random.normal(ks[4], (B, H, N)),
            jax.nn.sigmoid(jax.random.normal(ks[5], (B, H))),
            jnp.array([1, 0, 1]))
    want = jax.jit(lambda s: ku.kda_update(
        s, jnp.int32(1), *args, kernel=False))(state)
    got = jax.jit(lambda s: ku.kda_update(
        s, jnp.int32(1), *args, interpret=True))(state)
    on = np.array([True, False, True])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1])[on], np.asarray(want[1])[on],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[0][0], state[0])        # other layer
    np.testing.assert_array_equal(got[0][1, 1], state[1, 1])  # inactive
    # the rule reads the state before it writes it: what the decayed state
    # held for k is gone from the update, b of it
    s, (a, k, q, v, b, _) = np.asarray(state[1, 0, 0]), [
        np.asarray(t)[0, 0] for t in args[:5]] + [None]
    decayed = a[:, None] * s
    new = decayed + np.outer(k, b * (v - decayed.T @ k))
    np.testing.assert_allclose(np.asarray(want[0])[1, 0, 0], new, atol=1e-5)
    np.testing.assert_allclose(np.asarray(want[1])[0, 0], new.T @ q,
                               atol=1e-5)


def test_the_chunked_form_is_the_recurrence_under_strong_decay():
    """64 lanes of one run, decays down to exp(-30) a token: a quotient of
    cumulative products would overflow; differences of logs do not."""
    m, H, N = 16, 2, 16
    ks = jax.random.split(jax.random.key(4), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q, k = (unit(jax.random.normal(ks[i], (m, H, N))) for i in (0, 1))
    v = jax.random.normal(ks[2], (m, H, N))
    log_a = -30.0 * jax.random.uniform(ks[3], (m, H, N))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (m, H)))
    s0 = jax.random.normal(ks[5], (H, N, N))
    o, s = kimi._delta_chunk(q, k, v, log_a, b, s0)
    state, outs = s0[None, None], []
    for t in range(m):
        state, o_t = ku.kda_update(state, jnp.int32(0), jnp.exp(log_a[t])[
            None], k[t][None], q[t][None], v[t][None], b[t][None],
            jnp.array([1]), kernel=False)
        outs.append(o_t[0])
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, jnp.stack(outs), atol=2e-5)
    np.testing.assert_allclose(s, state[0, 0], atol=2e-5)


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose MLPs take every valid lane of the step in
    one call (`lm.all_lanes`), against `decode_step`: whoever prefills, and
    when the lanes are more than a call's rows."""
    chunk_step_against_decode(kimi, tiny(**F32), case,
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
    leaves = set(kimi.CACHE_TOKEN_AXIS) | set(kimi.CACHE_STATE)
    assert set(before) == leaves | {"counts"}
    for name in leaves:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


def test_an_overrun_lane_past_the_length_writes_nothing():
    """A chunk of 16 lanes of which 5 are valid: the state, the window and
    the rows are what a chunk of those 5 tokens alone leaves, to the bit
    for the rows and the window."""
    a, b = engine(), engine()
    B, C = a.max_batch, a.prefill_chunk_size
    lanes = np.arange(B) == 1
    for eng, filler in ((a, 0), (b, 9)):
        tokens = np.full((B, C), filler, np.int32)
        tokens[1, :5] = PROMPT[:5]
        _, eng.cache = eng._chunk_step(
            eng.params, eng.cache, tokens, np.zeros((B,), np.int32),
            np.where(lanes, 5, 0).astype(np.int32), lanes)
    for name in list(kimi.CACHE_TOKEN_AXIS) + list(kimi.CACHE_STATE):
        np.testing.assert_array_equal(np.asarray(a.cache[name]),
                                      np.asarray(b.cache[name]))


def test_a_layer_made_alone_is_the_layer_in_the_tree():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    tree = kimi.init_params(key, cfg)
    held = cfg.experts_held
    for l, at in enumerate(kimi._stack_index(cfg)):
        layer = kimi.init_layer(key, l, cfg)
        assert set(layer) == set(at)
        for part, i in at.items():
            rows = slice(i * held, (i + 1) * held) if part == "experts" else i
            jax.tree.map(
                lambda whole, alone, rows=rows: np.testing.assert_array_equal(
                    np.asarray(whole[rows], np.float32),
                    np.asarray(alone, np.float32)), tree[part], layer[part])
    p = tree["kda"]
    a, dt = np.exp(p["a_log"]), np.log1p(np.exp(p["dt_bias"]))
    assert a.shape == (3, 2) and (a >= 1).all() and (a <= 16).all()
    assert (dt >= 0.000999).all() and (dt <= 0.1001).all()
    assert p["conv_w"].dtype == jnp.float32 == p["a_log"].dtype
    assert p["w_qkv"].dtype == jnp.bfloat16 == tree["wte"].dtype
    assert p["w_fgb"].shape == (3, 64, 8 + 8 + 2 + 126)     # f, g, b, pad
    assert not np.asarray(p["w_fgb"][..., 18:], np.float32).any()
    assert tree["moe"]["router"].dtype == jnp.float32
    assert tree["experts"]["wg"].shape == (4 * 8, 64, 32)
    assert tree["lm_head"].shape == (64, 512)               # untied
    n = sum(a.size for a in jax.tree.leaves(tree))
    assert n == kimi.num_params(cfg)


def test_the_published_sizes_are_the_issues():
    cfg = kimi.KimiConfig.preset("kimi-linear-48b-a3b")
    assert [l + 1 for l, t in enumerate(cfg.layer_types)
            if t == "mla"] == [4, 8, 12, 16, 20, 24, 27]
    assert cfg.layers_of("kda") == 20 and cfg.n_expert_layer == 26
    assert round(kimi.num_params(cfg) / 1e9, 1) == 49.1      # whole: 98 GB
    cut = dataclasses.replace(cfg, n_layer=9, mla_layers=(4, 8),
                              experts_held=64, vocab_size=40960)
    assert round(kimi.num_params(cut) / 1e6) == 4274         # 8.55 GB
    cache = jax.eval_shape(lambda: kimi.init_cache(cut, 128, 10240))
    assert cache["kda"].shape == (7, 128, 32, 128, 128)
    assert cache["conv"].shape == (7, 128, 3 * 12288)
    assert cache["latent"].shape == (2, 128, 10240, 512)
    assert cache["k_rope"].shape == (2, 128, 10240, 64)
    rows = sum(cache[n].size * 2 for n in kimi.CACHE_TOKEN_AXIS)
    state = sum(cache[n].size * 4 for n in kimi.CACHE_STATE)
    assert rows // (128 * 10240) == 2304
    assert state // 128 == 15_712_256


# ------------------------------------------------------------- the share

def expert_layer(cfg, key, x, first, held):
    """Expert layer 1's block (router over all 8, the experts
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
    """The share tied to the model: the parts of the routed sum that the
    four shares give (`first_expert` 0, 2, 4, 6 of 8 experts, two held
    each), with what every chip computes alike, the shared expert, counted
    once, add up to what the uncut reference gives for the whole layer; and
    the counters count held and all pairs apart."""
    cfg = tiny(**compute)
    key = jax.random.key(SEED)
    x = jax.random.normal(jax.random.key(1), (2, 6, 64), jnp.float32)
    whole, counts, layer = expert_layer(cfg, key, x, 0, 8)
    model = {**REFERENCE_MODEL, "num_experts": 8}
    h = family._rms_norm(x, layer["moe"]["norm"]["scale"], 1e-5)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    with jax.default_matmul_precision("highest"):
        want, chosen = family._expert_block(h, f32["moe"], f32["experts"],
                                            model)
        shared = family._swiglu(h, f32["moe"]["shared"])
    tolerance = 1e-6 if compute is F32 else 2e-5
    np.testing.assert_allclose(whole, want, atol=tolerance)
    names = dict(zip(kimi.COUNTS, np.asarray(counts).tolist()))
    assert names["expert_rows"] == names["expert_rows_all"] == 2 * 6 * 3
    parts, held_rows = [], []
    for first in (0, 2, 4, 6):
        part, counts, mine = expert_layer(cfg, key, x, first, 2)
        # a share holds the very experts the whole layer has there
        np.testing.assert_array_equal(
            np.asarray(mine["experts"]["wg"], np.float32),
            np.asarray(layer["experts"]["wg"][first:first + 2], np.float32))
        parts.append(part - shared)
        names = dict(zip(kimi.COUNTS, np.asarray(counts).tolist()))
        assert names["expert_rows_all"] == 36
        held_rows.append(names["expert_rows"])
        in_share = (np.asarray(chosen) >= first) & (np.asarray(chosen)
                                                    < first + 2)
        assert names["expert_rows"] == in_share.sum()
        assert names["experts_touched"] == len(set(
            np.asarray(chosen)[in_share].tolist()))
        # and the reference, given the same share, gives the same part
        share_model = {**model, "num_experts": 2, "first_expert": first}
        with jax.default_matmul_precision("highest"):
            ref_part, _ = family._expert_block(
                h, f32["moe"], jax.tree.map(
                    lambda a: a[first:first + 2], f32["experts"]),
                share_model)
        np.testing.assert_allclose(part, ref_part, atol=tolerance)
    assert sum(held_rows) == 36
    np.testing.assert_allclose(sum(parts) + shared, want,
                               atol=4 * tolerance)


def test_a_share_of_the_experts_serves_the_references_logits():
    """The engine told that it holds experts 2..5 of the 8: the logits of
    the reference that is given the same share, and not the whole
    model's."""
    share = {"first_expert": 2, "experts_held": 4}
    eng = engine(compute={**F32, **share})
    chosen, got = through_the_programs(eng, PROMPT, 6)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    model = {**REFERENCE_MODEL, "num_experts": 4, "first_expert": 2}
    want = reference_logits(eng.cfg, row, at, model=model)
    assert np.abs(got - want).max() <= FLOAT32_LOGIT_TOLERANCE
    whole = reference_logits(tiny(**F32), row, at)
    assert np.abs(got - whole).max() > 100 * FLOAT32_LOGIT_TOLERANCE
    counts = eng.engine_stats()
    assert 0 < counts["moe_expert_rows"] < counts["moe_expert_rows_all"]
    assert counts["moe_expert_rows_all"] == 4 * 3 * (37 + 5)
    assert counts["moe_experts_touched"] <= 4 * (3 + 5) * 4


# ------------------------------------------------------------------- MLA

def test_mla_without_rotation_is_deepseeks_with_rotation_off():
    """`kimi._mla` against `deepseek._attention(rope=False)` on the same
    weights, float32: one absorbed form, two files."""
    cfg = tiny(**F32)
    ds = deepseek.DeepseekConfig.preset(
        "deepseek-tiny", d_model=64, n_head=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        norm_eps=1e-5, **F32)
    p = kimi.init_layer(jax.random.key(SEED), 2, cfg)["mla"]
    wkvb = jnp.concatenate([jnp.transpose(p["w_uk"], (2, 0, 1)),
                            jnp.transpose(p["w_uv"], (1, 0, 2))], axis=-1)
    bp = {"attn_norm": p["norm"], "attn": {
        "wq": p["wq"].reshape(64, 4, 24), "wkva": p["wkva"],
        "kv_norm": p["kv_norm"], "wkvb": wkvb, "wo": p["wo"]}}
    B, C, T = 2, 5, 32
    x = jax.random.normal(jax.random.key(3), (B, C, 64), jnp.float32)
    pos0 = jnp.array([3, 0])
    pos = pos0[:, None] + jnp.arange(C)
    ok = jnp.array([[True] * 5, [True] * 3 + [False] * 2])
    lat = jnp.zeros((1, B, T, 32)) + 0.5
    kr = jnp.zeros((1, B, T, 8)) - 0.25
    want, lat_d, kr_d = deepseek._attention(x, bp, ds, lat, kr, 0, pos0, pos,
                                            ok, rope=False)
    got, cache = kimi._mla(x, p, cfg, {"latent": lat, "k_rope": kr}, 0, pos0,
                           pos, ok)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(cache["latent"], lat_d, atol=1e-6)
    np.testing.assert_allclose(cache["k_rope"], kr_d, atol=1e-6)
    rotated, _, _ = deepseek._attention(x, bp, ds, lat, kr, 0, pos0, pos, ok)
    assert np.abs(np.asarray(rotated - want)).max() > 1e-4


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_both_programs_count_the_positions_read_beside_the_attended(
        monkeypatch, form):
    """`read_positions` beside `attended_positions` in both programs' rows
    of the counts: a prompt of 37 in chunks of 16 (its first lane with
    every slot's, its further lanes against its own rows: all T = 96 each
    in the plain form), then two decode steps at positions 37 and 38. In
    the plain form a live slot's first lane reads all T too; under the
    kernel (interpreted, blocks of 16) its position rounded up to a block.
    What was attended is the same, and so are the logits."""
    T, block = 96, 16
    _, plain = through_the_programs(engine(BF16), PROMPT, 3)
    if form == "kernel":
        monkeypatch.setattr(slot_rows, "BLOCK", block)
        for name in ("mla_attend", "read_positions"):
            monkeypatch.setattr(kimi, name, functools.partial(
                getattr(ma, name), interpret=True))
    eng = engine(BF16)
    _, logits = through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(kimi.COUNTS, row)) for row in np.asarray(
        eng.cache["counts"]).tolist())
    assert chunk["attended_positions"] == sum(range(1, 38))
    assert decode["attended_positions"] == 38 + 39
    if form == "plain":
        assert chunk["read_positions"] == 3 * T + 3 * T
        assert decode["read_positions"] == 2 * T
        np.testing.assert_array_equal(logits, plain)
    else:
        assert chunk["read_positions"] == (16 + 32 + 48) + 3 * T
        assert decode["read_positions"] == 48 + 48
        np.testing.assert_allclose(logits, plain, atol=2e-3)
        assert np.abs(plain).max() > 0.5


# -------------------------------------------------------------------- pool

def test_a_pool_hit_gives_the_logits_of_a_cold_prefill():
    """The snapshot and its row blocks into another slot, then the rest of
    the prompt: what a cold prefill of the whole prompt gives."""
    eng = engine()
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
    for name in kimi.CACHE_STATE:
        np.testing.assert_array_equal(np.asarray(eng.cache[name][:, 2]),
                                      np.asarray(eng.cache[name][:, 1]))
    for name in kimi.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name][:, 2, :32]),
            np.asarray(eng.cache[name][:, 1, :32]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


def test_the_family_refuses_what_cannot_carry_its_cache_by_name():
    eng = engine()
    with pytest.raises(NotImplementedError, match="kimi"):
        eng.export_prefix(prompt_ids=PROMPT)
    with pytest.raises(NotImplementedError, match="kimi"):
        eng.import_prefix({"ids": PROMPT})
    with pytest.raises(NotImplementedError, match="kimi"):
        eng.prefix_model_key
    with pytest.raises(NotImplementedError, match="kimi"):
        kimi.resident_specs(eng.cfg)


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    # the module's presets are two families' (`models/solar.py` is this
    # module under another word on the cache's leaves): the word picks
    for preset in kimi.PRESETS:
        word = preset.split("-")[0]
        module = importlib.import_module(f"ray_tpu.models.{word}")
        assert serving_family(preset) == (word, module, kimi.KimiConfig)
        assert module.decode_step is kimi.decode_step
    assert {p.split("-")[0] for p in kimi.PRESETS} == {"kimi", "solar"}
    for name in ("init_params", "resident_params", "resident_specs",
                 "init_cache", "decode_step", "prefill_chunk",
                 "CACHE_TOKEN_AXIS", "CACHE_STATE", "COUNTS"):
        assert hasattr(kimi, name), name
    assert kimi.CACHE_TOKEN_AXIS and kimi.CACHE_STATE
    assert kimi.COUNTS[:6] == deepseek.COUNTS     # Kanana's readers read it
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        assert "kimi" not in f.read()         # the engine knows the contract


@pytest.mark.parametrize("kwargs,what", [
    (dict(checkpoint="/nowhere"), "checkpoint="),
    (dict(tensor_parallel_size=2), "tensor_parallel_size")])
def test_what_is_gpt2s_refuses_the_family_by_name(kwargs, what):
    with pytest.raises(NotImplementedError, match="kimi") as e:
        LLMEngine(preset="kimi-tiny", **kwargs)
    assert what in str(e.value)


def test_lora_and_the_cluster_prefix_store_refuse_the_family_by_name():
    server = OpenAIServer(model_id="kimi", preset="kimi-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          lora_root="/nowhere")
    try:
        with pytest.raises(NotImplementedError, match="kimi") as e:
            server({"model": "kimi:adapter", "prompt_ids": PROMPT})
        assert "LoRA" in str(e.value)
    finally:
        server.engine.shutdown()
    with pytest.raises(NotImplementedError, match="kimi") as e:
        OpenAIServer(model_id="kimi", preset="kimi-tiny", max_batch=2,
                     max_seq_len=96, seed=SEED, cluster_prefix_cache=True)
    assert "cluster prefix store" in str(e.value)


def live_engine(**kwargs):
    kwargs.setdefault("kv_blocks", 24)
    return LLMEngine(preset="kimi-tiny", max_batch=3, max_seq_len=96,
                     seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                     prefill_chunk_size=16, **kwargs)


def greedy_by_hand(prompt, n):
    return through_the_programs(engine(), prompt, n)[0]


def test_the_loop_serves_what_the_programs_give_and_pools_between_chunks():
    """37 tokens: chunks of 16, 16 (the boundary, 32: rows and state are
    pooled here, with 5 tokens still to go) and 5; then the same prompt
    again and one that shares its first 32 tokens, both from the pool."""
    want = greedy_by_hand(PROMPT, 8)
    other = PROMPT[:32] + [11, 12, 13]
    want_other = greedy_by_hand(other, 8)
    eng = live_engine()
    try:
        first = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        stats = eng.engine_stats()
        assert first["token_ids"] == want
        assert (stats["slots_reset"], stats["snapshots_pooled"],
                stats["snapshot_hits"]) == (1, 1, 0)
        assert stats["chunk_steps"] == 3 and stats["tokens_prefilled"] == 37
        assert eng.kv.stats()["blocks_used"] == 4
        again = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        shared = eng.generate(prompt_ids=other, max_tokens=8)
        stats = eng.engine_stats()
        assert again["token_ids"] == want
        assert shared["token_ids"] == want_other
        assert (stats["slots_reset"], stats["snapshots_pooled"],
                stats["snapshot_hits"]) == (1, 1, 2)
        assert stats["tokens_prefilled"] == 37 + 5 + 3
        assert eng.kv.stats()["tokens_reused"] == 64
        # both gauges, and the device's own counts of held and all pairs
        assert stats["kv_bytes_per_token"] == 2 * (32 + 8) * 4
        assert stats["state_bytes_per_slot"] == 3 * (2 * 16 * 16
                                                     + 3 * 3 * 32) * 4
        assert stats["rows_without_snapshot_tokens"] == 0
        assert stats["moe_expert_rows"] == stats["moe_expert_rows_all"] > 0
        decode = stats["step_counts"]["decode"]
        assert decode["expert_layer_steps"] % 4 == 0
        assert decode["expert_rows_all"] == 3 * decode["expert_layer_steps"]
        # the plain form: all 96 positions of a live slot a decode step
        assert decode["read_positions"] == 96 * 3 * 7 \
            > decode["attended_positions"] > 0
    finally:
        eng.shutdown()


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="kimi", preset="kimi-tiny",
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
        assert ids == greedy_by_hand(PROMPT, 5)
        stats = server.stats()
        assert stats["kv_cache"]["blocks_used"] == 4
        assert stats["kv_cache"]["snapshots_used"] == 1
        assert stats["snapshots_pooled"] == 1
    finally:
        server.engine.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    eng = engine()
    B, C = eng.max_batch, eng.prefill_chunk_size
    ints, on = np.zeros((B,), np.int32), np.zeros((B,), bool)
    step = eng._step.lower(eng.params, eng.cache, ints, ints, on).as_text(
        debug_info=True)
    chunk = eng._chunk_step.lower(eng.params, eng.cache,
                                  np.zeros((B, C), np.int32), ints, ints,
                                  on).as_text(debug_info=True)
    # the chunk program is the decode program on every slot's first lane,
    # and the chunked form on the further lanes of the slots that have them
    for text, mixers in ((step, ["kda_update"]),
                         (chunk, ["kda_update", "kda_chunk"])):
        for scope in ["attn/kda_project", "attn/mla_project",
                      "attn/mla_attend", "attn/kv_update", "mlp/moe_router",
                      "mlp/moe_dispatch", "mlp/moe_experts", "mlp/moe_shared",
                      "unembed_loss", "embed", "layers"] + [
                f"attn/{m}" for m in mixers]:
            assert scope in text, scope
    assert "attn/kda_chunk" not in step
    reset = eng._reset_slot.lower(eng.cache, np.int32(0)).as_text(
        debug_info=True)
    assert "kv_update" in reset
