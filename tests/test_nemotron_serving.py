"""Nemotron-H through the serving path on the CPU at a tiny size: layers that
are a mixer or an expert block alone, the two forms of the Mamba-2 mixer by
groups of B and C, un-rotated grouped-head attention at 16 queries a
key-value head, and routed experts of two matrices inside a latent, of
which the replica may hold a share, against the plain reference's full
forward pass; the state-update kernel at one group and at eight against its
plain form; the pool's snapshot and rows into another slot; the shares tied
to the model; and the preset through the OpenAI server."""

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

from families import nemotron as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import (lm, mamba2, moe, nemotron,  # noqa: E402
                            serving_family)
from ray_tpu.ops import ssm_update as su  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

em = importlib.import_module("ray_tpu.ops.expert_mlp")
pieces_module = importlib.import_module("ray_tpu.ops.pieces")

# the tiny preset in the source's key names, for the reference: MEM*EME, 4
# heads of 32 lanes in 2 groups, 16 experts all held, F = 40 in a latent of 32
MODEL = {"vocab_size": 512, "num_hidden_layers": 7,
         "hybrid_override_pattern": "MEM*EME", "hidden_size": 64,
         "expand": 2, "mamba_num_heads": 4, "mamba_head_dim": 32,
         "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "n_routed_experts": 16, "num_experts_per_tok": 3,
         "n_shared_experts": 1, "moe_intermediate_size": 40,
         "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 48,
         "norm_topk_prob": True, "routed_scaling_factor": 5.0,
         "n_group": 1, "topk_group": 1, "layer_norm_epsilon": 1e-5,
         "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "use_conv_bias": True, "mamba_proj_bias": False, "mlp_bias": False,
         "attention_bias": False, "use_bias": False,
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
CONFIG = {"model": MODEL, "share": {"router_outputs": 16, "first_expert": 0}}
REFERENCE_MODEL = family.reference_model(CONFIG)
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return nemotron.NemotronConfig.preset(
        "nemotron-tiny", **{**family.program_sizes(CONFIG), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == nemotron.NemotronConfig.preset("nemotron-tiny")
    assert tiny().layer_types == ("mamba", "moe", "mamba", "attention",
                                  "moe", "mamba", "moe")
    # F is no multiple of a lane tile; a group is two heads
    assert tiny().d_ff_expert % 128 and tiny().ssm_groups == 2


def test_the_published_sizes_are_the_issues():
    cfg = nemotron.NemotronConfig.preset("nemotron-3-super-120b-a12b")
    assert len(cfg.pattern) == 88
    assert [cfg.pattern.count(c) for c in "ME*-"] == [40, 40, 8, 0]
    assert [l for l, c in enumerate(cfg.pattern) if c == "*"] == [
        7, 16, 25, 36, 47, 58, 69, 78]
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv) == (128, 64, 8, 128, 4)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff_expert,
            cfg.d_latent, cfg.d_ff_shared, cfg.routed_scaling_factor) == (
        512, 22, 2688, 1024, 5376, 5.0)
    # the whole model: 120.67 B, which is its name, and what a token reads
    whole = nemotron.num_params(cfg)
    mamba = (4096 * (8192 + 10240 + 128) + 8192 * 4096 + 3 * 128
             + 5 * 10240 + 8192 + 4096)
    expert_layer = (512 * 2 * 1024 * 2688 + 2 * 4096 * 5376
                    + 2 * 4096 * 1024 + 4096 * 512 + 512 + 4096)
    attention = 2 * 4096 * 32 * 128 + 2 * 4096 * 2 * 128 + 4096
    assert whole == (40 * mamba + 40 * expert_layer + 8 * attention
                     + 2 * 131072 * 4096 + 4096) == 120_668_707_840
    a_token = whole - 40 * (512 - 22) * 2 * 1024 * 2688
    assert round(a_token / 1e9, 2) == 12.77
    one_chip = dataclasses.replace(
        cfg, pattern="MEMEMEM*EME", experts_held=128, vocab_size=32768)
    assert nemotron.num_params(one_chip) == 4_648_163_712
    cache = jax.eval_shape(lambda: nemotron.init_cache(one_chip, 1, 4608))
    assert set(cache) == {"ssm", "conv", "k", "v", "counts"}
    state = sum(cache[n].size * 4 for n in nemotron.CACHE_STATE)
    rows = sum(cache[n].size * 2 for n in nemotron.CACHE_TOKEN_AXIS) // 4608
    assert (state, rows) == (21_585_920, 1024)
    for name, axis in nemotron.CACHE_TOKEN_AXIS.items():
        assert cache[name].shape[axis] == 4608


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    eng = LLMEngine(preset="nemotron-tiny", max_batch=3, max_seq_len=96,
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
    ref = family.Reference(model,
                           lambda l: nemotron.init_layer(key, l, cfg),
                           nemotron.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


# Float32 compute against the float32 reference: the same sums in another
# order (the SSD form's state carried across chunks and the recurrence's
# across steps, against one recurrence over the whole sequence; attention a
# block of positions at a time with a running maximum against the plain
# softmax; the experts' rows sorted and summed by gate against a loop over
# the experts): 2.4e-7 on logits of spread 0.16 here, whatever the chunks. bf16
# compute against it (the reference reads the same bf16 weights, and a
# product's activation goes as the two bf16 pieces that add up to it, so
# what is left is the rounding of the cached k and v and the second piece's
# own): 7.3e-6 over chunk sizes. A state held in bfloat16 moves the float32
# logits by 1.3e-5 at their worst position (49 tokens at dt of 0.001 to 0.1:
# D x is most of y yet), the latent rows rounded to bfloat16 on their way to
# the experts and back by 2.1e-3, a norm over all the lanes where it is by
# group and every head reading group 0's B and C by 0.10; in bf16 compute a
# product's activation as one piece moves them by 1e-3: 20 to 100,000 times
# what the program reads, and the tolerances tell each apart.
FLOAT32_LOGIT_TOLERANCE = 6e-7
BF16_LOGIT_TOLERANCE = 2.5e-5
REFUSED_ON_THE_CPU = ("bfloat16_state", "bfloat16_latent", "norm_over_all",
                      "one_group")


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
    divide them), of 8 (which ends on a block) and of 64 (one chunk);
    attention's blocks are 40 positions of the 96, so the last one starts
    early and a chunk's lanes cross a block's end."""
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
        # no greedy reply that repeats one token (granite's lesson)
        assert len(set(chosen)) > N_DECODE // 2


@pytest.mark.parametrize("degrade", REFUSED_ON_THE_CPU)
def test_a_degraded_reference_is_refused_by_the_float32_tolerance(degrade):
    eng = engine()
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    off = reference_logits(eng.cfg, row, at, degrade)
    assert np.abs(got - off).max() > 10 * FLOAT32_LOGIT_TOLERANCE


def test_products_of_one_piece_are_refused_by_the_bfloat16_tolerance(
        monkeypatch):
    """`ops/pieces.py` giving the activation's rounding and nothing for what
    the rounding left: every `lm.dot` and the experts' rows as one bf16
    piece. The bf16 tolerance, which the two pieces meet, refuses it."""
    whole = pieces_module.pieces

    def rounding_alone(x, dtype, n=2, axis=0):
        both = whole(x, dtype, n, axis)
        keep = jnp.arange(n).reshape((n,) + (1,) * (both.ndim - axis - 1))
        return jnp.where(keep == 0, both, jnp.zeros_like(both))

    monkeypatch.setattr(pieces_module, "pieces", rounding_alone)
    monkeypatch.setattr(moe, "pieces", rounding_alone)
    eng = engine(BF16)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))))
    assert np.abs(got - want).max() > 3 * BF16_LOGIT_TOLERANCE


def test_scores_through_bfloat16_are_another_function():
    """Too near at 37 positions for the tolerance above, and not the same:
    the reference's own scores at T = 256 move."""
    key = jax.random.key(3)
    cfg = tiny(**F32)
    p = jax.tree.map(np.asarray, nemotron.init_layer(key, 3, cfg))
    assert set(p) == {"attention"}
    x = jax.random.normal(jax.random.key(4), (1, 256, 64), jnp.float32)
    exact = family.reference_layer(x, p, REFERENCE_MODEL)
    off = family.reference_layer(x, p, REFERENCE_MODEL, "bfloat16_scores")
    assert 1e-7 < np.abs(np.asarray(exact - off)).max() < 1e-2


# -------------------------------------------------------- Mamba-2 by groups

@pytest.mark.parametrize("groups", [1, 8], ids=["one-group", "eight-groups"])
def test_the_state_update_kernel_is_its_plain_form(groups):
    """`ops/ssm_update.py` interpreted at the published state (N = 128) and
    16 heads of 64 lanes a group, against `_update_plain`: one group's B and
    C as granite hands them, [B, N], and eight groups' [B, 8, N], a column a
    lane tile; an inactive slot and the other layer bit for bit."""
    L, B, N, F = 2, 3, 128, groups * 1024
    ks = jax.random.split(jax.random.key(2), 5)
    state = jax.random.normal(ks[0], (L, B, N, F))
    cols = (B, N) if groups == 1 else (B, groups, N)
    args = (jax.nn.sigmoid(jax.random.normal(ks[1], (B, F)) + 2.0),
            jax.random.normal(ks[2], (B, F)),
            jax.random.normal(ks[3], cols), jax.random.normal(ks[4], cols),
            jnp.array([1, 0, 1]))
    want = jax.jit(lambda s: su.ssm_update(
        s, jnp.int32(1), *args, kernel=False))(state)
    got = jax.jit(lambda s: su.ssm_update(
        s, jnp.int32(1), *args, interpret=True))(state)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=2e-6)
    live = np.array([0, 2])
    np.testing.assert_allclose(np.asarray(got[1])[live],
                               np.asarray(want[1])[live], rtol=1e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(got[0][0], state[0])        # other layer
    np.testing.assert_array_equal(got[0][1, 1], state[1, 1])  # inactive
    if groups > 1:
        # a group's lanes read that group's columns and no other's
        b_one = args[2].at[:, 3].add(1.0)
        moved = su.ssm_update(state, jnp.int32(1), args[0], args[1], b_one,
                              args[3], args[4], interpret=True)[0]
        changed = np.abs(np.asarray(moved - got[0])[1, 0]).max(axis=0) > 0
        assert changed[3 * 1024:4 * 1024].all()
        assert not changed[:3 * 1024].any() and not changed[4 * 1024:].any()


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_the_ssd_form_is_the_recurrence_by_group(groups):
    """`mamba2.further`'s SSD form over 24 lanes of which 19 are valid,
    against 19 steps of `mamba2.first`'s recurrence from the same state:
    the outputs, the state and the window."""
    cfg = tiny(**F32, ssm_groups=groups)
    p = mamba2.init(jax.random.split(jax.random.key(1), 7), cfg)
    M, valid = 24, 19
    u = jax.random.normal(jax.random.key(2), (1, M, 64), jnp.float32)
    cache = mamba2.init_cache(cfg, 2, 2)
    cache = {k: jax.random.normal(jax.random.key(3), v.shape) * 0.3
             for k, v in cache.items()}
    ok = (jnp.arange(M) < valid)[None]
    got, after = jax.jit(lambda c: mamba2.further(
        u, p, cfg, c, 1, 1, ok))(cache)
    step = jax.jit(lambda c, ut: mamba2.first(
        jnp.broadcast_to(ut, (2, 1, 64)), p, cfg, c, 1,
        jnp.array([False, True])))
    outs, c = [], cache
    for t in range(valid):
        o, c = step(c, u[:, t:t + 1])
        outs.append(o[1, 0])
    np.testing.assert_allclose(got[0, :valid], jnp.stack(outs), atol=3e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(after[name], c[name], atol=3e-5)
        np.testing.assert_array_equal(after[name][0], cache[name][0])
        np.testing.assert_array_equal(after[name][1, 0], cache[name][1, 0])


# -------------------------------------------------------------------- pool

def test_a_pool_hit_gives_the_logits_of_a_cold_prefill():
    """The snapshot and the row blocks of `k` and `v` into another slot,
    then the rest of the prompt: what a cold prefill of the whole prompt
    gives."""
    eng = engine()
    assert eng.family == "nemotron" and eng.kv.both
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
    for name in nemotron.CACHE_STATE:
        np.testing.assert_array_equal(np.asarray(eng.cache[name][:, 2]),
                                      np.asarray(eng.cache[name][:, 1]))
    for name in nemotron.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name][:, 2, :, :32]),
            np.asarray(eng.cache[name][:, 1, :, :32]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose expert layers take every valid lane of the
    step in one call (`lm.all_lanes`), against `decode_step`: whoever
    prefills, and when the lanes are more than a call's rows."""
    chunk_step_against_decode(nemotron, tiny(**F32), case,
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
    leaves = set(nemotron.CACHE_TOKEN_AXIS) | set(nemotron.CACHE_STATE)
    assert set(before) == leaves | {"counts"}
    for name in leaves:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


def test_both_programs_count_the_positions_read_beside_the_attended(
        monkeypatch):
    """`read_positions` beside `attended_positions` (Solar's test at this
    family's one attention layer), and the pairs, 3 a lane an expert
    layer."""
    monkeypatch.setattr(lm, "GQA_BLOCK", 40)
    eng = engine()
    through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(nemotron.COUNTS, row)) for row in np.asarray(
        eng.cache["counts"]).tolist())
    assert chunk["attended_positions"] == sum(range(1, 38))
    assert decode["attended_positions"] == 38 + 39
    assert chunk["read_positions"] == 3 * 96 + 3 * 40
    assert decode["read_positions"] == 2 * 96
    assert decode["expert_rows_all"] == 2 * 3 * 3       # steps, layers, K
    assert decode["expert_layer_steps"] == 2 * 3
    assert chunk["expert_rows_all"] == 37 * 3 * 3


# ------------------------------------------------------------- the share

def expert_layer(cfg, key, x, first, held):
    """Layer 1's expert block (router over all 16, the experts
    first..first + held held) on x, without the residual, and what it
    counted."""
    share = dataclasses.replace(cfg, first_expert=first, experts_held=held)
    layer = nemotron.init_layer(key, 1, share)
    given = jnp.zeros((cfg.n_experts,), jnp.int32)
    out, given = nemotron._expert_block(
        x, layer["moe"], layer["experts"], 0, share, given,
        jnp.ones(x.shape[:2], bool))
    return out - x, nemotron._expert_counts(given, share), layer


@pytest.mark.parametrize("compute", [F32, BF16], ids=["float32", "bfloat16"])
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(
        compute):
    """The share tied to the model: the routed parts that the four shares
    give (`first_expert` 0, 4, 8, 12 of 16 experts, four held each), each
    through W_back, which is linear and has no bias, with what every chip
    computes alike, the shared expert, counted once, add up to what the
    uncut reference gives for the whole layer."""
    cfg = tiny(**compute)
    key = jax.random.key(SEED)
    x = jax.random.normal(jax.random.key(1), (2, 6, 64), jnp.float32)
    whole, counts, layer = expert_layer(cfg, key, x, 0, 16)
    h = family._rms_norm(x, layer["moe"]["norm"]["scale"], 1e-5)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    with jax.default_matmul_precision("highest"):
        want, chosen = family._expert_block(
            h.reshape(12, 64), f32["moe"], f32["experts"], REFERENCE_MODEL)
        shared = family._relu2(h @ f32["moe"]["shared"]["w_in"]) \
            @ f32["moe"]["shared"]["w_out"]
    want = want.reshape(2, 6, 64)
    tolerance = 2e-6 if compute is F32 else 1e-4
    np.testing.assert_allclose(whole, want, atol=tolerance)
    names = dict(zip(nemotron.COUNTS, np.asarray(counts).tolist()))
    assert names["expert_rows"] == names["expert_rows_all"] == 2 * 6 * 3
    parts, held_rows = [], []
    for first in (0, 4, 8, 12):
        part, counts, mine = expert_layer(cfg, key, x, first, 4)
        # a share holds the very experts the whole layer has there
        np.testing.assert_array_equal(
            np.asarray(mine["experts"]["wu"], np.float32),
            np.asarray(layer["experts"]["wu"][first:first + 4], np.float32))
        parts.append(part - shared)
        names = dict(zip(nemotron.COUNTS, np.asarray(counts).tolist()))
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
    assert counts["moe_expert_rows_all"] == 3 * 3 * (37 + 5)


# ------------------------------------------------------------ the experts

@pytest.mark.parametrize("d,f,matrices,want", [
    (1024, 2688, 2, em._column_tile(1024, 2688, 2, 2)),
    (2048, 768, 3, 512), (2304, 1024, 3, 512), (4096, 1280, 3, 640),
    (64, 40, 2, 40)])
def test_the_column_tile_by_width(d, f, matrices, want):
    """The accepted cells' widths keep what they had; 2,688 = 21 lane tiles
    in the two-matrix form takes a tile that divides it (no column computed
    twice) and fits."""
    tf = em._column_tile(d, f, 2, matrices)
    assert tf == want
    assert f % tf == 0 or f <= 2 * em.TILE_F


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    name, module, config = serving_family("nemotron-3-super-120b-a12b")
    assert (name, module, config) == ("nemotron", nemotron,
                                      nemotron.NemotronConfig)
    assert nemotron.CACHE_TOKEN_AXIS == {"k": 3, "v": 3}
    assert nemotron.CACHE_STATE == ("ssm", "conv")
    from ray_tpu.models import kimi
    assert nemotron.COUNTS == kimi.COUNTS


def test_the_loop_serves_what_the_programs_give_with_prefix_caching_on():
    """Through `generate`: greedy tokens of the running loop are the
    programs' own by hand, and a second request over the same prefix is a
    pool hit (snapshot and rows) with the same reply."""
    eng = LLMEngine(preset="nemotron-tiny", max_batch=3, max_seq_len=96,
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
    assert stats["kv_bytes_per_token"] == 1 * 2 * 2 * 16 * 4
    assert stats["state_bytes_per_slot"] == 3 * (16 * 128 + 3 * 192) * 4


def test_a_preamble_before_a_task_longer_than_a_block_is_pooled_at_its_end():
    """Prompts of a shared preamble of 32 tokens (4 blocks) and a task of 20
    of their own: the first leaves its snapshot at its last whole block, 48,
    inside its own task, where nobody finds it; the second finds the
    preamble's rows without a snapshot at their end and leaves its snapshot
    there, at 32; the third is a hit of 32 tokens, and its reply is what the
    programs give a cold prefill."""
    rng = np.random.default_rng(3)
    preamble = rng.integers(1, 512, 32).tolist()
    prompts = [preamble + rng.integers(1, 512, 20).tolist()
               for _ in range(3)]
    eng = LLMEngine(preset="nemotron-tiny", max_batch=3, max_seq_len=96,
                    seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                    kv_blocks=48, prefill_chunk_size=16)
    try:
        replies, reused, rows_alone = [], [], []
        for prompt in prompts:
            replies.append(eng.generate(prompt_ids=prompt, max_tokens=4,
                                        temperature=0.0)["token_ids"])
            stats = eng.engine_stats()
            reused.append(stats["snapshot_hits"])
            rows_alone.append(
                eng.kv.stats()["rows_without_snapshot_tokens"])
        pooled = eng.engine_stats()["snapshots_pooled"]
    finally:
        eng.shutdown()
    assert reused == [0, 0, 1] and rows_alone == [0, 32, 32]
    # at 48, at 32, and the third's own at 48
    assert pooled == 3
    assert replies[2] == through_the_programs(engine(), prompts[2], 4)[0]


def test_requests_that_start_at_once_over_one_preamble_wait_for_one_of_them():
    """Admission by hand on a stopped engine: the preamble's rows are pooled
    without a snapshot at their end (a first request left its own at 48);
    four requests over it stand in the queue. One is placed and is due at
    32; the others wait for it, and a request over another prefix does not;
    when the snapshot lands they are placed ahead of the queue, each a hit
    of 32 tokens."""
    rng = np.random.default_rng(4)
    preamble = rng.integers(1, 512, 32).tolist()
    eng = LLMEngine(
        preset="nemotron-tiny", max_batch=4, max_seq_len=96, seed=SEED,
        model_overrides=dict(F32), kv_block_size=8, kv_blocks=64,
        prefill_chunk_size=16)
    eng.shutdown()
    eng._thread.join()
    first = preamble + rng.integers(1, 512, 20).tolist()
    through_the_programs(eng, first[:48], 1, slot=0)
    assert eng.kv.store_prefix(first[:48], eng.cache, 0) == 1

    def request(prompt):
        return eng._make_request("", prompt, 4, 0.0, 0, 1.0)

    herd = [request(preamble + rng.integers(1, 512, 20).tolist())
            for _ in range(4)]
    other = request(rng.integers(1, 512, 52).tolist())
    for req in herd[:3] + [other, herd[3]]:
        eng._queue.put(req)
    eng._admit()
    placed = [r for r in eng._slots if r is not None]
    assert placed == [herd[0], other]
    assert eng._parked == herd[1:]
    assert eng._slot_snapshot_at[:2] == [32, 48]
    assert sorted(eng._prefix_in_flight.values()) == [0, 1]
    # the one that was placed reaches the boundary in two chunk steps
    while eng._slot_snapshot_at[0]:
        lanes = eng._dispatch_step()
        eng._read_step(*lanes)
    assert eng.snapshots_pooled >= 1 and not eng._parked
    assert eng._ready == herd[1:]
    eng._admit()
    assert [r for r in eng._slots if r is not None] == [
        herd[0], other, herd[1], herd[2]]
    assert herd[1].reused_tokens == herd[2].reused_tokens == 32
    assert eng.snapshot_hits == 2 and eng._ready == [herd[3]]


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="nemotron", preset="nemotron-tiny",
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
    params = jax.eval_shape(lambda: nemotron.init_params(
        jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: nemotron.init_cache(cfg, 2, 96))
    ints, flags = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    decode = jax.jit(lambda p, c: nemotron.decode_step(
        p, c, ints, ints, flags, cfg)).lower(params, cache).as_text(
            debug_info=True)
    chunk = jax.jit(lambda p, c: nemotron.prefill_chunk(
        p, c, jnp.zeros((2, 16), jnp.int32), ints, ints + 9, flags,
        cfg)).lower(params, cache).as_text(debug_info=True)
    for scope in ("attn/gqa_project", "attn/kv_update", "attn/gqa_attend",
                  "attn/ssm_project", "attn/ssm_conv", "attn/ssm_update",
                  "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
                  "mlp/moe_latent"):
        assert scope in decode and scope in chunk, scope
    assert "ssm_chunk" in chunk and "ssm_chunk" not in decode
