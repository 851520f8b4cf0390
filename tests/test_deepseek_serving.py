"""The DeepSeek-V3 layer on the serving path (`models/deepseek.py`, preset
`deepseek-tiny`, seeded weights, the CPU): the engine's own compiled
programs against the plain reference of `benchmarks/chip/families/
kanana.py`, the absorbed form of latent attention against the plain one,
the router, the prefix pool over latent leaves, and the engine end to end.
"""

import functools
import importlib
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
for p in (CHIP_DIR, os.path.join(CHIP_DIR, "rehearse")):
    if p not in sys.path:
        sys.path.insert(0, p)

from families import kanana  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import deepseek, mla, moe, serving_family  # noqa: E402
from ray_tpu.ops import slot_rows  # noqa: E402
from ray_tpu.ops.grouped_matmul import grouped_matmul  # noqa: E402

ma = importlib.import_module("ray_tpu.ops.mla_attend")
from ray_tpu.serve.kv_cache import PagedKVCache  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

# the tiny preset in the source's key names, for the reference
MODEL = {"vocab_size": 512, "num_hidden_layers": 3,
         "first_k_dense_replace": 1, "num_attention_heads": 4,
         "hidden_size": 64, "intermediate_size": 128,
         "moe_intermediate_size": 32, "n_routed_experts": 8,
         "num_experts_per_tok": 3, "n_shared_experts": 2,
         "norm_topk_prob": True, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
         "routed_scaling_factor": 2.448, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "rope_theta": 1000000, "rms_norm_eps": 1e-6}
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
SEED = 5


def tiny(**extra):
    return deepseek.DeepseekConfig.preset(
        "deepseek-tiny", **{**kanana.program_sizes(MODEL), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == deepseek.DeepseekConfig.preset("deepseek-tiny")


def engine(compute=F32, max_batch=3, **kwargs):
    eng = LLMEngine(preset="deepseek-tiny", max_batch=max_batch,
                    max_seq_len=96,
                    seed=SEED, model_overrides=dict(compute), kv_blocks=12,
                    kv_block_size=8, prefill_chunk_size=16, **kwargs)
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
    ref = kanana.Reference(MODEL, lambda l: deepseek.init_layer(key, l, cfg),
                           deepseek.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


PROMPT = np.random.default_rng(0).integers(0, 512, 37).tolist()
N_DECODE = 12

# bf16 compute against the float32 reference (which reads the same bf16
# weights, so only the activations' rounding is in it): a bf16 value carries
# 8 bits, every product's inputs are off by up to 2**-9 of their size, and
# the absorbed form rounds q' = q_nope W_uk to bf16 once more than the plain
# form does; the residual stream and the router's input are float32.
# Measured here over three seeds: 1.2e-3 to 1.4e-3 on logits of size ~0.5
# after three layers (3.1e-3 to 4.0e-3 with a bf16 stream); the tolerance
# is under four times that. At this size one part in float8 moves the
# logits by 1.5e-3 to 3.3e-3: it is the float32 tolerance that tells those
# apart here, and on the chip the cell's own check (`families/kanana.py`,
# PERF.md PR 29)
BF16_LOGIT_TOLERANCE = 5e-3
FLOAT32_LOGIT_TOLERANCE = 1e-4


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose MLPs take every valid lane of the step in
    one call (`lm.all_lanes`), against `decode_step`: whoever prefills, and
    when the lanes are more than a call's rows."""
    chunk_step_against_decode(deepseek, tiny(**F32), case,
                              FLOAT32_LOGIT_TOLERANCE, 1e-5)


@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE),
    ({"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16},
     BF16_LOGIT_TOLERANCE)], ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass: the logits at every generated
    position."""
    eng = engine(compute)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))))
    assert got.shape == want.shape == (N_DECODE, 512)
    assert np.abs(got - want).max() <= tolerance
    if compute is F32:
        assert chosen == want.argmax(axis=-1).tolist()


@pytest.mark.parametrize("degrade", kanana.DEGRADE[1:])
def test_a_part_in_float8_is_refused_by_the_float32_tolerance(degrade):
    row = PROMPT + [1] * 11
    at = list(range(len(PROMPT) - 1, len(row)))
    cfg = tiny(**F32)
    want = reference_logits(cfg, row, at)
    low = reference_logits(cfg, row, at, degrade)
    # float8 experts 1.2e-4, a float8 cache 4.3e-4; the float32 program
    # reads 1.2e-7 (at this size the experts add little to a token table
    # drawn for the published widths, `deepseek.EMBED_STD`)
    assert np.abs(low - want).max() > FLOAT32_LOGIT_TOLERANCE


def test_other_slots_and_the_padding_lanes_leave_no_trace():
    """A slot's logits do not depend on what the other slots hold, and an
    inactive slot's cache is not written."""
    eng = engine()
    _, alone = through_the_programs(eng, PROMPT, 4, slot=1)
    other = np.asarray(eng.cache["latent"][:, 0]).copy()
    _, again = through_the_programs(eng, PROMPT[::-1], 4, slot=0)
    _, beside = through_the_programs(eng, PROMPT, 4, slot=2)
    np.testing.assert_allclose(alone, beside, atol=1e-6)
    assert not np.array_equal(np.asarray(eng.cache["latent"][:, 0]), other)
    np.testing.assert_array_equal(
        np.asarray(eng.cache["latent"][:, 1, :len(PROMPT)]),
        np.asarray(eng.cache["latent"][:, 2, :len(PROMPT)]))
    # a chunk step with lanes for slot 2 alone: an inactive slot that was
    # handed tokens and an active one of no length keep every row, bit for
    # bit, and so do slot 2's positions past its three lanes
    before = {k: np.asarray(eng.cache[k]).copy()
              for k in deepseek.CACHE_TOKEN_AXIS}
    at = len(PROMPT) + 3
    tokens = np.random.default_rng(1).integers(0, 512, (3, 16)).astype(
        np.int32)
    _, eng.cache = eng._chunk_step(
        eng.params, eng.cache, tokens, np.array([5, 7, at], np.int32),
        np.array([9, 0, 3], np.int32), np.array([False, True, True]))
    for name, was in before.items():
        now = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(now[:, :2], was[:, :2])
        np.testing.assert_array_equal(now[:, 2, :at], was[:, 2, :at])
        np.testing.assert_array_equal(now[:, 2, at + 3:], was[:, 2, at + 3:])
        assert not np.array_equal(now[:, 2, at:at + 3], was[:, 2, at:at + 3])


# ------------------------------- a chunk step computes the plan's lanes only

# slot: (tokens before the step, the step's lanes, active)
MIXED = [(12, 0, True), (9, 1, True), (20, 5, True), (0, 16, True),
         (4, 7, False), (30, 16, True)]


@pytest.fixture(scope="module")
def mixed_step():
    """One chunk step whose slots have 0, 1, 5, 16, (inactive) 7 and 16
    lanes, three of them with lanes past the first at once, from a cache
    that holds each slot's tokens before; and the same lanes a token at a
    time through the decode program from a copy of that cache."""
    B, C = len(MIXED), 16
    eng = engine(max_batch=B)
    rng = np.random.default_rng(7)
    rows = [rng.integers(0, 512, before + lanes).tolist()
            for before, lanes, _ in MIXED]
    pos0 = np.array([before for before, _, _ in MIXED], np.int32)
    length = np.array([lanes for _, lanes, _ in MIXED], np.int32)
    active = np.array([on for _, _, on in MIXED])

    def decode(cache, at, on):
        """Each slot's token at position `at` (any, where it is not `on`)."""
        tokens = np.array([row[min(p, len(row) - 1)] if row else 0
                           for row, p in zip(rows, at)], np.int32)
        return eng._step(eng.params, cache, tokens, at.astype(np.int32), on)

    # what each slot holds before the step: a token at a time
    for j in range(int(pos0.max())):
        _, eng.cache = decode(eng.cache, np.minimum(j, pos0), j < pos0)
    start = jax.tree.map(lambda a: np.asarray(a).copy(), eng.cache)

    tokens = np.zeros((B, C), np.int32)
    for b, row in enumerate(rows):
        tokens[b, :length[b]] = row[pos0[b]:]
    tokens[4] = 3                                 # an inactive slot's are read
    logits, after = eng._chunk_step(eng.params, jax.tree.map(jnp.asarray,
                                                             start),
                                    tokens, pos0, length, active)
    by_step = jax.tree.map(jnp.asarray, start)
    last = np.zeros((B, 512), np.float32)
    first = None
    for j in range(C):
        on = active & (j < length)
        out, by_step = decode(by_step, pos0 + np.minimum(j, length), on)
        first = np.asarray(out) if j == 0 else first
        last[on] = np.asarray(out)[on]
    valid = [b for b in range(B) if active[b] and length[b]]
    return dict(eng=eng, rows=rows, pos0=pos0, length=length, active=active,
                valid=valid, start=start, logits=np.asarray(logits),
                after=jax.tree.map(np.asarray, after), first=first,
                last=last, by_step=jax.tree.map(np.asarray, by_step))


def reference_walk(cfg, row):
    """The reference over one row: (final hidden -> logits [T, V], what each
    expert layer's router chose [T, K])."""
    key = jax.random.key(SEED)
    x = deepseek.init_ends(key, cfg)["wte"][jnp.asarray(row)].astype(
        jnp.float32)
    chosen = []
    for l in range(cfg.n_layer):
        x, experts = kanana.reference_layer(
            x, deepseek.init_layer(key, l, cfg), MODEL)
        if experts is not None:
            chosen.append(np.asarray(experts))
    return np.asarray(kanana.reference_head(
        x, deepseek.init_ends(key, cfg), MODEL)), chosen


def test_a_mixed_chunk_step_gives_the_references_logits_and_the_decode_programs_cache(  # noqa: E501
        mixed_step):
    m = mixed_step
    for b in m["valid"]:
        want, _ = reference_walk(m["eng"].cfg, m["rows"][b])
        assert np.abs(m["logits"][b] - want[-1]).max() <= \
            FLOAT32_LOGIT_TOLERANCE, b
        np.testing.assert_allclose(m["logits"][b], m["last"][b], atol=2e-6)
    for name in deepseek.CACHE_TOKEN_AXIS:
        np.testing.assert_allclose(m["after"][name], m["by_step"][name],
                                   atol=2e-6)
        for b in m["valid"]:                       # and they were written
            at = slice(m["pos0"][b], m["pos0"][b] + m["length"][b])
            assert np.abs(m["after"][name][:, b, at]).max(axis=-1).all()


def test_a_slot_of_one_lane_rides_a_chunk_step_as_the_decode_program_takes_it(
        mixed_step):
    """Slot 1's one lane: the logits and the rows `decode_step` gives on the
    same cache, to a float32 sum's order (3e-8 here): its MLPs' rows ride a
    call with the further lanes of the slots that prefill (`lm.all_lanes`),
    where a pass of the first lanes alone gave the same bits."""
    m = mixed_step
    np.testing.assert_allclose(m["logits"][1], m["first"][1], atol=2e-7)
    for name in deepseek.CACHE_TOKEN_AXIS:
        np.testing.assert_allclose(m["after"][name][:, 1],
                                   m["by_step"][name][:, 1], atol=2e-7)


def test_a_mixed_chunk_step_leaves_what_it_was_not_handed(mixed_step):
    m = mixed_step
    for name in deepseek.CACHE_TOKEN_AXIS:
        was, now = m["start"][name], m["after"][name]
        for b in range(len(MIXED)):
            end = m["pos0"][b] + (m["length"][b] if b in m["valid"] else 0)
            np.testing.assert_array_equal(now[:, b, :m["pos0"][b]],
                                          was[:, b, :m["pos0"][b]])
            np.testing.assert_array_equal(now[:, b, end:], was[:, b, end:])
        for b in (0, 4):             # no length; inactive with seven lanes
            np.testing.assert_array_equal(now[:, b], was[:, b])


def test_the_chunk_programs_counts_are_over_the_lanes_the_plan_handed_out(
        mixed_step):
    m = mixed_step
    cfg = m["eng"].cfg
    E, K = cfg.n_experts, cfg.experts_per_token
    given = np.zeros((cfg.n_layer - cfg.n_dense_layer, E), np.int64)
    attended = touched_a_slot = 0
    for b in m["valid"]:
        _, chosen = reference_walk(cfg, m["rows"][b])
        for l, experts in enumerate(chosen):
            own = np.bincount(experts[m["pos0"][b]:].reshape(-1), minlength=E)
            given[l] += own
            touched_a_slot += int((own > 0).sum())
        attended += sum(range(m["pos0"][b] + 1,
                              m["pos0"][b] + m["length"][b] + 1))
    lanes = int(m["length"][m["valid"]].sum())
    assert lanes == 1 + 5 + 16 + 16
    moved = (m["after"]["counts"].astype(np.int64)
             - m["start"]["counts"].astype(np.int64))
    assert moved[0].tolist() == [0] * 6       # the decode program's row
    assert dict(zip(deepseek.COUNTS, moved[1].tolist())) == {
        "expert_rows": lanes * K * len(given),
        "experts_touched": int((given > 0).sum()),
        "busiest_expert_rows": int(given.max(axis=1).sum()),
        "expert_layer_steps": len(given),
        "attended_positions": attended,
        # the plain form: all T of a slot for its first lane with every
        # slot's, and all T again for its further lanes against its own
        "read_positions": 96 * (len(m["valid"]) + int(
            (m["length"][m["valid"]] > 1).sum()))}
    # over the union of the step's lanes, not slot by slot
    assert int((given > 0).sum()) < touched_a_slot



@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_both_programs_count_the_positions_read_beside_the_attended(
        monkeypatch, form):
    """`read_positions` beside `attended_positions` in both programs' rows
    of the counts: a prompt of 37 in chunks of 16, then two decode steps at
    positions 37 and 38. The plain form reads all T = 96 positions of a
    live slot a layer's call; the kernel (interpreted, blocks of 16) a
    slot's position rounded up to a block for every slot's first lane, and
    the further lanes stay the plain form's. What was attended is the
    same, and so are the logits."""
    T, block = 96, 16
    bf16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
    _, plain = through_the_programs(engine(bf16), PROMPT, 3)
    if form == "kernel":
        monkeypatch.setattr(slot_rows, "BLOCK", block)
        # the layer calls the kernel where it lives, `models/mla.py`; the
        # family counts what it read
        for module, name in ((mla, "mla_attend"),
                             (deepseek, "read_positions")):
            monkeypatch.setattr(module, name, functools.partial(
                getattr(ma, name), interpret=True))
    eng = engine(bf16)
    _, logits = through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(deepseek.COUNTS, row)) for row in np.asarray(
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
        np.testing.assert_allclose(logits, plain, atol=5e-2)
        assert np.abs(plain).max() > 0.5


# ------------------------------------------------------- latent attention

@functools.lru_cache(maxsize=None)
def seeded(cfg):
    return deepseek.init_params(jax.random.key(SEED), cfg)


def one_layer(cfg, l=1):
    return jax.tree.map(lambda a: a[l - cfg.n_dense_layer],
                        seeded(cfg)["blocks"])


def every_layers_experts(cfg):
    return deepseek._expert_stack(seeded(cfg)["blocks"], cfg)


def test_absorbed_attention_is_plain_attention():
    """`deepseek._attention` (queries folded into the latent, the cache
    read as it is) against the reference's keys and values by head, one
    layer, a whole sequence in one chunk."""
    cfg = tiny(**F32)
    bp = one_layer(cfg)
    T = 24
    x = jax.random.normal(jax.random.key(1), (1, T, cfg.d_model))
    cache = deepseek.init_cache(cfg, 1, T)
    pos = jnp.arange(T)[None]
    got, lat, kr = deepseek._attention(
        x, bp, cfg, cache["latent"], cache["k_rope"], 1,
        jnp.zeros((1,), jnp.int32), pos, jnp.ones((1, T), bool))
    # the reference's layer with the expert part taken off again
    dense = {**{k: bp[k] for k in ("attn_norm", "attn", "mlp_norm")},
             "mlp": {"wg": jnp.zeros((cfg.d_model, 8)),
                     "wu": jnp.zeros((cfg.d_model, 8)),
                     "wd": jnp.zeros((8, cfg.d_model))}}
    want, _ = kanana.reference_layer(x[0], dense, MODEL)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-6)
    assert float(jnp.abs(got - x).max()) > 1e-3        # it did something


def test_the_rotary_key_is_one_for_all_heads_and_the_cache_holds_it():
    cfg = tiny(**F32)
    bp = one_layer(cfg)
    T = 16
    x = jax.random.normal(jax.random.key(2), (1, T, cfg.d_model))
    cache = deepseek.init_cache(cfg, 1, T)
    _, lat, kr = deepseek._attention(
        x, bp, cfg, cache["latent"], cache["k_rope"], 2,
        jnp.zeros((1,), jnp.int32), jnp.arange(T)[None],
        jnp.ones((1, T), bool))
    h = kanana._rms_norm(x[0], bp["attn_norm"]["scale"], cfg.norm_eps)
    ckr = h @ bp["attn"]["wkva"]
    want_c = kanana._rms_norm(ckr[:, :32], bp["attn"]["kv_norm"]["scale"],
                              cfg.norm_eps)
    want_kr = kanana._rope(ckr[:, None, 32:], jnp.arange(T), 1e6)[:, 0]
    np.testing.assert_allclose(np.asarray(lat[2, 0]), np.asarray(want_c),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(kr[2, 0]), np.asarray(want_kr),
                               atol=1e-5)
    assert kr.shape == (3, 1, T, 8) and lat.shape == (3, 1, T, 32)
    assert not np.asarray(lat[1]).any()         # only layer 2 was written


def test_the_cache_holds_576_values_a_token_a_layer_at_the_published_widths():
    cfg = deepseek.DeepseekConfig.preset("kanana-2-30b-a3b", n_layer=8)
    cache = jax.eval_shape(lambda: deepseek.init_cache(cfg, 32, 4096))
    per_token = {k: cache[k] for k in deepseek.CACHE_TOKEN_AXIS}
    assert {k: v.shape for k, v in per_token.items()} == {
        "latent": (8, 32, 4096, 512), "k_rope": (8, 32, 4096, 64)}
    values = sum(v.size for v in per_token.values()) // (8 * 32 * 4096)
    assert values == 576 == cfg.cache_width
    assert set(cache) - set(per_token) == {"counts"}
    # by head it would be 32 x (192 + 128) = 10,240 values
    assert cfg.n_head * (cfg.qk_head_dim + cfg.v_head_dim) == 10240
    assert deepseek.num_params(cfg) == 5_069_642_624


# ------------------------------------------------------------------ router

def routed(x2, router, bias, **cfg):
    return moe._route(x2, router, tiny(**F32, **cfg), bias)


def test_selection_is_by_score_plus_bias_and_the_gates_by_score_alone():
    x2 = jax.random.normal(jax.random.key(3), (40, 64))
    router = jax.random.normal(jax.random.key(4), (64, 8)) * 0.3
    bias = jnp.array([1.5, 0, 0, 0, 0, 0, 0, -1.5])
    logits, s, gates, experts = routed(x2, router, bias)
    np.testing.assert_allclose(np.asarray(s),
                               np.asarray(jax.nn.sigmoid(x2 @ router)),
                               atol=1e-6)
    want = np.argsort(-np.asarray(s + bias), axis=-1)[:, :3]
    assert (np.sort(np.asarray(experts)) == np.sort(want)).all()
    kept = np.take_along_axis(np.asarray(s), np.asarray(experts), axis=-1)
    np.testing.assert_allclose(
        np.asarray(gates), kept / kept.sum(-1, keepdims=True) * 2.448,
        rtol=1e-6)
    # the two orders differ: the favoured expert is always chosen, the
    # other never, though by score alone each would be in some top 3
    by_score = np.argsort(-np.asarray(s), axis=-1)[:, :3]
    assert (np.asarray(experts) == 0).any(axis=-1).all()
    assert not (np.asarray(experts) == 7).any()
    assert not (by_score == 0).any(axis=-1).all() and (by_score == 7).any()
    # and a gate is the score, not score + bias
    _, _, unnormed, _ = routed(x2, router, bias, norm_topk_prob=False,
                               routed_scaling_factor=1.0)
    np.testing.assert_allclose(np.asarray(unnormed), kept, rtol=1e-6)


def test_the_presets_that_were_there_route_as_before():
    x2 = jax.random.normal(jax.random.key(3), (16, 128))
    for name, normed in (("olmoe-1b-7b", False), ("mixtral-8x7b", True)):
        cfg = moe.MoEConfig.preset(name)
        assert (cfg.router_scoring, cfg.routed_scaling_factor) == (
            "softmax", 1.0)
        router = jax.random.normal(jax.random.key(4), (128, cfg.n_experts))
        _, probs, gates, experts = moe._route(x2, router, cfg)
        top, idx = jax.lax.top_k(jax.nn.softmax(x2 @ router, -1),
                                 cfg.experts_per_token)
        if normed:
            top = top / top.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(gates), np.asarray(top),
                                   rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(experts), np.asarray(idx))
    with pytest.raises(ValueError, match="router_scoring"):
        moe._route(x2, router, moe.MoEConfig.preset(
            "moe-tiny", router_scoring="tanh"))


def test_the_router_runs_in_float32_whatever_the_compute_dtype():
    """The serving programs hand the router a bf16 residual stream: the
    product, the sigmoid and the top-k are float32 all the same, and a
    bf16 product of the same inputs is told from it."""
    cfg = tiny()                                      # bf16 compute
    x2 = jax.random.normal(jax.random.key(5), (64, 64)).astype(jnp.bfloat16)
    router = jax.random.normal(jax.random.key(6), (64, 8)) * 0.3
    logits, s, gates, _ = moe._route(x2, router, cfg, jnp.zeros((8,)))
    assert logits.dtype == s.dtype == gates.dtype == jnp.float32
    exact = np.asarray(x2, np.float64) @ np.asarray(router, np.float64)
    in_bf16 = np.asarray((x2 @ router.astype(jnp.bfloat16)), np.float64)
    assert np.abs(np.asarray(logits) - exact).max() < 1e-5
    assert np.abs(in_bf16 - exact).max() > 1e-3       # what bf16 would give
    hlo = jax.jit(lambda a, b: moe._route(a, b, cfg, jnp.zeros((8,)))).lower(
        x2, router).as_text()
    dots = [l for l in hlo.splitlines() if "dot_general" in l]
    assert dots and all("f32" in l and "bf16" not in l.split("->")[-1]
                        for l in dots)
    assert "HIGHEST" in hlo


def test_the_expert_layer_is_the_dense_sum_over_all_experts_plus_the_shared():
    cfg = tiny(**F32)
    bp = one_layer(cfg, l=2)            # the stack's second eight experts
    x = jax.random.normal(jax.random.key(7), (2, 9, cfg.d_model))
    got, given = deepseek._expert_mlp(
        x, bp, every_layers_experts(cfg), 1, cfg,
        jnp.zeros((8,), jnp.int32), jnp.ones((2, 9), bool))
    counts = deepseek._expert_counts(given)
    h = kanana._rms_norm(x.reshape(18, -1), bp["mlp_norm"]["scale"],
                         cfg.norm_eps)
    _, _, gates, experts = moe._route(h, bp["moe"]["router"], cfg,
                                      bp["moe"]["bias"])
    dense_gates = np.zeros((18, 8), np.float32)
    np.put_along_axis(dense_gates, np.asarray(experts), np.asarray(gates), -1)
    want = x.reshape(18, -1) + kanana._swiglu(h, bp["shared"])
    for e in range(8):
        y = kanana._swiglu(h, {k: bp["moe"][k][e] for k in ("wg", "wu", "wd")})
        want = want + dense_gates[:, e:e + 1] * y
    np.testing.assert_allclose(np.asarray(got.reshape(18, -1)),
                               np.asarray(want), atol=2e-6)
    assert counts.tolist()[0] == 18 * 3 and counts.tolist()[3] == 1


def test_nothing_is_dropped_when_every_token_wants_the_same_experts():
    cfg = tiny(**F32)
    bp = one_layer(cfg)
    skew = {**bp, "moe": {**bp["moe"], "bias": jnp.array(
        [5., 5., 5., 0, 0, 0, 0, 0])}}
    x = jax.random.normal(jax.random.key(8), (1, 40, cfg.d_model))
    got, given = deepseek._expert_mlp(
        x, skew, every_layers_experts(cfg), 0, cfg,
        jnp.zeros((8,), jnp.int32), jnp.ones((1, 40), bool))
    rows, touched, busiest, _ = deepseek._expert_counts(given).tolist()
    assert (rows, touched, busiest) == (120, 3, 40)
    h = kanana._rms_norm(x[0], bp["mlp_norm"]["scale"], cfg.norm_eps)
    s = jax.nn.sigmoid(h @ bp["moe"]["router"])[:, :3]
    g = s / s.sum(-1, keepdims=True) * 2.448
    want = x[0] + kanana._swiglu(h, bp["shared"]) + sum(
        g[:, e:e + 1] * kanana._swiglu(
            h, {k: bp["moe"][k][e] for k in ("wg", "wu", "wd")})
        for e in range(3))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-6)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_stack_of_all_layers_experts_is_each_layers_own_sliced_out(
        monkeypatch, program):
    """Both programs hand the kernels the stack of both expert layers'
    experts with the ids offset by the layer. The form that went (PR 48)
    sliced a layer's eight out of the stack and gave them the layer's own
    ids: kept here as the reference, it gives the same logits, rows and
    counts to the bit (the cpu backend's `ragged_dot` multiplies a row by
    its group's matrix, whatever the other groups are). Offset by the
    layer's number where the expert layer's is meant (l E for (l - 1) E),
    the first expert layer reads the second's experts and the second none:
    the comparison tells that apart."""
    cfg = tiny()                                     # bf16, as it is served
    B, T, C = 4, 96, 16
    params = seeded(cfg)
    rng = np.random.default_rng(11)
    held = np.array([21, 9, 30, 14], np.int32)       # tokens a slot holds
    active = np.array([True, True, True, False])
    tokens = jnp.asarray(rng.integers(0, 512, (B, 32)), jnp.int32)
    cache = deepseek.init_cache(cfg, B, T)
    for at in (0, 16):                               # what the slots hold
        _, cache = deepseek.prefill_chunk(
            params, cache, tokens[:, at:at + 16], jnp.full((B,), at),
            jnp.clip(held - at, 0, 16), jnp.ones((B,), bool), cfg)
    start = jax.tree.map(np.asarray, cache)
    step = jnp.asarray(rng.integers(0, 512, (B, C)), jnp.int32)
    if program == "decode_step":
        args = (step[:, 0], jnp.asarray(held), jnp.asarray(active))
    else:           # slot 1 prefills 16 lanes, the others ride along with one
        args = (step, jnp.asarray(held), jnp.array([1, C, 1, 1], jnp.int32),
                jnp.asarray(active))
    stacked = deepseek._expert_mlp

    def run(expert_mlp):
        monkeypatch.setattr(deepseek, "_expert_mlp", expert_mlp)
        logits, after = jax.jit(
            lambda p, c, *a: getattr(deepseek, program)(p, c, *a, cfg))(
            params, jax.tree.map(jnp.asarray, start), *args)
        return np.asarray(logits)[active], jax.tree.map(np.asarray, after)

    def sliced(x, bp, stack, i, cfg, given, ok, packed=False):
        E = cfg.n_experts
        own = tuple(jax.lax.dynamic_slice_in_dim(w, i * E, E) for w in stack)
        return stacked(x, bp, own, 0, cfg, given, ok, packed)

    def by_the_layers_number(x, bp, stack, i, cfg, given, ok, packed=False):
        return stacked(x, bp, stack, i + cfg.n_dense_layer, cfg, given, ok,
                       packed)

    got, after = run(stacked)
    want, after_sliced = run(sliced)
    np.testing.assert_array_equal(got, want)
    for name in deepseek.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(after[name], after_sliced[name])
        assert not np.array_equal(after[name], start[name])
    row = int(program == "prefill_chunk")
    moved = after["counts"][row, :4] - start["counts"][row, :4]
    np.testing.assert_array_equal(after["counts"], after_sliced["counts"])
    lanes = 3 if program == "decode_step" else 2 + C
    assert moved[0] == lanes * cfg.experts_per_token * 2 and moved[3] == 2
    # (the tiny preset's experts add little to a stream the table sets:
    # 2e-3 of the logits' spread, a thousand roundings of a float32 sum)
    wrong, _ = run(by_the_layers_number)
    assert np.abs(wrong - want).max() > 1e-3 * (want.max() - want.min())


@pytest.mark.parametrize("rows,groups", [(192, 128), (24, 8), (8, 8)])
def test_the_grouped_matmul_kernel_at_few_rows_a_group(rows, groups):
    """The megablox kernel itself, interpreted, where most groups have one
    row or none (a decode step: 192 rows over 128 groups)."""
    rng = np.random.default_rng(rows)
    of = np.sort(rng.integers(0, groups, rows))
    sizes = jnp.asarray(np.bincount(of, minlength=groups), jnp.int32)
    lhs = jax.random.normal(jax.random.key(0), (rows, 256))
    rhs = jax.random.normal(jax.random.key(1), (groups, 256, 128))
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    got = grouped_matmul(lhs, rhs, sizes, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3,
                               rtol=1e-4)
    assert int((sizes == 0).sum()) > 0


# ----------------------------------------------------------------- weights

def test_a_layer_made_alone_is_the_layer_in_the_tree():
    cfg = tiny()
    key = jax.random.key(SEED)
    params = deepseek.init_params(key, cfg)
    for l in range(cfg.n_layer):
        stack, i = (("dense", l) if l < cfg.n_dense_layer
                    else ("blocks", l - cfg.n_dense_layer))
        alone = deepseek.init_layer(key, l, cfg)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a[i], np.float32), np.asarray(b, np.float32)),
            params[stack], alone)
    dtypes = {a.dtype.name for a in jax.tree.leaves(params)}
    assert dtypes == {"bfloat16", "float32"}
    assert params["blocks"]["moe"]["wg"].dtype == jnp.bfloat16
    assert params["blocks"]["moe"]["router"].dtype == jnp.float32
    assert float(jnp.abs(params["blocks"]["moe"]["bias"]).max()) > 0
    assert deepseek.resident_params(params, cfg) is params
    assert sum(a.size for a in jax.tree.leaves(params)) == \
        deepseek.num_params(cfg)


# ------------------------------------------------------------- prefix pool

def test_a_pool_hit_gives_the_logits_a_full_prefill_gives():
    eng = engine()
    chosen, plain = through_the_programs(eng, PROMPT, 6, slot=0)
    assert eng.kv.store_prefix(PROMPT, eng.cache, 0) == 4    # 37 // 8
    n_hit, blocks = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(blocks) == 4
    counts = np.asarray(eng.cache["counts"]).copy()
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, blocks)
    np.testing.assert_array_equal(np.asarray(eng.cache["counts"]), counts)
    for leaf in deepseek.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[leaf][:, 2, :32]),
            np.asarray(eng.cache[leaf][:, 0, :32]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, plain, atol=1e-5)


def test_the_pool_over_latent_leaves_evicts_and_takes_a_block_of_128():
    cfg = deepseek.DeepseekConfig.preset("deepseek-tiny", max_seq_len=512)
    cache = deepseek.init_cache(cfg, 2, 512)
    cache = {**cache, "latent": cache["latent"] + 1.0}
    kv = PagedKVCache.for_cache(cache, deepseek.CACHE_TOKEN_AXIS,
                                num_blocks=3, block_size=128)
    assert {k: v.shape for k, v in kv.pools.items()} == {
        "latent": (3, 3, 128, 32), "k_rope": (3, 3, 128, 8)}
    first, second = list(range(256)), list(range(1000, 1384))
    assert kv.store_prefix(first, cache, 0) == 2
    assert kv.store_prefix(second, cache, 1) == 3     # evicts both of first
    assert kv.stats()["blocks_evicted"] == 2
    assert kv.match_prefix(first) == (0, [])
    n, blocks = kv.match_prefix(second)
    assert n == 384
    out = kv.copy_into_slot(deepseek.init_cache(cfg, 2, 512), 0, blocks)
    assert float(out["latent"][:, 0, :384].min()) == 1.0
    assert not np.asarray(out["latent"][:, 0, 384:]).any()
    assert not np.asarray(out["latent"][:, 1]).any()
    assert "counts" in out


def test_gpt2s_pool_is_the_two_arrays_it_was():
    kv = PagedKVCache(n_layer=2, n_head=3, head_dim=4, num_blocks=5,
                      block_size=8)
    assert kv.pool_k.shape == kv.pool_v.shape == (2, 5, 3, 8, 4)
    assert list(kv.pools) == ["k", "v"]
    assert kv._copiers["k"] is kv._copiers["v"]        # one pair of programs
    leaf = jnp.zeros((2, 2, 3, 32, 4))
    text = kv._copy_out.lower(kv.pool_k, leaf,
                              kv._plan({"k": leaf}, 0, rows=[(1, 0)])
                              ).as_text()
    # the loop's one window of the leaf into the pool; the other slices
    # read the plan
    assert text.count("sizes = [2, 1, 3, 8, 4]") == 1
    assert text.count("dynamic_update_slice") == 1


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    from ray_tpu.models import gpt2

    assert serving_family("gpt2-1.5b") == ("gpt2", gpt2, gpt2.GPT2Config)
    for preset in deepseek.PRESETS:
        assert serving_family(preset) == ("deepseek", deepseek,
                                          deepseek.DeepseekConfig)
    with pytest.raises(ValueError, match="llama-7b"):
        serving_family("llama-7b")
    for module in (gpt2, deepseek):
        for name in ("init_params", "resident_params", "resident_specs",
                     "init_cache", "decode_step", "prefill_chunk",
                     "CACHE_TOKEN_AXIS"):
            assert hasattr(module, name), (module, name)
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        text = f.read()
    assert "ray_tpu.models import gpt2" not in text
    assert "deepseek" not in text.replace("deepseek.init_cache", "")


@pytest.mark.parametrize("kwargs,what", [
    (dict(checkpoint="/nowhere"), "checkpoint="),
    (dict(tensor_parallel_size=2), "tensor_parallel_size")])
def test_what_is_gpt2s_refuses_the_family_by_name(kwargs, what):
    with pytest.raises(NotImplementedError, match="deepseek") as e:
        LLMEngine(preset="deepseek-tiny", **kwargs)
    assert what in str(e.value)


def test_the_transfers_and_lora_refuse_the_family_by_name(tmp_path):
    srv = OpenAIServer(preset="deepseek-tiny", max_batch=2, max_seq_len=64,
                       lora_root=str(tmp_path), kv_block_size=8)
    try:
        for call in (lambda: srv.engine.export_prefix(prompt_ids=[1, 2, 3]),
                     lambda: srv.engine.import_prefix({"ids": []}),
                     lambda: srv.engine.prefix_model_key,
                     lambda: srv({"model": "ray-tpu-llm:adapter",
                                  "prompt_ids": [1, 2]})):
            with pytest.raises(NotImplementedError, match="deepseek"):
                call()
    finally:
        srv.engine.shutdown()


def test_one_streamed_completion_through_the_openai_server():
    srv = OpenAIServer(preset="deepseek-tiny", max_batch=2, max_seq_len=96,
                       seed=SEED, model_overrides=dict(F32), kv_blocks=12,
                       kv_block_size=8, prefill_chunk_size=16)
    try:
        body = {"prompt_ids": PROMPT, "max_tokens": 6, "temperature": 0.0,
                "stream": True}
        sid = srv(body)["__sse_stream__"]["stream_id"]
        ids, cursor, deadline = [], 0, time.time() + 120
        while time.time() < deadline:
            out = srv.stream_next(sid, cursor=cursor)
            ids += out["token_ids"]
            cursor = out["cursor"]
            if out["done"]:
                break
        assert out["done"] and out["finish_reason"] == "length"
        # what the programs give by hand is what the loop served
        by_hand, _ = through_the_programs(engine(), PROMPT, 6)
        assert ids == by_hand
        again = srv({"prompt_ids": PROMPT, "max_tokens": 6,
                     "temperature": 0.0})
        assert again["choices"][0]["token_ids"] == ids
        stats = srv.stats()
        assert stats["kv_cache"]["tokens_reused"] == 32
        assert stats["kv_bytes_per_token"] == 3 * 40 * 4
        decode, chunk = (stats["step_counts"][k] for k in ("decode", "chunk"))
        assert decode["expert_layer_steps"] == 2 * 5 * 2   # layers x steps
        assert decode["expert_rows"] == 3 * decode["expert_layer_steps"]
        assert chunk["expert_rows"] == 3 * 2 * (37 + 5)
        assert chunk["attended_positions"] == sum(range(1, 38)) + sum(
            range(33, 38))
        # the plain form reads all 96 positions: three chunks then one, a
        # first lane and further lanes each; five decode steps a request
        assert chunk["read_positions"] == 96 * 2 * (3 + 1)
        assert decode["read_positions"] == 96 * 5 * 2
        assert decode["attended_positions"] == 2 * sum(range(38, 43))
        assert stats["moe_expert_rows"] == (decode["expert_rows"]
                                            + chunk["expert_rows"])
        assert 0 < stats["moe_experts_touched"] <= stats["moe_expert_rows"]
    finally:
        srv.engine.shutdown()


def test_a_chunk_of_one_token_counts_as_a_chunk_and_a_failed_read_is_raised():
    """Each program names its own row of the counts (a chunk program of
    one lane a slot has the decode program's shapes), and only a wedged
    loop's timeout leaves the counters out of the stats."""
    eng = LLMEngine(preset="deepseek-tiny", max_batch=2, max_seq_len=32,
                    seed=SEED, prefill_chunk_size=1, enable_prefix_caching=False)
    try:
        eng.generate(prompt_ids=[3, 4, 5, 6], max_tokens=3)
        counts = eng.engine_stats()["step_counts"]
        assert counts["chunk"]["expert_layer_steps"] == 2 * 4
        assert counts["decode"]["expert_layer_steps"] == 2 * 2
    finally:
        eng.shutdown()
    eng._thread.join()
    eng.cache = {"counts": None}        # a renamed or broken leaf
    with pytest.raises(TypeError):
        eng.engine_stats()


@pytest.mark.parametrize("preset", ["deepseek-tiny", "brumby-tiny"],
                         ids=["rows", "state"])
def test_the_engine_counts_the_lanes_its_plan_hands_the_chunk_steps(preset):
    """`chunk_tokens`: the lanes of the chunk steps that were a token's, a
    decode lane riding along among them; `chunk_prefilling_slots`: the
    slots that had more than one. A prompt's last token alone is a chunk
    step and no such slot. `chunk_steps_one_dispatch`: the chunk steps
    whose further lanes fitted the rows of the first lanes' call, 2 + 8 - 2
    here, which the default budget never exceeds with both slots busy;
    `chunk_lanes_packed`: the further lanes they carried."""
    eng = LLMEngine(preset=preset, max_batch=2, max_seq_len=96, seed=SEED,
                    prefill_chunk_size=8, enable_prefix_caching=False)

    def counted():
        stats = eng.engine_stats()
        return tuple(stats[k] for k in (
            "chunk_steps", "chunk_tokens", "chunk_prefilling_slots",
            "chunk_steps_one_dispatch", "chunk_lanes_packed"))

    try:
        assert counted() == (0, 0, 0, 0, 0)
        eng.generate(prompt_ids=list(range(3, 20)), max_tokens=3)
        assert counted() == (3, 17, 2, 3, 14)         # 8, 8 and 1 lanes
        # ten tokens beside a slot that decodes: 1 + 8, then 1 + 2
        sid = eng.start_stream(prompt_ids=[5, 6, 7, 8, 9], max_tokens=80)
        while not eng.stream_next(sid, timeout=30.0)["token_ids"]:
            pass
        assert counted() == (4, 22, 3, 4, 18)
        eng.generate(prompt_ids=list(range(30, 40)), max_tokens=2)
        assert not eng._streams[sid][0].done.is_set()
        assert counted() == (6, 34, 5, 6, 26)
        assert eng.engine_stats()["tokens_prefilled"] == 17 + 5 + 10
    finally:
        eng.shutdown()


def test_a_larger_budgets_step_of_more_lanes_than_a_call_is_counted_apart():
    """Two prompts prefilling at once under a budget of 16 at 2 slots and
    chunks of 8: a step of 8 + 8 lanes has 14 further ones for the 8 rows
    behind the first lanes, so its second slot goes a round of its own
    (`lm.lane_rounds`) and the step is no `chunk_steps_one_dispatch`; the
    replies are the ones each prompt gets alone."""
    prompts = [list(range(3, 44)), list(range(50, 91))]
    eng = LLMEngine(preset="deepseek-tiny", max_batch=2, max_seq_len=96,
                    seed=SEED, prefill_chunk_size=8,
                    max_num_batched_tokens=16, enable_prefix_caching=False)
    try:
        alone = [eng.generate(prompt_ids=p, max_tokens=4)["token_ids"]
                 for p in prompts]
        before = eng.engine_stats()
        sids = [eng.start_stream(prompt_ids=p, max_tokens=4,
                                 temperature=0.0) for p in prompts]
        got = []
        for sid in sids:
            ids = []
            while True:
                out = eng.stream_next(sid, cursor=len(ids), timeout=60.0)
                ids += out["token_ids"]
                if out["done"]:
                    break
            got.append(ids)
        after = eng.engine_stats()
    finally:
        eng.shutdown()
    assert got == alone
    steps = after["chunk_steps"] - before["chunk_steps"]
    fitted = (after["chunk_steps_one_dispatch"]
              - before["chunk_steps_one_dispatch"])
    assert 0 < fitted < steps <= 12
    assert before["chunk_steps_one_dispatch"] == before["chunk_steps"] == 12


def test_gpt2s_stats_gain_the_gauge_and_no_counter():
    eng = LLMEngine(preset="gpt2-tiny", max_batch=2, max_seq_len=32)
    try:
        stats = eng.engine_stats()
        assert stats["kv_bytes_per_token"] == 2 * 2 * 4 * 32 * 2   # bf16 k, v
        assert "step_counts" not in stats and "moe_expert_rows" not in stats
    finally:
        eng.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    eng = engine()
    ints, on = np.zeros((3,), np.int32), np.zeros((3,), bool)
    step = eng._step.lower(eng.params, eng.cache, ints, ints, on)
    chunk = eng._chunk_step.lower(eng.params, eng.cache,
                                  np.zeros((3, 16), np.int32), ints, ints, on)
    assert "module @jit__step " in step.as_text()
    assert "module @jit__chunk " in chunk.as_text()
    for lowered in (step, chunk):
        text = lowered.as_text(debug_info=True)
        for scope in ("embed", "attn/mla_project", "attn/kv_update",
                      "attn/mla_attend", "mlp/moe_router", "mlp/moe_dispatch",
                      "mlp/moe_experts", "mlp/moe_shared", "unembed_loss",
                      "layers"):
            assert scope in text, scope
