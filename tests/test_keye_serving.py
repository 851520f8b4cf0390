"""Keye-VL-2.0's language model through the serving path on the CPU at a
tiny size: grouped-head attention over the rows a learned indexer chooses
(the choice by index in the decode program and every slot's first lane, as
a mask in a chunk's further lanes) and routed experts, against the plain
reference's full forward pass, at positions below, at and past a small
topk; the two forms of the choice against the reference's stable sort on
equal scores; the three position streams; the cache's contract in the
engine (three leaves a token untouched where inactive, pooled by the block,
found again); and what the family refuses by name."""

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

from families import keye as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import deepseek, keye, llama, serving_family  # noqa: E402
from ray_tpu.ops import dsa, slot_rows  # noqa: E402
from ray_tpu.serve.kv_cache import PagedKVCache  # noqa: E402
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

# the tiny preset in the source's key names, for the reference: 3 layers, 4
# query and 2 key-value heads of 16, an indexer of 2 heads of 8 that keeps
# 16 rows, 8 experts of which a token takes 3
MODEL = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
         "hidden_act": "silu", "hidden_size": 64, "mlp_only_layers": [],
         "moe_intermediate_size": 32, "norm_topk_prob": True,
         "num_attention_heads": 4, "num_experts": 8,
         "num_experts_per_tok": 3, "num_hidden_layers": 3,
         "num_key_value_heads": 2, "num_local_experts": 8,
         "rms_norm_eps": 1e-6,
         "rope_scaling": {"mrope_section": [2, 3, 3],
                          "rope_type": "default", "type": "default"},
         "rope_theta": 10000000,
         "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                       "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                       "q_chunk_size": 512, "topk": 16},
         "tie_word_embeddings": False, "use_sliding_window": False,
         "vocab_size": 512}
TOPK = 16
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
# 37 tokens: a chunk of 16 ends at the topk, where the selection is still
# the identity; every later lane and every decode step stands past it
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return keye.KeyeConfig.preset(
        "keye-tiny", **{**family.program_sizes(MODEL), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == keye.KeyeConfig.preset("keye-tiny")
    assert tiny().index_topk == TOPK < len(PROMPT)


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    eng = LLMEngine(preset="keye-tiny", max_batch=3, max_seq_len=96,
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


def reference_logits(cfg, row, at, degrade=None, model=MODEL):
    key = jax.random.key(SEED)
    ref = family.Reference(model, lambda l: keye.init_layer(key, l, cfg),
                           keye.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


# Float32 compute against the float32 reference: the same sums in another
# order (the choice by `top_k` or by the k-th value against a stable sort;
# attention over gathered rows, or over a slot's rows under a mask, against
# a block of queries over the sequence): 1.2e-7 at the worst position on
# logits of spread 0.16 here. bf16 compute against it (the reference reads
# the same bf16 weights and a product's activation goes as two bf16 pieces,
# so what is left is the rounding of q, of the cached rows and of
# attention's weights, and a set that differs by a row where the indexer's
# bf16 keys put two scores the other way round): 8.3e-3 at the worst
# position, where one such row was swapped, and 3.4e-4 in the mean. A dense
# attend moves the float32 logits by 1.8e-2 at their worst position (3.5e-3
# in the mean), a window of the last 16 by 2.4e-2 (4.7e-3) and a topk of 8
# by 2.3e-2 (4.3e-3), rows through float8 by 8.1e-3 (8.9e-4): thousands of
# times the float32 tolerance, and (but for float8's) past both bf16 ones.
# The indexer's scores through bfloat16 choose another set at 48 positions
# only where two scores within 0.4% of each other straddle the boundary:
# `test_scores_through_bfloat16_choose_another_set` plants that.
FLOAT32_LOGIT_TOLERANCE = 3e-6
BF16_LOGIT_TOLERANCE = 1.2e-2
BF16_LOGIT_MEAN_TOLERANCE = 1e-3


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no cache, no chunks, the selection a
    mask from a stable sort): the logits at every generated position,
    whatever the chunks' boundaries. 37 tokens in chunks of 16 (the first
    ends at the topk), of 7 and 8 (a first lane at, below and past the
    topk), and of 64 (one chunk: 36 further lanes, 20 of them past it)."""
    eng = engine(compute, chunk=chunk)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(eng.cfg, row, list(range(len(PROMPT) - 1,
                                                     len(row))))
    assert got.shape == want.shape == (N_DECODE, 512)
    assert np.abs(got - want).max() <= tolerance
    if compute is F32:
        assert chosen == want.argmax(axis=-1).tolist()
    else:
        assert np.abs(got - want).mean() <= BF16_LOGIT_MEAN_TOLERANCE


@pytest.mark.parametrize("degrade,bf16_too", [
    ("dense_attend", True), ("window", True), ("half_topk", True),
    ("float8_rows", False)])
def test_a_degraded_reference_is_refused_by_the_tolerance(degrade, bf16_too):
    """Another mathematics (no selection, a window, half the topk) or rows
    below the stated precision: past the float32 tolerance by orders of
    magnitude, and the three other models past the bf16 one too."""
    eng = engine()
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    off = reference_logits(eng.cfg, row,
                           list(range(len(PROMPT) - 1, len(row))), degrade)
    worst = np.abs(got - off).max()
    assert worst > 100 * FLOAT32_LOGIT_TOLERANCE
    assert (worst > BF16_LOGIT_TOLERANCE) == bf16_too
    assert (np.abs(got - off).mean() > BF16_LOGIT_MEAN_TOLERANCE) == bf16_too


def test_scores_through_bfloat16_choose_another_set():
    """Two scores 0.1% apart on either side of the boundary are one bfloat16
    value: through bfloat16 the tie goes to the lower index, which float32
    had left out."""
    rng = np.random.default_rng(3)
    scores = rng.permutation(np.linspace(-1.0, 1.0, 60)).astype(np.float32)
    order = np.argsort(-scores, kind="stable")
    inside, outside = sorted(order[TOPK - 1:TOPK + 1])[::-1]  # high index in
    scores[inside], scores[outside] = 0.5003, 0.5001
    scores[np.setdiff1d(order[:TOPK - 1], [inside, outside])] += 1.0
    scores[np.setdiff1d(order[TOPK + 1:], [inside, outside])] -= 1.0
    exact = np.asarray(family._selected(jnp.asarray(scores)[None], TOPK))[0]
    rounded = np.asarray(family._selected(
        family._through_bfloat16(jnp.asarray(scores))[None], TOPK))[0]
    assert exact[inside] and not exact[outside]
    assert rounded[outside] and not rounded[inside]
    # the program's two forms keep float32's set
    idx, chosen = dsa.select_rows(jnp.asarray(scores)[None], TOPK)
    assert chosen.all() and set(np.asarray(idx)[0]) == set(
        np.flatnonzero(exact))
    np.testing.assert_array_equal(
        np.asarray(dsa.select_mask(jnp.asarray(scores)[None], TOPK))[0],
        exact)


# ---------------------------------------------------------------- the choice

def _scores(case: str):
    """[queries, T] float32 scores with -inf past each query's position."""
    rng = np.random.default_rng(11)
    T = 48
    at = np.array([3, 15, 16, 30, 47])
    s = rng.standard_normal((len(at), T)).astype(np.float32)
    if case == "ties":
        # a handful of values, so that the boundary falls inside a run of
        # equal scores in every row
        s = rng.integers(0, 4, (len(at), T)).astype(np.float32)
    elif case == "all-equal":
        s = np.zeros((len(at), T), np.float32)
    elif case == "relu-zeros":
        # what a ReLU leaves: exact zeros, here so many that the boundary
        # falls among them
        s = np.maximum(s - 1.0, 0.0)
    return np.where(np.arange(T)[None] <= at[:, None], s, -np.inf), at


@pytest.mark.parametrize("case", ["distinct", "ties", "all-equal",
                                  "relu-zeros"])
def test_both_forms_choose_the_references_set_on_equal_scores(case):
    """`select_rows` (by index) and `select_mask` against the reference's
    stable sort: the same set, ties to the lower index, and all of a
    query's positions while it has fewer than topk."""
    scores, at = _scores(case)
    want = np.asarray(family._selected(jnp.asarray(scores), TOPK)) \
        & (scores > -np.inf)
    assert want.sum(axis=1).tolist() == np.minimum(at + 1, TOPK).tolist()
    np.testing.assert_array_equal(
        np.asarray(dsa.select_mask(jnp.asarray(scores), TOPK)), want)
    idx, chosen = (np.asarray(a) for a in dsa.select_rows(
        jnp.asarray(scores), TOPK))
    for q in range(len(at)):
        assert sorted(idx[q][chosen[q]]) == np.flatnonzero(want[q]).tolist()
        assert chosen[q].sum() == min(at[q] + 1, TOPK)
    if case != "distinct":
        # a tie at the boundary went to the lower index
        q = len(at) - 1
        kth = np.sort(scores[q])[::-1][TOPK - 1]
        level = np.flatnonzero(scores[q] == kth)
        kept = [i for i in level if want[q, i]]
        assert kept == level[:len(kept)].tolist() and len(kept) < len(level)


def test_a_topk_as_long_as_the_rows_keeps_every_row_seen():
    scores, at = _scores("distinct")
    mask = np.asarray(dsa.select_mask(jnp.asarray(scores), 48))
    np.testing.assert_array_equal(mask, scores > -np.inf)
    idx, chosen = dsa.select_rows(jnp.asarray(scores), 64)
    assert idx.shape == (5, 48) and np.asarray(chosen).sum(1).tolist() == (
        at + 1).tolist()


def test_the_indexers_scores_are_the_references_sum():
    rng = np.random.default_rng(2)
    qi = rng.standard_normal((2, 3, 2, 8)).astype(np.float32)
    w = rng.standard_normal((2, 3, 2)).astype(np.float32)
    rows = rng.standard_normal((2, 20, 8)).astype(np.float32)
    at = np.array([[4, 5, 6], [17, 18, 19]])
    got = np.asarray(dsa.index_scores(jnp.asarray(qi), jnp.asarray(w),
                                      jnp.asarray(rows), jnp.asarray(at)))
    want = np.einsum("nqjt,nqj->nqt", np.maximum(
        np.einsum("nqje,nte->nqjt", qi, rows), 0.0), w)
    seen = np.arange(20) <= at[..., None]
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-5, atol=1e-5)
    assert np.isneginf(got[~seen]).all()
    # rows held in bfloat16: the query goes as two pieces, so what is left
    # is the rows' own rounding, nothing of the query's
    held = jnp.asarray(rows).astype(jnp.bfloat16)
    got16 = np.asarray(dsa.index_scores(jnp.asarray(qi), jnp.asarray(w),
                                        held, jnp.asarray(at)))
    exact = np.einsum("nqjt,nqj->nqt", np.maximum(np.einsum(
        "nqje,nte->nqjt", qi, np.asarray(held.astype(jnp.float32))), 0.0), w)
    np.testing.assert_allclose(got16[seen], exact[seen], rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ the positions

def test_three_equal_streams_are_plain_rope():
    cfg = tiny()
    at = jnp.asarray([[0, 1, 2, 50], [7, 8, 9, 90]])
    (cos, sin), (cos_i, sin_i) = keye.rope_angles(
        jnp.broadcast_to(at, (3, 2, 4)), cfg)
    plain = llama.rope_freqs(at, cfg.head_dim, cfg.rope_theta)
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(plain[0]))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(plain[1]))
    plain_i = llama.rope_freqs(at, cfg.index_head_dim, cfg.rope_theta)
    np.testing.assert_array_equal(np.asarray(cos_i), np.asarray(plain_i[0]))
    np.testing.assert_array_equal(np.asarray(sin_i), np.asarray(plain_i[1]))


def test_unequal_streams_turn_each_pair_by_its_sections_stream():
    cfg = tiny(rope_theta=100.0)
    positions = jnp.asarray([[[5]], [[11]], [[23]]])           # [3, 1, 1]
    (cos, _), (cos_i, _) = keye.rope_angles(positions, cfg)
    inv = 1.0 / 100.0 ** (np.arange(0, 16, 2) / 16)
    stream = [5, 5, 11, 11, 11, 23, 23, 23]                # sections 2, 3, 3
    np.testing.assert_allclose(np.asarray(cos)[0, 0], np.cos(stream * inv),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(cos_i)[0, 0],
        np.cos(5 * (1.0 / 100.0 ** (np.arange(0, 8, 2) / 8))), rtol=1e-5,
        atol=1e-6)


@pytest.mark.parametrize("streams", ["text", "image"])
def test_the_programs_take_three_position_streams(streams):
    """A chunk and then decode steps at positions the caller gives, against
    the reference at the same: text (three equal streams: what the engine
    sends, and what passing none gives) and an image's patches (a height
    and a width that differ from the row index)."""
    cfg = tiny(**F32)
    key = jax.random.key(SEED)
    params = keye.init_params(key, cfg)
    n, steps = 29, 4
    row = PROMPT[:n + steps]
    index = np.arange(n + steps)
    if streams == "text":
        positions = np.broadcast_to(index, (3, n + steps))
    else:
        positions = np.stack([index // 3, (index * 5) % 11, index % 7 + 2])
    x = keye.init_ends(key, cfg)["wte"][jnp.asarray(row)].astype(jnp.float32)
    for l in range(cfg.n_layer):
        x = family.reference_layer(x, keye.init_layer(key, l, cfg), MODEL,
                                   jnp.asarray(positions))
    want = np.asarray(family.reference_head(x, keye.init_ends(key, cfg),
                                            MODEL))[n - 1:]
    cache = keye.init_cache(cfg, 2, 64)
    tokens = np.zeros((2, 32), np.int32)
    tokens[1, :n] = row[:n]
    chunk_at = np.zeros((3, 2, 32), np.int32)
    chunk_at[:, 1, :n] = positions[:, :n]
    logits, cache = keye.prefill_chunk(
        params, cache, jnp.asarray(tokens), jnp.zeros((2,), jnp.int32),
        jnp.asarray([0, n]), jnp.asarray([False, True]), cfg,
        positions=jnp.asarray(chunk_at))
    got = [np.asarray(logits[1])]
    if streams == "text":
        again, _ = keye.prefill_chunk(
            params, keye.init_cache(cfg, 2, 64), jnp.asarray(tokens),
            jnp.zeros((2,), jnp.int32), jnp.asarray([0, n]),
            jnp.asarray([False, True]), cfg)
        np.testing.assert_array_equal(np.asarray(again[1]), got[0])
    for j in range(steps):
        step_at = np.zeros((3, 2), np.int32)
        step_at[:, 1] = positions[:, n + j]
        logits, cache = keye.decode_step(
            params, cache, jnp.asarray([0, row[n + j]]),
            jnp.asarray([0, n + j]), jnp.asarray([False, True]), cfg,
            positions=jnp.asarray(step_at))
        got.append(np.asarray(logits[1]))
    assert np.abs(np.stack(got) - want).max() <= FLOAT32_LOGIT_TOLERANCE
    if streams == "image":
        plain = reference_logits(cfg, row, list(range(n - 1, n + steps)))
        assert np.abs(plain - want).max() > 1e-3       # another function


# ------------------------------------------------------------------ the cache

LEAVES = ("k", "v", "ik")


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose MLPs take every valid lane of the step in
    one call (`lm.all_lanes`), against `decode_step`: whoever prefills, and
    when the lanes are more than a call's rows."""
    chunk_step_against_decode(keye, tiny(**F32), case,
                              FLOAT32_LOGIT_TOLERANCE, 1e-6)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_an_inactive_lanes_cache_is_bit_identical_after_a_step(program):
    """Slot 0 inactive, slot 2 a chunk of no valid lane: all three leaves
    come back to the bit, while slot 1 moves."""
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
    assert set(before) == set(LEAVES) | {"counts"} \
        and set(keye.CACHE_TOKEN_AXIS) == set(LEAVES)
    for name in LEAVES:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


def test_an_overrun_lane_past_the_length_writes_nothing():
    """A chunk of 16 lanes of which 5 are valid: the three leaves are what
    a chunk of those 5 tokens alone leaves, to the bit."""
    a, b = engine(), engine()
    B, C = a.max_batch, a.prefill_chunk_size
    lanes = np.arange(B) == 1
    for eng, filler in ((a, 0), (b, 9)):
        tokens = np.full((B, C), filler, np.int32)
        tokens[1, :5] = PROMPT[:5]
        _, eng.cache = eng._chunk_step(
            eng.params, eng.cache, tokens, np.zeros((B,), np.int32),
            np.where(lanes, 5, 0).astype(np.int32), lanes)
    for name in LEAVES:
        np.testing.assert_array_equal(np.asarray(a.cache[name]),
                                      np.asarray(b.cache[name]))
        assert not np.asarray(a.cache[name])[:, 1, 5:].any()


def test_the_pool_takes_the_third_leaf_as_a_geometry():
    """`PagedKVCache.for_cache` pools whatever `CACHE_TOKEN_AXIS` names: k
    and v share one pair of copy programs, the indexer's key has its own,
    and nothing in it is a new kind."""
    cfg = tiny()
    cache = keye.init_cache(cfg, 3, 96)
    pool = PagedKVCache.for_cache(cache, keye.CACHE_TOKEN_AXIS,
                                  num_blocks=10, block_size=8)
    assert {n: p.shape for n, p in pool.pools.items()} == {
        "k": (3, 10, 8, 32), "v": (3, 10, 8, 32), "ik": (3, 10, 8, 8)}
    assert pool._copiers["k"] is pool._copiers["v"]
    assert pool._copiers["ik"] is not pool._copiers["k"]
    assert not pool.snapshots and not pool.both and "counts" not in pool.pools


def test_the_programs_through_the_kernel_give_the_plain_paths_logits(
        monkeypatch):
    """Both programs with every slot's first lane through `ops/
    dsa_attend.py`'s kernel, interpreted, in blocks of 32 positions (the set
    a mask, the slot's rows read to its position) against the same programs
    through the gather of the chosen rows: the float32 logits at every
    generated position lie within the rounding of the accumulation's order,
    and `read_positions` says which path read what."""
    import functools
    import importlib

    op = importlib.import_module("ray_tpu.ops.dsa_attend")
    plain = engine()
    forced, want = through_the_programs(plain, PROMPT, N_DECODE)
    monkeypatch.setattr(slot_rows, "BLOCK", 32)
    for name, fn in (("rows_chosen", op.rows_chosen),
                     ("dsa_attend", op.dsa_attend),
                     ("dsa_read", op.read_positions)):
        monkeypatch.setattr(keye, name, functools.partial(
            fn, interpret=True))
    through = engine()
    _, got = through_the_programs(through, PROMPT, N_DECODE, forced=forced)
    np.testing.assert_allclose(got, want, atol=FLOAT32_LOGIT_TOLERANCE)
    assert np.abs(want).max() > 0.1

    def read(eng):
        return [dict(zip(keye.COUNTS, row))["read_positions"]
                for row in np.asarray(eng.cache["counts"]).tolist()]

    # 11 decode steps at positions 37-47, past the topk of 16; the chunks'
    # first lanes at 0, 16 and 32 and their further lanes' one run each
    assert read(plain) == [11 * 16, 1 + 16 + 16 + 3 * 96]
    assert read(through) == [11 * 64, 32 + 32 + 64 + 3 * 96]


def test_a_pool_hit_gives_the_logits_of_a_cold_prefill():
    """The prompt's whole blocks, all three leaves, into another slot, then
    the rest of the prompt: what a cold prefill of the whole prompt gives,
    with the rest (5 tokens at positions 32-36, past the topk) choosing
    among copied rows."""
    eng = engine()
    chosen, cold = through_the_programs(eng, PROMPT, 6, slot=0)
    through_the_programs(eng, PROMPT[:32], 1, slot=1)
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 4
    n_hit, blocks = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(blocks) == 4                 # 36 // 8 blocks
    # slot 2 held another sequence: its rows past the hit stay, stale
    through_the_programs(eng, PROMPT[::-1], 2, slot=2)
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, blocks)
    for name in LEAVES:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name][:, 2, :32]),
            np.asarray(eng.cache[name][:, 1, :32]))
        assert np.asarray(eng.cache[name][:, 2, :32]).any()
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


def test_a_layer_made_alone_is_the_layer_in_the_tree():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    params = keye.init_params(key, cfg)
    E = cfg.n_experts
    for l in range(cfg.n_layer):
        made = keye.init_layer(key, l, cfg)
        jax.tree.map(lambda s, a: np.testing.assert_array_equal(
            np.asarray(s[l].astype(jnp.float32)),
            np.asarray(a.astype(jnp.float32))), params["layers"],
            made["layer"])
        jax.tree.map(lambda s, a: np.testing.assert_array_equal(
            np.asarray(s[l * E:(l + 1) * E].astype(jnp.float32)),
            np.asarray(a.astype(jnp.float32))), params["experts"],
            made["experts"])
    assert params["experts"]["wg"].shape == (3 * 8, 64, 32)
    assert params["layers"]["w_index"].shape == (3, 64, 128)
    assert not np.asarray(params["layers"]["w_index"][:, :, 26:].astype(
        jnp.float32)).any()                       # 2 x 8 + 8 + 2, then zeros
    assert sum(a.size for a in jax.tree.leaves(params)) == keye.num_params(
        cfg)


def test_the_published_sizes_are_the_issues():
    cfg = keye.KeyeConfig.preset("keye-vl-2.0-30b-a3b")
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.queries_per_kv, cfg.kv_width) == (2048, 32, 4, 128, 8, 512)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.index_width) == (16, 64, 2048, 1152)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff_expert,
            cfg.router_scoring, cfg.norm_topk_prob) == (
        128, 8, 768, "softmax", True)
    assert (cfg.n_layer, cfg.vocab_size, cfg.rope_theta, cfg.mrope_section,
            cfg.norm_eps) == (48, 151936, 1e7, (16, 24, 24), 1e-6)
    six = keye.KeyeConfig.preset("keye-vl-2.0-30b-a3b", n_layer=6)
    # a layer 625.4 M (18.87 attention, 2.26 indexer and its padding, 0.26
    # router, 603.98 experts), the ends 622.3 M: 8.75 GB in bf16
    per_layer = (keye.num_params(six) - 2 * 151936 * 2048 - 2048) / 6
    assert round(per_layer / 1e6, 1) == 625.5
    assert round(2 * keye.num_params(six) / 1e9, 2) == 8.75
    cache = jax.eval_shape(lambda: keye.init_cache(six, 32, 13312))
    assert {n: cache[n].shape for n in LEAVES} == {
        "k": (6, 32, 13312, 512), "v": (6, 32, 13312, 512),
        "ik": (6, 32, 13312, 64)}
    # a token's rows: 6 x (2,048 + 128) bytes
    assert sum(cache[n].size * 2 for n in LEAVES) // (32 * 13312) == 13056


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    for preset in keye.PRESETS:
        assert serving_family(preset) == ("keye", keye, keye.KeyeConfig)
    for name in ("init_params", "resident_params", "resident_specs",
                 "init_cache", "decode_step", "prefill_chunk",
                 "CACHE_TOKEN_AXIS", "COUNTS"):
        assert hasattr(keye, name), name
    assert not hasattr(keye, "CACHE_STATE")
    assert keye.COUNTS[:4] == deepseek.COUNTS[:4]   # Kanana's readers read it
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        assert "keye" not in f.read()         # the engine knows the contract


@pytest.mark.parametrize("kwargs,what", [
    (dict(checkpoint="/nowhere"), "checkpoint="),
    (dict(tensor_parallel_size=2), "tensor_parallel_size")])
def test_what_is_gpt2s_refuses_the_family_by_name(kwargs, what):
    with pytest.raises(NotImplementedError, match="keye") as e:
        LLMEngine(preset="keye-tiny", **kwargs)
    assert what in str(e.value)


def test_the_family_refuses_what_cannot_carry_its_cache_by_name():
    eng = engine()
    with pytest.raises(NotImplementedError, match="keye"):
        eng.export_prefix(prompt_ids=PROMPT)
    with pytest.raises(NotImplementedError, match="keye"):
        eng.import_prefix({"ids": PROMPT})
    with pytest.raises(NotImplementedError, match="keye"):
        eng.prefix_model_key
    with pytest.raises(NotImplementedError, match="keye"):
        keye.resident_specs(eng.cfg)


def test_lora_and_the_cluster_prefix_store_refuse_the_family_by_name():
    server = OpenAIServer(model_id="keye", preset="keye-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          lora_root="/nowhere")
    try:
        with pytest.raises(NotImplementedError, match="keye") as e:
            server({"model": "keye:adapter", "prompt_ids": PROMPT})
        assert "LoRA" in str(e.value)
    finally:
        server.engine.shutdown()
    with pytest.raises(NotImplementedError, match="keye") as e:
        OpenAIServer(model_id="keye", preset="keye-tiny", max_batch=2,
                     max_seq_len=96, seed=SEED, cluster_prefix_cache=True)
    assert "cluster prefix store" in str(e.value)


def live_engine(**kwargs):
    kwargs.setdefault("kv_blocks", 24)
    return LLMEngine(preset="keye-tiny", max_batch=3, max_seq_len=96,
                     seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                     prefill_chunk_size=16, **kwargs)


def greedy_by_hand(prompt, n):
    return through_the_programs(engine(), prompt, n)[0]


def test_the_loop_serves_what_the_programs_give_and_counts_what_it_read():
    """37 tokens: chunks of 16, 16 and 5; then the same prompt again and one
    that shares its first 32 tokens, both from the pool. The programs' own
    counts: the positions the indexer scored and the rows the choice
    left."""
    want = greedy_by_hand(PROMPT, 8)
    other = PROMPT[:32] + [11, 12, 13]
    want_other = greedy_by_hand(other, 8)
    eng = live_engine()
    try:
        first = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        stats = eng.engine_stats()
        assert first["token_ids"] == want
        assert stats["chunk_steps"] == 3 and stats["tokens_prefilled"] == 37
        assert eng.kv.stats()["blocks_used"] == 4
        # each lane's pos + 1: 1..37 in the chunks, 38..44 in 7 decode steps
        assert stats["positions_indexed"] == sum(range(1, 45))
        assert stats["rows_selected"] == sum(range(1, 17)) + 28 * 16
        counts = stats["step_counts"]
        assert counts["decode"]["positions_indexed"] == sum(range(38, 45))
        assert counts["decode"]["rows_selected"] == 7 * 16
        assert counts["chunk"]["rows_selected"] == sum(range(1, 17)) + 21 * 16
        # off the chip a first lane's attention fetches its chosen rows (1,
        # 16 and 16 in the chunks), a slot's further lanes all 96 a run
        assert counts["decode"]["read_positions"] == 7 * 16
        assert counts["chunk"]["read_positions"] == 1 + 16 + 16 + 3 * 96
        assert counts["decode"]["expert_layer_steps"] == 7 * 3
        assert counts["decode"]["expert_rows"] == 7 * 3 * 3
        again = eng.generate(prompt_ids=PROMPT, max_tokens=8)
        shared = eng.generate(prompt_ids=other, max_tokens=8)
        stats = eng.engine_stats()
        assert again["token_ids"] == want
        assert shared["token_ids"] == want_other
        assert stats["tokens_prefilled"] == 37 + 5 + 3
        assert eng.kv.stats()["tokens_reused"] == 64
        assert stats["kv_bytes_per_token"] == 3 * (2 * 32 + 8) * 4
        assert "state_bytes_per_slot" not in stats
    finally:
        eng.shutdown()


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="keye", preset="keye-tiny",
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
        assert server.stats()["kv_cache"]["blocks_used"] == 4
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
    for text in (step, chunk):
        for scope in ["attn/gqa_project", "attn/dsa_index",
                      "attn/dsa_select", "attn/dsa_attend", "attn/kv_update",
                      "mlp/moe_router", "mlp/moe_dispatch",
                      "mlp/moe_experts", "unembed_loss", "embed", "layers"]:
            assert scope in text, scope
