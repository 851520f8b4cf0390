"""Granite 4.0-H through the serving path on the CPU at a tiny size: the two
forms of the Mamba-2 mixer (the SSD form in chunks, then the one-token
recurrence) beside grouped-head attention over cached rows, against the
plain reference's full forward pass; the kernel against the plain form; the
cache's contract in the engine (state zeroed at placement, rows, state and
window untouched where inactive, both pooled between two chunk steps, found
again); the pool of both kinds; and what the family refuses by name."""

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

from families import granite as family  # noqa: E402

from ray_tpu.models import granite, serving_family  # noqa: E402
from ray_tpu.ops import rows_write as rw  # noqa: E402
from ray_tpu.ops import ssm_update as su  # noqa: E402
from ray_tpu.serve.kv_cache import (PagedKVCache, chain_hashes,  # noqa: E402
                                    export_prefix, import_prefix)
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

# the tiny preset in the source's key names, for the reference
MODEL = {"vocab_size": 512, "num_hidden_layers": 6,
         "layer_types": ["mamba", "mamba", "attention"] * 2,
         "hidden_size": 64, "shared_intermediate_size": 128,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 32,
         "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
         "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
         "embedding_multiplier": 12, "residual_multiplier": 0.22,
         "attention_multiplier": 0.015625, "logits_scaling": 8,
         "rms_norm_eps": 1e-5, "hidden_act": "silu", "attention_bias": False,
         "position_embedding_type": "nope", "tie_word_embeddings": True,
         "normalization_function": "rmsnorm", "num_local_experts": 0}
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return granite.GraniteConfig.preset(
        "granite-tiny", **{**family.program_sizes(MODEL), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == granite.GraniteConfig.preset("granite-tiny")


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    kwargs.setdefault("max_seq_len", 96)
    eng = LLMEngine(preset="granite-tiny", max_batch=3,
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
    ref = family.Reference(MODEL, lambda l: granite.init_layer(key, l, cfg),
                           granite.init_ends(key, cfg), degrade)
    return ref.logits([row], [at])[0]


# Float32 compute against the float32 reference: the same sums in another
# order (the SSD form's state carried across chunks and the recurrence's
# across steps against one recurrence over the whole sequence; attention
# over cached rows against attention over the sequence), 9e-9 on logits of
# size 0.005 here (the tiny table is the head too). bf16 compute against it
# (the reference reads the same bf16 weights, and a product's activation
# goes as the two bf16 pieces that add up to it, so what is left is the
# rounding of the queries, the cached rows and attention's weights): 8.6e-7
# over chunk sizes; with the activations' rounding in every product it was
# 5.6e-5. A state held in bfloat16 moves the float32 logits by 2.3e-6, rows
# in float8 by 6.2e-6, the other scale by 1.0e-5, 250 to 1,100 times what
# the float32 program reads: the float32 tolerance tells them apart here,
# and on the chip the cell's own check (`families/granite.py`, PERF.md PR
# 38).
FLOAT32_LOGIT_TOLERANCE = 1e-7
BF16_LOGIT_TOLERANCE = 3e-6


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no cache, no chunks): the logits at
    every generated position, whatever the chunks' boundaries. 37 tokens in
    chunks of 16 and of 7 (which do not divide them), of 8 (which ends on a
    block) and of 64 (one chunk)."""
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
    L, B, N, F = 2, 3, 16, 256
    ks = jax.random.split(jax.random.key(2), 5)
    state = jax.random.normal(ks[0], (L, B, N, F))
    args = (jax.nn.sigmoid(jax.random.normal(ks[1], (B, F)) + 3.0),
            jax.random.normal(ks[2], (B, F)),
            jax.random.normal(ks[3], (B, N)),
            jax.random.normal(ks[4], (B, N)), jnp.array([1, 0, 1]))
    want = jax.jit(lambda s: su.ssm_update(
        s, jnp.int32(1), *args, kernel=False))(state)
    got = jax.jit(lambda s: su.ssm_update(
        s, jnp.int32(1), *args, interpret=True))(state)
    on = np.array([True, False, True])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1])[on], np.asarray(want[1])[on],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[0][0], state[0])        # other layer
    np.testing.assert_array_equal(got[0][1, 1], state[1, 1])  # inactive


def test_the_row_writing_kernel_is_the_plain_form():
    L, B, G, d, T = 2, 3, 2, 16, 256
    ks = jax.random.split(jax.random.key(3), 2)
    c = jax.random.normal(ks[0], (L, B, G, d, T)).astype(jnp.bfloat16)
    val = jax.random.normal(ks[1], (B, G, d)).astype(jnp.bfloat16)
    pos, on = jnp.array([0, 130, 255]), jnp.array([True, False, True])
    want = jax.jit(lambda c: rw.rows_write(c, jnp.int32(1), val, pos, on,
                                           kernel=False))(c)
    got = jax.jit(lambda c: rw.rows_write(c, jnp.int32(1), val, pos, on,
                                          interpret=True))(c)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_array_equal(got[0], c[0])               # other layer
    np.testing.assert_array_equal(got[1, 1], c[1, 1])         # not on
    np.testing.assert_array_equal(got[1, 2, :, :, 255], val[2])
    changed = np.asarray(got != c)
    assert changed.sum() <= 2 * G * d and changed[1, 0, :, :, 1:].sum() == 0


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
    assert set(before) == set(granite.CACHE_TOKEN_AXIS) | set(
        granite.CACHE_STATE)
    for name in before:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any()


# ------------------------------ a first lane's attention through the kernel

def _attend_through(monkeypatch, interpret: bool):
    """`granite.gqa_attend` as the chip runs it (the kernel, interpreted) or
    as the CPU does (`lm.gqa_attend`): what `slot_state.on_tpu` decides."""
    op = importlib.import_module("ray_tpu.ops.gqa_attend")
    how = dict(interpret=True) if interpret else dict(kernel=False)
    monkeypatch.setattr(granite, "gqa_attend",
                        functools.partial(op.gqa_attend, **how))


@pytest.fixture(scope="module")
def three_sequences():
    """An engine of three slots x 256 positions (two blocks of 128 a slot)
    whose slots hold sequences that have reached positions 152 (the second
    block), 126 (two short of a block's edge) and 39: (the engine, its
    cache as numpy, the positions)."""
    eng = engine(max_seq_len=256)
    rng = np.random.default_rng(63)
    pos = []
    for slot, n in enumerate((150, 124, 37)):
        through_the_programs(eng, rng.integers(1, 512, n).tolist(), 3,
                             slot=slot)
        pos.append(n + 2)
    return eng, jax.tree.map(np.asarray, eng.cache), np.array(pos, np.int32)


# (which slots are live, which slot's rows are NaN where it is dead)
LIVE = {"all-live": ([1, 1, 1], None),
        "a-dead-slot-between": ([1, 0, 1], 1),
        "a-dead-slot-first": ([0, 1, 1], 0),
        "a-dead-slot-last": ([1, 1, 0], 2)}


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_first_lanes_through_the_attention_kernel_are_the_plain_paths(
        monkeypatch, three_sequences, program, live):
    """`decode_step` and `prefill_chunk` with every slot's first lane
    through `ops/gqa_attend.py`'s kernel (interpreted, two blocks of 128):
    the live slots' logits are the plain path's to the float32 tolerance
    (the same sums, a block at a time), a slot that is not live keeps its
    rows, state and window bit for bit, and whatever its rows hold (NaN
    here) and whatever the kernel returns for it reaches no live lane: the
    live lanes' logits are the same bits as with the slot's rows sound. In
    the chunk program slot 0 is a decode lane riding along, slot 1 prefills
    5 lanes across the block's edge (its further lanes in the plain form)
    and slot 2 has a chunk of one lane."""
    eng, start, pos = three_sequences
    on, dead = np.array(LIVE[live][0], bool), LIVE[live][1]
    B, C = eng.max_batch, 8
    tokens = np.random.default_rng(7).integers(1, 512, (B, C)).astype(
        np.int32)

    def run(interpret, garbage=False):
        _attend_through(monkeypatch, interpret)
        cache = {name: jnp.asarray(a) for name, a in start.items()}
        if garbage:
            for name in granite.CACHE_TOKEN_AXIS:
                cache[name] = cache[name].at[:, dead].set(jnp.nan)
        if program == "decode":
            return jax.jit(lambda c: granite.decode_step(
                eng.params, c, tokens[:, 0], pos, on, eng.cfg))(cache)
        return jax.jit(lambda c: granite.prefill_chunk(
            eng.params, c, tokens, pos, np.array([1, 5, 1], np.int32), on,
            eng.cfg))(cache)

    (got, got_cache), (want, want_cache) = run(True), run(False)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got[on]).all() and np.abs(want[on]).max() > 1e-4
    assert np.abs(got - want)[on].max() <= FLOAT32_LOGIT_TOLERANCE
    for name, before in start.items():
        after = np.asarray(got_cache[name])
        np.testing.assert_array_equal(after[:, ~on], before[:, ~on])
        assert (after[:, on] != before[:, on]).any()
        np.testing.assert_allclose(after, np.asarray(want_cache[name]),
                                   rtol=0, atol=1e-6)
    if dead is not None:
        beside, _ = run(True, garbage=True)
        np.testing.assert_array_equal(np.asarray(beside)[on], got[on])


@pytest.mark.parametrize("T,on_the_chip,block", [
    (8192, True, 512), (8192, False, 8192), (1024, True, 128),
    (1024, False, 1024), (96, True, 96)])
def test_the_block_the_engine_counts_rows_read_by_follows_the_path(
        monkeypatch, T, on_the_chip, block):
    """`granite.rows_read_block` is `ops/gqa_attend.read_block` of the
    cache's leaves (which `tests/test_ops_gqa_attend.py` holds to the grid
    the op traces): `block_last` of the leaf's length where the kernel runs,
    all T where the plain form does."""
    from ray_tpu.ops import slot_state

    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    cache = jax.eval_shape(lambda: granite.init_cache(tiny(), 2, T))
    assert granite.rows_read_block(cache) == block


def test_the_engine_counts_the_positions_read_beside_the_attended():
    """Off the chip every lane of every step reads all T: `positions_read`
    is in `engine_stats()` for this family, T a lane."""
    eng = LLMEngine(preset="granite-tiny", max_batch=2, max_seq_len=64,
                    seed=SEED, prefill_chunk_size=16, kv_block_size=8)
    try:
        assert eng._rows_read_block == 64
        eng.generate(prompt_ids=PROMPT[:20], max_tokens=4, temperature=0.0)
        stats = eng.engine_stats()
        assert stats["positions_read"] == 64 * stats["engine_steps"]
        assert 0 < stats["positions_attended"] < stats["positions_read"]
    finally:
        eng.shutdown()


def test_a_layer_made_alone_is_the_layer_in_the_tree():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    tree = granite.init_params(key, cfg)
    for l, kind in enumerate(cfg.layer_types):
        at = cfg.layer_types[:l].count(kind)
        jax.tree.map(lambda whole, alone, at=at: np.testing.assert_array_equal(
            np.asarray(whole[at], np.float32), np.asarray(alone, np.float32)),
            tree[kind], granite.init_layer(key, l, cfg))
    ssm = tree["mamba"]["ssm"]
    a, dt = np.exp(ssm["a_log"]), np.log1p(np.exp(ssm["dt_bias"]))
    assert a.shape == (4, 4) and (a >= 1).all() and (a <= 16).all()
    assert (dt >= 0.000999).all() and (dt <= 0.1001).all()
    assert ssm["conv_w"].dtype == jnp.float32 == ssm["a_log"].dtype
    assert ssm["w_zx"].dtype == jnp.bfloat16 == tree["wte"].dtype
    assert ssm["w_dt"].dtype == jnp.float32
    assert "lm_head" not in tree                       # the table is tied
    n = sum(a.size for a in jax.tree.leaves(tree))
    assert n == granite.num_params(cfg)


def test_the_published_sizes_are_the_issues():
    cfg = granite.GraniteConfig.preset("granite-4.0-h-micro")
    assert cfg.layer_types.count("attention") == 4
    assert [l for l, t in enumerate(cfg.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert granite._period(cfg) == (4, [("mamba", 5), ("attention", 1),
                                        ("mamba", 4)])
    assert round(granite.num_params(cfg) / 1e6) == 3191
    assert (cfg.head_dim, cfg.ssm_inner, cfg.conv_width) == (64, 4096, 4352)
    cache = jax.eval_shape(lambda: granite.init_cache(cfg, 48, 8192))
    assert cache["k"].shape == cache["v"].shape == (4, 48, 8, 64, 8192)
    assert cache["ssm"].shape == (36, 48, 128, 4096)
    assert cache["conv"].shape == (36, 48, 3 * 4352)
    rows = sum(cache[n].size * 2 for n in ("k", "v"))
    state = sum(cache[n].size * 4 for n in ("ssm", "conv"))
    assert rows // (48 * 8192) == 8192
    assert state // 48 == 77_377_536


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
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 0    # is there
    n_hit, entry = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(entry) == 4                  # 36 // 8 blocks
    # slot 2 held another sequence: its rows past the hit stay, stale
    through_the_programs(eng, PROMPT[::-1], 2, slot=2)
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, entry)
    for name in granite.CACHE_STATE:
        np.testing.assert_array_equal(np.asarray(eng.cache[name][:, 2]),
                                      np.asarray(eng.cache[name][:, 1]))
    for name in granite.CACHE_TOKEN_AXIS:
        np.testing.assert_array_equal(
            np.asarray(eng.cache[name][:, 2, ..., :32]),
            np.asarray(eng.cache[name][:, 1, ..., :32]))
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


def pool(num_blocks, num_snapshots):
    cfg = tiny()
    cache = jax.tree.map(lambda a: a + 1, granite.init_cache(cfg, 2, 64))
    kv = PagedKVCache.for_cache(
        cache, granite.CACHE_TOKEN_AXIS, num_blocks=num_blocks, block_size=8,
        state=granite.CACHE_STATE, num_snapshots=num_snapshots)
    return cfg, cache, kv


def test_a_cache_of_both_kinds_gets_a_pool_of_both():
    cfg, cache, kv = pool(6, 2)
    assert kv.snapshots and kv.both
    assert {k: v.shape for k, v in kv.pools.items()} == {
        "k": (2, 6, 2, 16, 8), "v": (2, 6, 2, 16, 8),
        "ssm": (4, 2, 16, 128), "conv": (4, 2, 3 * 160)}
    # by default a snapshot for every whole slot of rows the blocks hold
    kv = PagedKVCache.for_cache(cache, granite.CACHE_TOKEN_AXIS,
                                num_blocks=24, block_size=8,
                                state=granite.CACHE_STATE)
    assert kv.num_snapshots == 24 * 8 // 64 == 3
    # the pure kinds are what they were
    rows = PagedKVCache.for_cache(cache, granite.CACHE_TOKEN_AXIS,
                                  num_blocks=4, block_size=8)
    assert not rows.snapshots and not rows.both
    states = PagedKVCache.for_cache(cache, {}, num_blocks=4, block_size=8,
                                    state=granite.CACHE_STATE)
    assert states.snapshots and not states.both
    assert states.pools["ssm"].shape == (4, 4, 16, 128)


def test_rows_without_a_snapshot_are_no_hit_and_are_counted():
    cfg, cache, kv = pool(8, 2)
    ids = list(range(100, 140))
    assert kv.store_prefix(ids[:7], cache, 0) == 0          # no whole block
    assert kv.store_prefix(ids[:16], cache, 0) == 1     # 2 blocks + snapshot
    assert kv.stats()["blocks_used"] == 2
    assert kv.stats()["snapshots_used"] == 1
    assert kv.peek_prefix_len(ids) == 16 and kv.hits == 0
    # the rows of a third block, pooled by hand with no snapshot at its end
    h3 = chain_hashes(ids, 8)[2][0]
    kv._table[h3] = kv._alloc()
    assert kv.peek_prefix_len(ids) == 16
    n, blocks = kv.match_prefix(ids)
    assert (n, len(blocks)) == (16, 2)
    assert kv.rows_without_snapshot_tokens == 8
    assert kv.stats()["rows_without_snapshot_tokens"] == 8
    # a prompt that ends inside the second block reaches no snapshot
    assert kv.match_prefix(ids[:15]) == (0, [])
    assert kv.rows_without_snapshot_tokens == 8 + 8
    assert kv.stats()["prefix_hits"] == 1
    # what the hit copies: the two blocks to the slot's first 16 positions
    # and the state over the whole slot
    out = kv.copy_into_slot(granite.init_cache(cfg, 2, 64), 1, blocks)
    assert float(out["k"][:, 1, ..., :16].min()) == 1.0
    assert not np.asarray(out["k"][:, 1, ..., 16:]).any()
    assert float(out["ssm"][:, 1].min()) == 1.0
    assert float(out["conv"][:, 1].min()) == 1.0
    assert not np.asarray(out["ssm"][:, 0]).any()


def test_a_block_under_a_snapshot_outlives_newer_loose_rows_and_lru_holds():
    cfg, cache, kv = pool(5, 2)
    a, b, c = (list(range(s, s + 24)) for s in (100, 200, 300))
    assert kv.store_prefix(a[:16], cache, 0) == 1           # blocks 1-2 of 5
    assert kv.store_prefix(b[:16], cache, 1) == 1           # blocks 3-4
    assert kv.match_prefix(a)[0] == 16                      # a: most recent
    # c needs two blocks; one is free, the other must come from b, the
    # least recently used entry: its snapshot goes first, then a block
    assert kv.store_prefix(c[:16], cache, 0) == 1
    stats = kv.stats()
    assert stats["snapshots_evicted"] == 1 and stats["blocks_evicted"] == 1
    assert stats["snapshots_used"] == 2
    assert kv.match_prefix(b)[0] == 0
    assert kv.match_prefix(a)[0] == 16 and kv.match_prefix(c)[0] == 16
    # b's other block is loose now (rows without a snapshot): it goes
    # before any block a snapshot stands on, however recently those matched
    assert chain_hashes(b, 8)[0][0] not in kv._table
    loose = kv._table[chain_hashes(b, 8)[1][0]]
    assert not kv._pins.get(loose)
    d = list(range(400, 408))
    assert kv.store_prefix(d, cache, 1) == 1
    assert chain_hashes(b, 8)[1][0] not in kv._table
    assert kv.stats()["snapshots_evicted"] == 2      # two snapshots, three
    assert kv.match_prefix(d)[0] == 8                # prefixes: a went (LRU)
    assert kv.match_prefix(a)[0] == 0 and kv.match_prefix(c)[0] == 16
    # a prefix longer than the pool's blocks is not pooled, and costs none
    before = kv.stats()
    assert kv.store_prefix(list(range(500, 548)), cache, 0) == 0
    assert kv.match_prefix(c)[0] == 16 and kv.match_prefix(d)[0] == 8
    assert kv.stats()["snapshots_used"] == before["snapshots_used"]


def test_the_transfers_refuse_the_pool_and_the_family_by_name():
    eng = engine()
    with pytest.raises(NotImplementedError, match="snapshots"):
        export_prefix(eng.kv, PROMPT)
    with pytest.raises(NotImplementedError, match="snapshots"):
        import_prefix(eng.kv, {"ids": PROMPT, "block_size": 8})
    with pytest.raises(NotImplementedError, match="granite"):
        eng.export_prefix(prompt_ids=PROMPT)
    with pytest.raises(NotImplementedError, match="granite"):
        eng.import_prefix({"ids": PROMPT})
    with pytest.raises(NotImplementedError, match="granite"):
        eng.prefix_model_key
    with pytest.raises(NotImplementedError, match="granite"):
        granite.resident_specs(eng.cfg)


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    for preset in granite.PRESETS:
        assert serving_family(preset) == ("granite", granite,
                                          granite.GraniteConfig)
    for name in ("init_params", "resident_params", "resident_specs",
                 "init_cache", "decode_step", "prefill_chunk",
                 "CACHE_TOKEN_AXIS", "CACHE_STATE"):
        assert hasattr(granite, name), name
    assert granite.CACHE_TOKEN_AXIS and granite.CACHE_STATE
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        assert "granite" not in f.read()      # the engine knows the contract


@pytest.mark.parametrize("kwargs,what", [
    (dict(checkpoint="/nowhere"), "checkpoint="),
    (dict(tensor_parallel_size=2), "tensor_parallel_size")])
def test_what_is_gpt2s_refuses_the_family_by_name(kwargs, what):
    with pytest.raises(NotImplementedError, match="granite") as e:
        LLMEngine(preset="granite-tiny", **kwargs)
    assert what in str(e.value)


def test_lora_and_the_cluster_prefix_store_refuse_the_family_by_name():
    server = OpenAIServer(model_id="granite", preset="granite-tiny",
                          max_batch=2, max_seq_len=96, seed=SEED,
                          lora_root="/nowhere")
    try:
        with pytest.raises(NotImplementedError, match="granite") as e:
            server({"model": "granite:adapter", "prompt_ids": PROMPT})
        assert "LoRA" in str(e.value)
    finally:
        server.engine.shutdown()
    with pytest.raises(NotImplementedError, match="granite") as e:
        OpenAIServer(model_id="granite", preset="granite-tiny", max_batch=2,
                     max_seq_len=96, seed=SEED, cluster_prefix_cache=True)
    assert "cluster prefix store" in str(e.value)


def live_engine(**kwargs):
    kwargs.setdefault("kv_blocks", 24)
    return LLMEngine(preset="granite-tiny", max_batch=3, max_seq_len=96,
                     seed=SEED, model_overrides=dict(F32), kv_block_size=8,
                     prefill_chunk_size=16, **kwargs)


def greedy_by_hand(prompt, n):
    return through_the_programs(engine(), prompt, n)[0]


def test_the_loop_serves_what_the_programs_give_and_pools_between_chunks():
    """37 tokens: chunks of 16, 16 (the boundary, 32: rows and state are
    pooled here, with 5 tokens still to go) and 5; then the same prompt
    again and one that shares its first 32 tokens, both from the pool."""
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
        assert eng.kv.stats()["blocks_used"] == 4      # no rows pooled again
        # both gauges, for the first time
        assert stats["kv_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
        assert stats["state_bytes_per_slot"] == 4 * (16 * 128 + 3 * 160) * 4
        assert stats["rows_without_snapshot_tokens"] == 0
        assert stats["positions_attended"] > 0
    finally:
        eng.shutdown()


def test_a_chunk_never_crosses_the_boundary_the_entry_is_due_at():
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


def test_a_reused_slot_gives_what_a_fresh_engine_gives():
    """One slot, no pool: the second request takes the slot the first left
    and reads none of its state; its stale rows lie past its position."""
    want = greedy_by_hand(PROMPT[::-1][:20], 8)
    eng = LLMEngine(preset="granite-tiny", max_batch=1, max_seq_len=96,
                    seed=SEED, model_overrides=dict(F32),
                    enable_prefix_caching=False, prefill_chunk_size=16)
    try:
        assert len(eng.generate(prompt_ids=PROMPT,
                                max_tokens=6)["token_ids"]) == 6
        got = eng.generate(prompt_ids=PROMPT[::-1][:20], max_tokens=8)
        assert got["token_ids"] == want
        assert eng.engine_stats()["slots_reset"] == 2
    finally:
        eng.shutdown()


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="granite", preset="granite-tiny",
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
    # and the SSD form on the further lanes of the slots that have them
    for text, mixers in ((step, ["ssm_update"]),
                         (chunk, ["ssm_update", "ssm_chunk"])):
        for scope in ["attn/ssm_project", "attn/ssm_conv", "attn/gqa_project",
                      "attn/gqa_attend", "attn/kv_update", "mlp",
                      "unembed_loss", "embed", "layers"] + [
                f"attn/{m}" for m in mixers]:
            assert scope in text, scope
    # by the scope's path: a helper traced once (`jnp.repeat`) keeps the
    # frames of its first caller in every program
    assert "attn/ssm_chunk" not in step
    reset = eng._reset_slot.lower(eng.cache, np.int32(0)).as_text(
        debug_info=True)
    assert "kv_update" in reset
