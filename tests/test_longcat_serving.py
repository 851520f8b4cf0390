"""LongCat-Flash through the serving path on the CPU at a tiny size: a
double layer whose expert block is read at one sublayer and added at the
next one's end, latent attention with a query latent and two factors in its
absorbed form, zero-compute experts beside routed ones of which the replica
may hold a share, against the plain reference's full forward pass; the
shares tied to the model; the three kinds of pair counted apart; the pool's
rows of both sublayers into another slot; and the preset through the OpenAI
server."""

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

from families import longcat as family  # noqa: E402

from ray_tpu.cluster_utils import (LANES_OF_A_STEP,  # noqa: E402
                                   chunk_step_against_decode)
from ray_tpu.models import (deepseek, kimi, longcat, mla, moe,  # noqa: E402
                            serving_family)
from ray_tpu.serve.llm import LLMEngine, OpenAIServer  # noqa: E402

pieces_module = importlib.import_module("ray_tpu.ops.pieces")

# the tiny preset in the source's key names, for the reference: 2 double
# layers, 4 heads of 16 + 8 and 16 lanes, latents of 24 and 32, 8 routed
# experts (all held) beside 4 zero-compute ones, 3 a token
MODEL = {"attention_bias": False, "attention_method": "MLA",
         "vocab_size": 512, "num_layers": 2, "hidden_size": 64,
         "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
         "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
         "routed_scaling_factor": 6, "n_routed_experts": 8,
         "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
         "rms_norm_eps": 1e-5, "rope_theta": 10000000}
CONFIG = {"model": MODEL, "published": {"n_routed_experts": 8},
          "assumed": {"hidden_act": "silu"},
          "share": {"router_outputs": 12, "zero_compute_outputs": 4,
                    "first_expert": 0}}
REFERENCE_MODEL = family.reference_model(CONFIG)
F32 = {"dtype": jnp.float32, "param_dtype": jnp.float32}
BF16 = {"dtype": jnp.bfloat16, "param_dtype": jnp.bfloat16}
SEED = 5
PROMPT = np.random.default_rng(0).integers(1, 512, 37).tolist()
N_DECODE = 12


def tiny(**extra):
    return longcat.LongcatConfig.preset(
        "longcat-tiny", **{**family.program_sizes(CONFIG), **extra})


def test_the_tiny_preset_is_the_model_the_reference_is_given():
    assert tiny() == longcat.LongcatConfig.preset("longcat-tiny")
    assert (tiny().router_outputs, tiny().zero_experts) == (12, 4)


def test_the_published_sizes_are_the_issues():
    cfg = longcat.LongcatConfig.preset("longcat-flash-chat")
    assert (cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.d_ff_expert) == (
        28, 6144, 12288, 2048)
    assert (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        64, 128, 64, 128, 1536, 512)
    assert (cfg.n_experts, cfg.zero_experts, cfg.router_outputs,
            cfg.experts_per_token, cfg.routed_scaling_factor) == (
        512, 256, 768, 12, 6.0)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.router_scoring,
            cfg.norm_topk_prob) == (1e7, 1e-5, "softmax", False)
    s_q, s_kv = mla.latent_scales(cfg)
    assert (s_q, round(s_kv, 4)) == (2.0, 3.4641)
    # the whole model: 560.7 B, which is its name, and what a token reads
    # under a balanced router (8 of its 12 pairs real)
    whole = longcat.num_params(cfg)
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 8192 * 6144 + 6144 + 1536 + 512)
    dense = 3 * 6144 * 12288 + 6144
    expert = 3 * 6144 * 2048
    layer = 2 * attn + 2 * dense + 6144 * 768 + 768
    assert whole == (28 * (layer + 512 * expert) + 2 * 131072 * 6144
                     + 6144)
    assert round(whole / 1e9, 1) == 560.7
    # (a token reads one row of the table and the head whole)
    assert round((whole - 28 * (512 - 8) * expert - 131072 * 6144) / 1e9,
                 1) == 27.1
    one_chip = dataclasses.replace(cfg, n_layer=4, experts_held=16,
                                   vocab_size=16384)
    assert round(longcat.num_params(one_chip) / 1e6, 1) == 5172.7
    cache = jax.eval_shape(lambda: longcat.init_cache(one_chip, 128, 3072))
    assert set(cache) == {"latent", "k_rope", "counts"}
    assert cache["latent"].shape == (8, 128, 3072, 512)
    assert cache["k_rope"].shape == (8, 128, 3072, 64)
    rows = sum(cache[n].size * 2 for n in longcat.CACHE_TOKEN_AXIS)
    assert rows // (128 * 3072) == 9216
    assert not hasattr(longcat, "CACHE_STATE")


def test_a_zero_expert_type_the_layer_does_not_know_is_refused_by_name():
    with pytest.raises(ValueError, match="'copy'"):
        tiny(zero_expert_type="copy")
    x = jnp.zeros((1, 2, 8), jnp.float32)
    w = jnp.zeros((2, 8, 8), jnp.float32)
    cfg = dataclasses.replace(moe.MoEConfig(), n_experts=3,
                              experts_per_token=1, dtype=jnp.float32)
    with pytest.raises(ValueError, match="'constant'"):
        moe._experts(x, jnp.ones((1, 2, 1)), jnp.zeros((1, 2, 1), jnp.int32),
                     w, w, w, cfg, zero_experts=1, zero_type="constant")


def engine(compute=F32, chunk=16, **kwargs):
    kwargs.setdefault("kv_blocks", 24)
    eng = LLMEngine(preset="longcat-tiny", max_batch=3, max_seq_len=96,
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
    E = cfg.experts_held
    return {"attn": jax.tree.map(lambda a: a[2 * l:2 * l + 2],
                                 params["attn"]),
            "dense": jax.tree.map(lambda a: a[2 * l:2 * l + 2],
                                  params["dense"]),
            "moe": jax.tree.map(lambda a: a[l:l + 1], params["moe"]),
            "experts": jax.tree.map(lambda a: a[l * E:(l + 1) * E],
                                    params["experts"])}


def reference_logits(cfg, row, at, degrade=None, model=REFERENCE_MODEL,
                     params=None):
    key = jax.random.key(SEED)
    if params is None:
        layers, ends = (lambda l: longcat.init_layer(key, l, cfg),
                        longcat.init_ends(key, cfg))
    else:
        layers, ends = (lambda l: layer_of(params, l, cfg), params)
    return family.Reference(model, layers, ends, degrade).logits(
        [row], [at])[0]


def test_a_layer_of_the_tree_is_the_layer_made_alone():
    cfg = tiny(**BF16)
    key = jax.random.key(SEED)
    params = longcat.init_params(key, cfg)
    assert params["attn"]["wqa"].shape == (4, 64, 24)
    assert params["moe"]["router"].shape == (2, 64, 12)
    assert params["moe"]["router"].dtype == jnp.float32
    assert params["experts"]["wg"].shape == (16, 64, 32)
    for l in range(cfg.n_layer):
        alone, held = longcat.init_layer(key, l, cfg), layer_of(params, l,
                                                                cfg)
        for a, b in zip(jax.tree.leaves(alone), jax.tree.leaves(held)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == longcat.num_params(cfg)


# Float32 compute against the float32 reference: the same sums in another
# order (the absorbed form's two products against the plain form's keys and
# values by head, attention through the cache against one pass, the experts'
# rows sorted and summed by gate against a loop over the experts): 1.5e-7 on
# logits of spread 0.16 here, whatever the chunks. bf16 compute against it
# (the reference reads the same bf16 weights, holds its rows through
# bfloat16 as the program's cache does, and a product's activation goes as
# the two bf16 pieces that add up to it, so what is left is the rounding of
# the queries that meet the rows and of attention's probabilities): 1.2e-5
# over chunk sizes. Against the float32 program a stream through bfloat16
# reads 2.4e-3, the zero term dropped 8.1e-3, the key-value latent without
# its factor 2.4e-3, rows through float8 2.1e-4, r a sublayer early 2.5e-5
# and the reference's own products of one piece 2.0e-5: 130 to 50,000 times
# what the program reads; in bf16 compute a program whose every product is
# one piece reads 1e-3, sixteen times the tolerance.
FLOAT32_LOGIT_TOLERANCE = 6e-7
BF16_LOGIT_TOLERANCE = 6e-5
REFUSED_ON_THE_CPU = ("bfloat16_stream", "no_zero_term", "unscaled_latent",
                      "float8_rows", "one_piece", "r_a_sublayer_early")


@pytest.mark.parametrize("chunk", [16, 8, 7, 64],
                         ids=lambda c: f"chunks-of-{c}")
@pytest.mark.parametrize("compute,tolerance", [
    (F32, FLOAT32_LOGIT_TOLERANCE), (BF16, BF16_LOGIT_TOLERANCE)],
    ids=["float32", "bfloat16"])
def test_prefill_in_chunks_then_decode_gives_the_references_logits(
        compute, tolerance, chunk):
    """Through `LLMEngine`'s own compiled programs, against the plain
    reference's full forward pass (no cache, no chunks, MLA in its plain
    form): the logits at every generated position, whatever the chunks'
    boundaries. 37 tokens in chunks of 16 and of 7 (which do not divide
    them), of 8 (which ends on a block) and of 64 (one chunk)."""
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
    the rounding left: every `lm.dot`, the products by head and the experts'
    rows as one bf16 piece. The bf16 tolerance, which the two pieces meet,
    refuses it."""
    whole = pieces_module.pieces

    def rounding_alone(x, dtype, n=2, axis=0):
        both = whole(x, dtype, n, axis)
        keep = jnp.arange(n).reshape((n,) + (1,) * (both.ndim - axis - 1))
        return jnp.where(keep == 0, both, jnp.zeros_like(both))

    for module in (pieces_module, moe, mla):
        monkeypatch.setattr(module, "pieces", rounding_alone)
    eng = engine(BF16)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    want = reference_logits(
        eng.cfg, row, list(range(len(PROMPT) - 1, len(row))),
        model={**REFERENCE_MODEL, "rows": "bfloat16"})
    assert np.abs(got - want).max() > 3 * BF16_LOGIT_TOLERANCE


# ------------------------------------------------- where the shortcut lands

def with_norms_of_their_own(params, seed=11):
    """The tree with every norm's scale drawn U(0.5, 2) a lane: with scales
    of one, r (mostly the zero term, a multiple of the normed stream) is
    nearly parallel to the stream at this size, and the next norm takes a
    parallel part out again."""
    leaves, tree = jax.tree.flatten_with_path(params)
    rng = np.random.default_rng(seed)
    out = [jnp.asarray(rng.uniform(0.5, 2.0, a.shape), a.dtype)
           if any(getattr(k, "key", None) == "scale" for k in path) else a
           for path, a in leaves]
    return jax.tree.unflatten(tree, out)


def test_r_lands_at_the_layers_end_and_not_a_sublayer_early():
    """The program's logits are the reference's, in which r is added where
    the layer ends; a reference that adds it with the first dense FFN's
    result, before the second attention sublayer reads the stream, is
    another function by four hundred tolerances (2.2e-3 here), and so is a
    program that does."""
    eng = engine()
    eng.params = with_norms_of_their_own(eng.params)
    chosen, got = through_the_programs(eng, PROMPT, N_DECODE)
    row = PROMPT + chosen[:-1]
    at = list(range(len(PROMPT) - 1, len(row)))
    want = reference_logits(eng.cfg, row, at, params=eng.params)
    early = reference_logits(eng.cfg, row, at, "r_a_sublayer_early",
                             params=eng.params)
    assert np.abs(got - want).max() <= 2 * FLOAT32_LOGIT_TOLERANCE
    assert np.abs(got - early).max() > 400 * FLOAT32_LOGIT_TOLERANCE


def test_a_program_that_adds_r_a_sublayer_early_fails_the_reference(
        monkeypatch):
    """The same comparison with the program moved: `_dense_ffn` asked to add
    what `_expert_block` last gave to the first dense FFN's result and
    nothing at the end."""
    held = {}
    block, ffn = longcat._expert_block, longcat._dense_ffn

    def early_block(h, *args, **kwargs):
        r, given = block(h, *args, **kwargs)
        held["r"] = r
        return jnp.zeros_like(r), given

    def early_ffn(h, p, cfg):
        out = ffn(h, p, cfg)
        return out + held.pop("r") if "r" in held else out

    monkeypatch.setattr(longcat, "_expert_block", early_block)
    monkeypatch.setattr(longcat, "_dense_ffn", early_ffn)
    cfg = tiny(**F32)
    params = with_norms_of_their_own(
        longcat.init_params(jax.random.key(SEED), cfg))
    cache = longcat.init_cache(cfg, 1, 64)
    tokens = jnp.asarray([PROMPT[:32]], jnp.int32)
    got, _ = longcat.prefill_chunk(
        params, cache, tokens, jnp.zeros((1,), jnp.int32),
        jnp.asarray([32]), jnp.asarray([True]), cfg)
    want = reference_logits(cfg, PROMPT[:32], [31], params=params)
    early = reference_logits(cfg, PROMPT[:32], [31], "r_a_sublayer_early",
                             params=params)
    assert np.abs(np.asarray(got) - early).max() <= 4 * FLOAT32_LOGIT_TOLERANCE
    assert np.abs(np.asarray(got) - want).max() > 400 * FLOAT32_LOGIT_TOLERANCE


# ------------------------------------------------------- latent attention

@pytest.mark.parametrize("compute", [F32, BF16], ids=["float32", "bfloat16"])
def test_absorbed_attention_with_a_query_latent_and_both_factors_is_plain(
        compute):
    """`mla.attention` in its whole form (the query latent, its norm and
    factor, the key-value latent's factor folded into what the cache holds,
    the key's up-projection folded into the query) against the reference's
    plain form, keys and values by head and no cache, on one layer's
    weights: all 24 lanes at once, and a lane at a time through the cache."""
    cfg = tiny(**compute)
    layer = longcat.init_layer(jax.random.key(3), 0, cfg)
    p = jax.tree.map(lambda a: a[1], layer["attn"])
    x = jax.random.normal(jax.random.key(4), (2, 24, 64), jnp.float32)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    u = family._rms_norm(x, f32["norm"]["scale"], 1e-5)
    model = {**REFERENCE_MODEL,
             "rows": "float32" if compute is F32 else "bfloat16"}
    with jax.default_matmul_precision("highest"):
        want = x + jnp.stack([family._mla_row(row, f32, model, None)
                              for row in u])
    cache = longcat.init_cache(cfg, 2, 32)
    pos0 = jnp.zeros((2,), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    ok = jnp.ones((2, 24), bool)
    scales = mla.latent_scales(cfg)
    got, lat, kr = mla.attention(x, p["norm"], p, cfg, cache["latent"],
                                 cache["k_rope"], 3, pos0, pos, ok,
                                 scales=scales, whole=True)
    tolerance = 2e-6 if compute is F32 else 3e-3
    np.testing.assert_allclose(got, want, atol=tolerance)
    assert np.abs(np.asarray(want - x)).max() > 0.02
    # the rows went to entry 3 and nowhere else, the factor in them
    assert not np.asarray(lat[:3], np.float32).any()
    c = np.asarray(lat[3, :, :24], np.float32)
    assert abs(np.sqrt((c * c).mean()) - scales[1]) < 0.05 * scales[1]
    # a lane at a time through the cache, every slot's one lane
    lat, kr = cache["latent"], cache["k_rope"]
    for t in range(24):
        step, lat, kr = mla.attention(
            x[:, t:t + 1], p["norm"], p, cfg, lat, kr, 3,
            jnp.full((2,), t), jnp.full((2, 1), t), ok[:, :1], scales=scales,
            whole=True)
        np.testing.assert_allclose(step[:, 0], want[:, t], atol=tolerance)
    # without the factors it is another function
    bare, _, _ = mla.attention(x, p["norm"], p, cfg, cache["latent"],
                               cache["k_rope"], 3, pos0, pos, ok, whole=True)
    assert np.abs(np.asarray(bare - want)).max() > 100 * tolerance \
        or compute is BF16


def test_kananas_layer_is_the_shared_layer_with_no_latent_and_no_factor():
    """`deepseek._attention` after the move: `mla.attention` in its rounded
    form by the weights a Kanana layer holds, to the bit the arithmetic the
    family had (one piece a product, the norm's output rounded, the blended
    window's write), and its lowered program holds none of the whole form's
    operations (no piece of an activation, no scatter of rows)."""
    cfg = deepseek.DeepseekConfig.preset("deepseek-tiny")
    bp = jax.tree.map(lambda a: a[0], deepseek.init_params(
        jax.random.key(0), cfg)["blocks"])
    assert set(bp["attn"]) == {"wq", "wkva", "kv_norm", "wkvb", "wo"}
    cache = deepseek.init_cache(cfg, 3, 32)
    x = jax.random.normal(jax.random.key(1), (3, 1, 64), jnp.float32)
    pos0 = jnp.asarray([2, 5, 9], jnp.int32)
    ok = jnp.ones((3, 1), bool)

    def before_the_move(x, bp, lat, kr, l):
        from ray_tpu.models import lm
        from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs
        from ray_tpu.ops.mla_attend import mla_attend
        import math

        p, n, r = bp["attn"], cfg.qk_nope_head_dim, cfg.kv_lora_rank
        h = rms_norm(x, bp["attn_norm"], cfg.norm_eps).astype(cfg.dtype)
        q = jnp.einsum("bcd,dhk->bchk", h, lm.weight(p["wq"], cfg.dtype))
        ckr = h @ lm.weight(p["wkva"], cfg.dtype)
        c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
        cos, sin = rope_freqs(pos0[:, None], cfg.qk_rope_head_dim,
                              cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q_rope = apply_rope(q[..., n:], cos, sin)
        k_r = apply_rope(ckr[..., r:][:, :, None], cos, sin)[:, :, 0]
        wkvb = lm.weight(p["wkvb"], cfg.dtype)
        q_abs = jnp.einsum("bchn,rhn->bchr", q[..., :n], wkvb[..., :n])
        lat = mla.cache_write(lat, l, c, pos0, ok)
        kr = mla.cache_write(kr, l, k_r, pos0, ok)
        mixed = mla_attend(q_abs[:, 0], q_rope[:, 0], lat, kr, l, pos0,
                           ok[:, 0], 1.0 / math.sqrt(cfg.qk_head_dim))
        mixed = jnp.moveaxis(mixed[:, :, None], 1, 2).astype(cfg.dtype)
        o = jnp.einsum("bchr,rhv->bchv", mixed, wkvb[..., n:])
        return x + jnp.dot(o.reshape(3, 1, -1), lm.weight(p["wo"], cfg.dtype),
                           preferred_element_type=x.dtype), lat, kr

    got = deepseek._attention(x, bp, cfg, cache["latent"], cache["k_rope"],
                              1, pos0, pos0[:, None], ok)
    want = before_the_move(x, bp, cache["latent"], cache["k_rope"], 1)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    text = jax.jit(lambda x, lat, kr: deepseek._attention(
        x, bp, cfg, lat, kr, 1, pos0, pos0[:, None], ok)).lower(
            x, cache["latent"], cache["k_rope"]).as_text()
    assert "reduce_precision" not in text and "scatter" not in text
    whole = jax.jit(lambda x, lat, kr: mla.attention(
        x, bp["attn_norm"], bp["attn"], cfg, lat, kr, 1, pos0,
        pos0[:, None], ok, whole=True)).lower(
            x, cache["latent"], cache["k_rope"]).as_text()
    assert "reduce_precision" in whole and "scatter" in whole


# -------------------------------------------------------------------- pool

def test_a_pool_hit_gives_back_both_sublayers_rows_of_every_layer():
    """The row blocks of `latent` and `k_rope`, 4 entries (2 layers x 2
    sublayers), into another slot, then the rest of the prompt: what a cold
    prefill of the whole prompt gives."""
    eng = engine()
    assert eng.family == "longcat" and not eng.kv.both
    chosen, cold = through_the_programs(eng, PROMPT, 6, slot=0)
    through_the_programs(eng, PROMPT[:32], 1, slot=1)
    assert eng.kv.store_prefix(PROMPT[:32], eng.cache, 1) == 4
    n_hit, blocks = eng.kv.match_prefix(PROMPT[:-1])
    assert n_hit == 32 and len(blocks) == 4                 # 36 // 8 blocks
    # slot 2 held another sequence: its rows past the hit stay, stale
    through_the_programs(eng, PROMPT[::-1], 2, slot=2)
    eng.cache = eng.kv.copy_into_slot(eng.cache, 2, blocks)
    for name in longcat.CACHE_TOKEN_AXIS:
        leaf = np.asarray(eng.cache[name], np.float32)
        assert leaf.shape[0] == 4
        np.testing.assert_array_equal(leaf[:, 2, :32], leaf[:, 1, :32])
        for entry in range(4):                  # every sublayer wrote rows
            assert leaf[entry, 2, :32].any()
    _, by_hit = through_the_programs(eng, PROMPT, 6, slot=2, start=n_hit,
                                     forced=chosen)
    np.testing.assert_allclose(by_hit, cold, atol=FLOAT32_LOGIT_TOLERANCE)


@pytest.mark.parametrize("case", LANES_OF_A_STEP)
def test_a_chunk_step_is_its_tokens_a_token_at_a_time(case):
    """The chunk program, whose routers, experts and dense FFNs take every
    valid lane of the step in one call (`lm.pack_lanes`), against
    `decode_step`: whoever prefills, and when the lanes are more than a
    call's rows (r then lives a round)."""
    chunk_step_against_decode(longcat, tiny(**F32), case,
                              FLOAT32_LOGIT_TOLERANCE, 1e-6)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_an_inactive_lanes_cache_is_bit_identical_after_a_step(program):
    """Slot 0 inactive, slot 2 a chunk of no valid lane: their rows come
    back to the bit, while slot 1 moves."""
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
    assert set(before) == set(longcat.CACHE_TOKEN_AXIS) | {"counts"}
    for name in longcat.CACHE_TOKEN_AXIS:
        after = np.asarray(eng.cache[name])
        np.testing.assert_array_equal(after[:, 0], before[name][:, 0])
        np.testing.assert_array_equal(after[:, 2], before[name][:, 2])
        assert (after[:, 1] != before[name][:, 1]).any(axis=(1, 2)).all()


def test_both_programs_count_the_positions_and_the_three_kinds_of_pair():
    """`read_positions` beside `attended_positions`, once a step whatever
    the sublayers; and the pairs, 3 a lane a layer, each held, zero-compute
    or absent (none absent here: all 8 routed experts are held)."""
    eng = engine()
    through_the_programs(eng, PROMPT, 3)
    decode, chunk = (dict(zip(longcat.COUNTS, row)) for row in np.asarray(
        eng.cache["counts"]).tolist())
    assert chunk["attended_positions"] == sum(range(1, 38))
    assert decode["attended_positions"] == 38 + 39
    assert chunk["read_positions"] == 3 * 96 + 3 * 96
    assert decode["read_positions"] == 2 * 96
    assert decode["expert_rows_all"] == 2 * 2 * 3       # steps, layers, K
    assert decode["expert_layer_steps"] == 2 * 2
    assert chunk["expert_rows_all"] == 37 * 2 * 3
    for counts in (decode, chunk):
        assert counts["expert_rows"] + counts["zero_rows"] \
            == counts["expert_rows_all"]
        assert 0 < counts["zero_rows"] < counts["expert_rows_all"]


# ------------------------------------------------------------- the share

def expert_block(cfg, key, h, first, held, moe_weights=None):
    """Layer 1's expert block (router over all 12 outputs, the routed
    experts first..first + held held) on the normed h, and what it
    counted."""
    share = dataclasses.replace(cfg, first_expert=first, experts_held=held)
    layer = longcat.init_layer(key, 1, share)
    p = jax.tree.map(lambda a: a[0], moe_weights or layer["moe"])
    given = jnp.zeros((cfg.router_outputs,), jnp.int32)
    r, given = longcat._expert_block(
        h, p, layer["experts"], 0, share, given, jnp.ones(h.shape[:2], bool))
    return r, dict(zip(longcat.COUNTS, np.asarray(
        longcat._expert_counts(given, share)).tolist())), layer


@pytest.mark.parametrize("compute", [F32, BF16], ids=["float32", "bfloat16"])
def test_the_four_shares_of_an_expert_block_add_up_to_the_uncut_block(
        compute):
    """The share tied to the model (the cell's is 32 shares of 16 experts;
    the tiny preset's 4 of 2): the routed parts that the four shares give
    (`first_expert` 0, 2, 4, 6 of 8 routed experts, two held each), with
    what every chip computes alike, the zero-compute experts' term, counted
    once, add up to what the uncut reference gives for the whole block."""
    cfg = tiny(**compute)
    key = jax.random.key(SEED)
    h = jax.random.normal(jax.random.key(1), (2, 6, 64), jnp.float32)
    whole, counts, layer = expert_block(cfg, key, h, 0, 8)
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), layer)
    with jax.default_matmul_precision("highest"):
        want, chosen = family._expert_block(
            h.reshape(12, 64), f32["moe"], f32["experts"], REFERENCE_MODEL)
        routed_alone, _ = family._expert_block(
            h.reshape(12, 64), f32["moe"], f32["experts"], REFERENCE_MODEL,
            "no_zero_term")
    want, chosen = want.reshape(2, 6, 64), np.asarray(chosen)
    zero_term = want - routed_alone.reshape(2, 6, 64)
    assert np.abs(np.asarray(zero_term)).max() > 0.1
    tolerance = 2e-6 if compute is F32 else 1e-4
    np.testing.assert_allclose(whole, want, atol=tolerance)
    assert counts["expert_rows_all"] == 2 * 6 * 3
    assert counts["zero_rows"] == (chosen >= 8).sum() > 0
    assert counts["expert_rows"] == (chosen < 8).sum()
    parts, held_rows = [], []
    for first in (0, 2, 4, 6):
        part, counts, mine = expert_block(cfg, key, h, first, 2)
        # a share holds the very experts the whole layer has there
        np.testing.assert_array_equal(
            np.asarray(mine["experts"]["wu"], np.float32),
            np.asarray(layer["experts"]["wu"][first:first + 2], np.float32))
        parts.append(part - zero_term)
        assert counts["expert_rows_all"] == 36
        assert counts["zero_rows"] == (chosen >= 8).sum()
        held_rows.append(counts["expert_rows"])
        assert counts["expert_rows"] == (
            (chosen >= first) & (chosen < first + 2)).sum()
    assert sum(held_rows) == (chosen < 8).sum()
    np.testing.assert_allclose(sum(parts) + zero_term, want,
                               atol=4 * tolerance)


def test_a_token_of_zero_pairs_alone_a_token_with_none_and_absent_pairs():
    """A router made by hand: token 0's three largest scores are
    zero-compute outputs (9, 10, 11), token 1's routed experts of which one
    is held here (experts 2 and 3 of 0..7) and two are absent, token 2's one
    of each kind. The three kinds are counted apart, token 0's r is its
    gates' sum times h and nothing else, and token 1's has no zero term."""
    cfg = tiny(**F32)
    key = jax.random.key(SEED)
    h = jnp.eye(3, 64, dtype=jnp.float32)[None] * 4.0           # [1, 3, 64]
    router = np.zeros((1, 64, 12), np.float32)
    router[0, 0, [9, 10, 11]] = [3.0, 2.5, 2.0]
    router[0, 1, [2, 5, 7]] = [3.0, 2.5, 2.0]
    router[0, 2, [3, 6, 8]] = [3.0, 2.5, 2.0]
    weights = {"router": jnp.asarray(router),
               "bias": jnp.zeros((1, 12), jnp.float32)}
    r, counts, layer = expert_block(cfg, key, h, 2, 2, weights)
    assert (counts["expert_rows_all"], counts["expert_rows"],
            counts["zero_rows"]) == (9, 2, 4)           # and 3 absent
    assert counts["experts_touched"] == 2
    scores = np.asarray(jax.nn.softmax(h[0] @ router[0], axis=-1))
    np.testing.assert_allclose(
        r[0, 0], 6.0 * scores[0, [9, 10, 11]].sum() * h[0, 0], rtol=1e-6)

    def swiglu(x, e):
        w = jax.tree.map(lambda a: np.asarray(a[e], np.float64),
                         layer["experts"])
        x = np.asarray(x, np.float64)
        a = x @ w["wg"]
        return (a / (1 + np.exp(-a)) * (x @ w["wu"])) @ w["wd"]

    # the share holds experts 2 and 3 as entries 0 and 1
    np.testing.assert_allclose(
        r[0, 1], 6.0 * scores[1, 2] * swiglu(h[0, 1], 0), atol=1e-6)
    np.testing.assert_allclose(
        r[0, 2], 6.0 * scores[2, 3] * swiglu(h[0, 2], 1)
        + 6.0 * scores[2, 8] * np.asarray(h[0, 2]), atol=1e-6)


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
    assert counts["moe_expert_rows_all"] == 2 * 3 * (37 + 5)


# ------------------------------------------------------------------ engine

def test_the_presets_name_picks_the_module():
    name, module, config = serving_family("longcat-flash-chat")
    assert (name, module, config) == ("longcat", longcat,
                                      longcat.LongcatConfig)
    assert longcat.CACHE_TOKEN_AXIS == {"latent": 2, "k_rope": 2}
    assert longcat.COUNTS == kimi.COUNTS + ("zero_rows",)


def test_the_loop_serves_what_the_programs_give_with_prefix_caching_on():
    """Through `generate`: greedy tokens of the running loop are the
    programs' own by hand, and a second request over the same prefix is a
    pool hit with the same reply."""
    eng = LLMEngine(preset="longcat-tiny", max_batch=3, max_seq_len=96,
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
    assert eng.kv.stats()["tokens_reused"] == 32
    # 2 layers x 2 sublayers x (32 + 8) values of 4 bytes
    assert stats["kv_bytes_per_token"] == 4 * 40 * 4
    assert "state_bytes_per_slot" not in stats
    assert stats["step_counts"]["chunk"]["zero_rows"] > 0


def test_one_streamed_completion_through_the_openai_server():
    server = OpenAIServer(model_id="longcat", preset="longcat-tiny",
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
        assert server.stats()["kv_cache"]["blocks_used"] == 4
    finally:
        server.engine.shutdown()


def test_the_scopes_the_readers_sum_by_are_in_both_programs():
    cfg = tiny()
    params = jax.eval_shape(lambda: longcat.init_params(
        jax.random.key(0), cfg))
    cache = jax.eval_shape(lambda: longcat.init_cache(cfg, 2, 96))
    ints, flags = jnp.zeros((2,), jnp.int32), jnp.ones((2,), bool)
    decode = jax.jit(lambda p, c: longcat.decode_step(
        p, c, ints, ints, flags, cfg)).lower(params, cache).as_text(
            debug_info=True)
    chunk = jax.jit(lambda p, c: longcat.prefill_chunk(
        p, c, jnp.zeros((2, 16), jnp.int32), ints, ints + 9, flags,
        cfg)).lower(params, cache).as_text(debug_info=True)
    for scope in ("attn/mla_project", "attn/kv_update", "attn/mla_attend",
                  "mlp/mlp_dense", "mlp/moe_router", "mlp/moe_dispatch",
                  "mlp/moe_experts", "mlp/moe_zero", "layers"):
        assert scope in decode and scope in chunk, scope
    # a family without zero-compute experts traces none of the scope
    other = deepseek.DeepseekConfig.preset("deepseek-tiny")
    text = jax.jit(lambda p, c: deepseek.decode_step(
        p, c, ints, ints, flags, other)).lower(
            jax.eval_shape(lambda: deepseek.init_params(jax.random.key(0),
                                                        other)),
            jax.eval_shape(lambda: deepseek.init_cache(other, 2, 96))
    ).as_text(debug_info=True)
    assert "moe_zero" not in text and "moe_experts" in text
