"""The Keye-VL-2.0 family (`model_type: KeyeVL2`, the language model): what
the benchmark needs to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layer of `Kwai-Keye/Keye-VL-2.0-30B-A3B` as its config.json's keys
   (Qwen3-MoE's, with `sa_config`'s DeepSeek-Sparse-Attention indexer)
   describe it, in plain `jax.numpy` and float32 under
   `jax.default_matmul_precision("highest")`, no cache, no chunks, a layer
   at a time, a block of queries at a time. It imports nothing from
   `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's weights as the
   program lays them out, which is the one thing it takes from the program
   (`layer.{attn_norm, w_qkv [d, 32 x 128 + 2 x 4 x 128] (W_q, W_k, W_v
   side by side), q_norm, k_norm [128], wo, w_index [d, 16 x 64 + 64 + 16
   (+ 48 of padding)] (W_qI, W_kI, W_w side by side), ki_norm.{scale,
   bias}, mlp_norm, router [d, 128]}` with `experts.{wg, wu [128, d, 768],
   wd}`). With d 2048, eps 1e-6, u = RMSNorm(x):

       q, k, v = u W_q, u W_k, u W_v; RMSNorm over a head's 128 on q, k;
         rotary (rotate-half, theta 1e7), pair i of 64 at position stream
         section(i) of mrope_section [16, 24, 24]
       qI = u W_qI -> [16, 64]; kI = LayerNorm(u W_kI); w = u W_w / 32;
         rotary on qI, kI (32 pairs, stream 0)
       I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]),  s <= t
       S_t = the 2,048 largest of I[t, .] by a stable sort (ties to the
         lower index); all s <= t while t < 2,048
       o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h // 8] / sqrt(128))
         v[s, h // 8];  x += o W_o
       h = RMSNorm(x); p = softmax(h W_r); the 8 largest, renormalised;
         x += sum_e p_e W_d^e (silu(W_g^e h) * W_u^e h)
       final RMSNorm, untied head

   The selection is a mask over the whole sequence from a stable sort of a
   block of queries' scores (never `top_k`, nor the program's two forms),
   the experts a loop over all 128 with the gate zero outside a token's
   eight. `degrade` computes another mathematics (`dense_attend`: no
   selection; `window`: the last 2,048 positions; `half_topk`: 1,024) or
   one part below what the configuration states (`bfloat16_scores`: the
   indexer's scores through bfloat16 after the sum; `float8_rows`: k, v
   and the indexer's key through float8): what the family's limits have to
   refuse.
2. The arithmetic of the rooflines (`dsa_index_cost`, `dsa_select_cost`,
   `dsa_attend_cost`, and Kanana's `moe_experts_decode_cost`, whose keys
   this configuration shares): the least a decode step must move or
   compute there, whatever implements the scope, XLA's gather or a kernel.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/keye_server.py`), the tokenizer, and the check of
   what was served (`check_served`, as the document cells').
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
from families.gpt2 import CharTokenizer as _CharTokenizer
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             _through_float8, compare_served, engine_logits,
                             moe_experts_decode_cost, request_body)

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `KeyeConfig`."""
    sa, rope = model["sa_config"], model["rope_scaling"]
    assert model["hidden_act"] == "silu" and not model["attention_bias"]
    assert model["decoder_sparse_step"] == 1 and not model["mlp_only_layers"]
    assert model["num_experts"] == model["num_local_experts"]
    assert not model["use_sliding_window"] and not model["tie_word_embeddings"]
    assert sa["indexer_num_kv_heads"] == 1 and rope["rope_type"] == "default"
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "d_model": model["hidden_size"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "rope_theta": float(model["rope_theta"]),
            "mrope_section": tuple(rope["mrope_section"]),
            "index_heads": sa["indexer_num_heads"],
            "index_head_dim": sa["indexer_head_dim"],
            "index_topk": sa["topk"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_experts": model["num_experts"],
            "experts_per_token": model["num_experts_per_tok"],
            "norm_topk_prob": model["norm_topk_prob"],
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `KeyeConfig`, as the engine builds it."""
    from ray_tpu.models import keye

    deploy = config["deployment"]
    return keye.KeyeConfig.preset(
        deploy["preset"], **program_sizes(config["model"]),
        max_seq_len=deploy["max_seq_len"])


# -------------------------------------------------------------- arithmetic


def dsa_index_cost(model: dict, positions: float) -> dict:
    """The least one layer's indexer needs for `positions` scored positions
    (each lane's pos + 1, summed over a step's lanes): each position's one
    key read once (64 values of 2 bytes), and for each of the 16 heads a
    product over 64 (2 x 64 operations), the ReLU, the weight and the sum
    (3). Bound by the bytes on a v5e (2,096 operations against 128 bytes a
    position, the chip's balance 240 an byte)."""
    sa = model["sa_config"]
    heads, e = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"bytes": positions * e * 2.0,
            "flops": positions * heads * (2.0 * e + 3)}


def dsa_select_cost(model: dict, positions: float) -> dict:
    """The least a selection that is an operation of its own needs for
    `positions` scored positions: each float32 score read once and
    compared once."""
    del model
    return {"bytes": positions * 4.0, "flops": positions * 1.0}


def dsa_attend_cost(model: dict, rows: float) -> dict:
    """The least one layer's attention needs for `rows` chosen rows (each
    lane's min(pos + 1, topk), summed over a step's lanes): each chosen
    row's key and value read once by the 4 key-value heads (2 x 4 x 128
    values of 2 bytes), and 2 x 32 x 128 operations for the scores and as
    many for the weighted values."""
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["head_dim"]
    return {"bytes": rows * 2.0 * kv * d * 2,
            "flops": rows * 4.0 * heads * d}


def kv_bytes_per_token(model: dict) -> int:
    """k, v and the indexer's key, every layer, 2 bytes a value."""
    return model["num_hidden_layers"] * 2 * (
        2 * model["num_key_value_heads"] * model["head_dim"]
        + model["sa_config"]["indexer_head_dim"])


# --------------------------------------------------------------- reference

DEGRADE = (None, "dense_attend", "window", "half_topk", "bfloat16_scores",
           "float8_rows")
QUERY_BLOCK = 256
INDEX_NORM_EPS = 1e-6       # the LayerNorm on the indexer's key (assumed)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, p, eps):
    import jax.numpy as jnp

    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    return centred / jnp.sqrt(jnp.mean(centred * centred, axis=-1,
                                       keepdims=True) + eps) \
        * p["scale"] + p["bias"]


def _rotate(x, angle):
    """x [T, H, p] by `angle` [T, p/2]: lane i turns with lane i + p/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _angles(positions, dim: int, theta: float):
    """positions [T] -> [T, dim/2]."""
    import jax.numpy as jnp

    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    return positions.astype(jnp.float32)[:, None] * inv[None]


def _mrope_angles(positions, model: dict):
    """positions [3, T] -> [T, 64]: pair i's angle from the stream its
    section names."""
    import jax.numpy as jnp
    import numpy as np

    sections = model["rope_scaling"]["mrope_section"]
    stream = np.repeat(np.arange(3), sections)
    each = [_angles(p, model["head_dim"], float(model["rope_theta"]))
            for p in positions]
    return sum(jnp.where(jnp.asarray(stream == s), each[s], 0.0)
               for s in range(3))


def _selected(scores, k: int):
    """scores [Q, T] (-inf where a row is not to be seen) -> the mask of
    each query's k largest by a stable sort: equal scores keep the order
    of their indices."""
    import jax.numpy as jnp

    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return rank < k


def _attention(u, p, model: dict, positions, degrade):
    """u [T, d] (normed), positions [3, T] -> the mixer's output [T, d]; T
    a multiple of `QUERY_BLOCK` or shorter than it."""
    import jax
    import jax.numpy as jnp

    sa = model["sa_config"]
    heads, kv, d = (model["num_attention_heads"],
                    model["num_key_value_heads"], model["head_dim"])
    per = heads // kv
    ih, e, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, seq = model["rms_norm_eps"], u.shape[0]
    qkv = u @ p["w_qkv"]
    q = _rms_norm(qkv[:, :heads * d].reshape(seq, heads, d),
                  p["q_norm"]["scale"], eps)
    k = _rms_norm(qkv[:, heads * d:(heads + kv) * d].reshape(seq, kv, d),
                  p["k_norm"]["scale"], eps)
    v = qkv[:, (heads + kv) * d:].reshape(seq, kv, d)
    angle = _mrope_angles(positions, model)
    q, k = _rotate(q, angle), _rotate(k, angle)
    iq = u @ p["w_index"]
    angle_i = _angles(positions[0], e, float(model["rope_theta"]))
    qi = _rotate(iq[:, :ih * e].reshape(seq, ih, e), angle_i)
    ki = _rotate(_layer_norm(iq[:, ih * e:(ih + 1) * e], p["ki_norm"],
                             INDEX_NORM_EPS)[:, None], angle_i)[:, 0]
    w = iq[:, (ih + 1) * e:(ih + 1) * e + ih] / math.sqrt(ih * e)
    if degrade == "float8_rows":
        k, v, ki = _through_float8(k), _through_float8(v), _through_float8(ki)
    if degrade == "half_topk":
        topk //= 2
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, qib, wb, first = args
        at = (first + jnp.arange(block))[:, None]
        seen = jnp.arange(seq)[None, :] <= at
        if degrade == "dense_attend":
            keep = seen
        elif degrade == "window":
            keep = seen & (jnp.arange(seq)[None, :] > at - topk)
        else:
            index = jnp.sum(
                jax.nn.relu(jnp.einsum("ije,se->ijs", qib, ki))
                * wb[:, :, None], axis=1)                        # [blk, T]
            if degrade == "bfloat16_scores":
                index = _through_bfloat16(index)
            keep = _selected(jnp.where(seen, index, -jnp.inf), topk) & seen
        scores = jnp.einsum("igrd,sgd->gris",
                            qb.reshape(block, kv, per, d), k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep[None, None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("gris,sgd->igrd", probs, v).reshape(block,
                                                              heads * d)

    blocks = seq // block
    out = jax.lax.map(attend, (q.reshape(blocks, block, heads, d),
                               qi.reshape(blocks, block, ih, e),
                               w.reshape(blocks, block, ih),
                               jnp.arange(blocks) * block))
    return out.reshape(seq, heads * d) @ p["wo"]


def _expert_block(h, router, experts, model: dict):
    """h [T, d] (normed) -> (the routed sum, what the router chose
    [T, K])."""
    import jax
    import jax.numpy as jnp

    top_k, n_experts = model["num_experts_per_tok"], model["num_experts"]
    assert router.shape[1] == experts["wg"].shape[0] == n_experts
    probs = jax.nn.softmax(h @ router, axis=-1)
    kept, chosen = jax.lax.top_k(probs, top_k)
    if model["norm_topk_prob"]:
        kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=h.dtype)
                    * kept[..., None], axis=-2)                    # [T, E]

    def expert(acc, e):
        wg, wu, wd, gate = e
        y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
        return acc + gate[:, None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (experts["wg"], experts["wu"], experts["wd"], gates.T))
    return routed, chosen


def reference_layer(x, p, model: dict, positions=None, degrade=None):
    """x [T, d] float32 -> x after the layer whose weights are `p`
    (`layer` and `experts`, as `keye.init_layer` makes them). `positions`
    [3, T]: every token's three position streams; a text's are its index,
    three times."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    layer, eps = p["layer"], model["rms_norm_eps"]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(x.shape[0]),
                                     (3, x.shape[0]))
    with jax.default_matmul_precision("highest"):
        x = x + _attention(_rms_norm(x, layer["attn_norm"]["scale"], eps),
                           layer, model, positions, degrade)
        return x + _expert_block(
            _rms_norm(x, layer["mlp_norm"]["scale"], eps), layer["router"],
            p["experts"], model)[0]


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, vocab]: the final norm and the untied head."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["rms_norm_eps"])
        return x @ ends["lm_head"].astype(jnp.float32)


class Reference:
    """The reference walked a layer at a time over several sequences of one
    padded length, each its own: `layer_weights(l)` makes layer l's weights
    (the program's `init_layer` from the seed, or a test's own), which are
    dropped before the next layer's are made."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        self._layer = jax.jit(
            lambda x, p: reference_layer(x, p, model, degrade=degrade))
        # `ends` an argument: closed over, the table and the head would be
        # constants of the compiled program
        self._head = jax.jit(lambda x, ends: reference_head(x, ends, model))

    def hidden(self, rows: list) -> list:
        """rows: token id lists -> each row's final hidden [T_padded, d]
        (causal: the padding after a row cannot reach it)."""
        import jax.numpy as jnp
        import numpy as np

        width = -(-max(len(r) for r in rows) // QUERY_BLOCK) * QUERY_BLOCK
        xs = []
        for row in rows:
            ids = np.zeros((width,), np.int32)
            ids[:len(row)] = row
            xs.append(self.ends["wte"][jnp.asarray(ids)].astype(jnp.float32))
        for l in range(self.model["num_hidden_layers"]):
            p = self.layer_weights(l)
            xs = [self._layer(x, p) for x in xs]
            del p
        return xs

    def logits(self, rows: list, at: list) -> list:
        """For each row the float32 logits [len(at[i]), vocab] at the
        positions `at[i]`."""
        import jax.numpy as jnp
        import numpy as np

        xs = self.hidden(rows)
        most = -(-max(len(a) for a in at) // 64) * 64
        out = []
        for x, positions in zip(xs, at):
            take = np.zeros((most,), np.int32)
            take[:len(positions)] = positions
            out.append(np.asarray(self._head(x[jnp.asarray(take)], self.ends))
                       [:len(positions)])
        return out


# ----------------------------------------------------------------- serving


class CharTokenizer(_CharTokenizer):
    """`families/gpt2.py`'s one character a token id (it reaches 196,608
    ids), with this vocabulary's end-of-text id (`<|endoftext|>`, 151643 in
    the Qwen tokenizer the config's 151,936 rows are: `assumed.tokenizer`)."""

    eos_id = 151643


def engine_options(config: dict, seed: int) -> dict:
    """What the deployment hands `LLMEngine`: the replica's engine and the
    one the check builds are made alike from these."""
    deploy = config["deployment"]
    return dict(
        preset=deploy["preset"],
        model_overrides=program_sizes(config["model"]),
        max_batch=deploy["max_batch"], max_seq_len=deploy["max_seq_len"],
        seed=seed, tokenizer=CharTokenizer(),
        scheduler=deploy["scheduler"],
        enable_prefix_caching=deploy["enable_prefix_caching"],
        prefill_chunk_size=deploy["prefill_chunk_size"],
        kv_blocks=deploy["kv_blocks"],
        kv_block_size=deploy["kv_block_size"])


def build_app(config: dict, seed: int, num_tpu_chips: int):
    """`serve/llm.build_openai_app`'s deployment, option for option, with
    `BenchServer` in `OpenAIServer`'s place, as `families/granite.py` does."""
    from ray_tpu.serve.api import deployment

    from families.keye_server import BenchServer

    # a program without this family says so here, in the phase's own
    # process, and not in a replica that the deployment starts again
    program_config(config)
    actor_options = {"num_cpus": 1}
    if num_tpu_chips:
        actor_options["num_tpu_chips"] = num_tpu_chips
    model_id = config["name"]
    slots = config["deployment"]["max_batch"]
    dep = deployment(BenchServer, name=f"openai-{model_id}",
                     num_replicas=1, ray_actor_options=actor_options,
                     max_ongoing_requests=slots * 2, slo_config=None)
    return dep.bind(model_id=model_id, checkpoint=None,
                    **engine_options(config, seed),
                    roofline_costs=roofline_costs(config["model"]))


def roofline_costs(model: dict) -> dict:
    """The cost functions at one unit, for the replica's `stats()` to carry
    to the readers (which see the record, not the configuration): the
    experts' under the names Kanana's readers know, and the three steps of
    the sparse attention, a scored position and a chosen row."""
    return {"attention_layers": model["num_hidden_layers"],
            "routed_experts": model["num_experts"],
            "moe_experts_per_row": moe_experts_decode_cost(model, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(model, 0.0, 1.0),
            "dsa_layers": model["num_hidden_layers"],
            "dsa_index_per_position": dsa_index_cost(model, 1.0),
            "dsa_select_per_position": dsa_select_cost(model, 1.0),
            "dsa_attend_per_row": dsa_attend_cost(model, 1.0)}


# What decides `correct`, in two steps as for the other document cells
# (`families/kanana.py` says why the served tokens alone cannot: with seeded
# weights the largest logit changes on rounding).
#
# 1. What was served is what the timed programs compute. With the chip
#    free, an engine made as the replica's was takes the sampled replies the
#    way the window's requests went (`families/kanana.py`'s
#    `engine_logits`): each prompt's whole blocks prefilled in chunks in one
#    slot, the rows there pooled (all three leaves), found again and copied
#    into another slot, the question as a chunk, and the served tokens
#    decoded one step each, the sampled replies live in their slots at
#    once. The share of served tokens that are not their row's maximum may
#    not pass `SERVED_NOT_ENGINE_TOP_LIMIT`. The cell reads 0 in thirteen
#    of fifteen runs, 0.33% and 0.13% in the others (4 of 1,199 tokens and
#    2 of 1,536): a decode lane
#    that rides a chunk step goes through the chunk program's own
#    compilation of the first lane, the check decodes every served token
#    through the decode program, and where the two round apart a row at
#    the set's boundary can change sides. The limit lies nine times above
#    the wider reading (one wrong refusal costs a PR); another slot's, seed's or
#    model's tokens read 100%.
# 2. Those logits, the timed programs' own, are the reference's: their mean
#    absolute difference at the generated positions (the logits' spread is
#    0.905) may not pass `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The program reads
#    0.0549-0.0610 over its seeds and the cell's runs (0.053 a row at 8,192
#    positions, 0.063 at 12,288), five times what Kanana's reads on the
#    same expert shape, and that is the set: the cache's bf16 keys move an
#    indexer score by a rounding, a slot's 8-13 thousand scores lie closer
#    than that at the boundary of 2,048, so a few rows a query a layer fall
#    on its other side than in the float32 reference, and with seeded
#    weights (no indexer trained toward attention's weights) a swapped row
#    is a random row of attention's. The reference with rows through float8
#    reads 0.0905-0.0907, with a dense attend 0.213, with a topk of 1,024
#    0.242, with a window of the last 2,048 0.364-0.367: the limit lies 1.23
#    times above the program's widest reading (1.19 above the widest row)
#    and 1.21 times under the mildest of those four, and refuses each. The
#    indexer's scores through bfloat16 after the sum read 0.0552-0.0563,
#    inside the program's own range: rounding the scores moves them by as
#    much as the cache's keys already do, and no limit on these logits can
#    tell the two apart (Kimi's float8 rows are the precedent).
#
# Every reading is beside the limits in the configuration file (`limits`)
# and in PERF.md (PR 46): rehearse/keye_on_chip.py on the v5e at the
# published widths, seeds 1-3, and the cell's own runs.
SERVED_NOT_ENGINE_TOP_LIMIT = 0.03
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.075


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import keye

    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: keye.init_layer(key, l, cfg),
            keye.init_ends(key, cfg))


def stopped_engine(config: dict, seed: int):
    """An `LLMEngine` made as the replica's was (the seed's weights, the
    deployment, the compile cache's programs) with its loop stopped: its
    two step programs, its cache and its pool are the caller's to drive."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(**engine_options(config, seed))
    eng.shutdown()
    eng._thread.join()
    return eng


def verdict(readings: dict) -> dict:
    if "error" in readings:
        return {"ok": False, **readings}
    return {"ok": bool(
        readings["served_not_engine_top_share"]
        <= SERVED_NOT_ENGINE_TOP_LIMIT
        and readings["engine_logit_mean_abs"]
        <= ENGINE_LOGIT_MEAN_ABS_LIMIT), **readings,
        "limits": {"served_not_engine_top_share": SERVED_NOT_ENGINE_TOP_LIMIT,
                   "engine_logit_mean_abs": ENGINE_LOGIT_MEAN_ABS_LIMIT}}


def check_served(config: dict, seed: int, served: list) -> dict:
    """With the chip free: the engine's logits for what was served, then
    (the engine let go) the reference's, a layer at a time."""
    import gc
    import time

    if not served:
        return {"ok": False, "error": "no greedy reply ended in the window"}
    t0 = time.time()
    eng = stopped_engine(config, seed)
    t_built = time.time()
    engine = engine_logits(eng, served)
    del eng
    gc.collect()                        # the engine's weights and cache
    t1 = time.time()
    layer_weights, ends = seeded_weights(config, seed)
    rows, at = _rows_and_positions(served)
    reference = Reference(config["model"], layer_weights, ends).logits(rows,
                                                                       at)
    return {**verdict(compare_served(served, engine, reference)),
            "replies": len(served),
            "seconds": {"engine_build": round(t_built - t0, 1),
                        "engine": round(t1 - t_built, 1),
                        "reference": round(time.time() - t1, 1)}}
