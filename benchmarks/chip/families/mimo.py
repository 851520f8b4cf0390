"""The MiMo-V2 family (`model_type: mimo_v2`): what the benchmark needs to
know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `XiaomiMiMo/MiMo-V2.5`'s language model as its config.json
   and the family's published description give them, in plain `jax.numpy`
   and float32 under `jax.default_matmul_precision("highest")`, no kernel,
   no cache, no ring, no chunks, a layer at a time. It imports nothing from
   `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's weights as the
   program lays them out, which is the one thing it takes from the program
   (`attn_g.{norm, wqkv [d, 64 x 192 + 4 x 192 + 4 x 128], wo [64 x 128,
   d]}` in a global layer or `attn_s.{norm, wqkv [d, 64 x 192 + 8 x 192 +
   8 x 128], wo, sink [64]}` in a sliding one, and `dense.{norm, w_in [d,
   2 x 16384] (gate and up side by side), w_out}` or `moe.{norm, router
   [d, 256], bias}` with `experts.{wg, wu [E', d, 2048], wd [E', 2048,
   d]}`). With d 4096, eps 1e-5:

       x += Attn_l(RMSNorm_a(x));  x += MLP_l(RMSNorm_m(x))      (pre-norm)
       Attn_l (64 query heads; a q and a k head 192 lanes, a v head 128):
         [q | k | v] = u W_qkv; the first int(192 x 0.334) = 64 lanes of q
           and k rotated (lane i with lane i + 32), the other 128 pass;
           v = 0.707 v; s = q . k / sqrt(192); o = sum_t p_t v_t; W_o
         global layer (`hybrid_layer_pattern[l] == 0`): 4 key-value heads
           (head h reads key-value head h // 16), theta 1e7; position i
           attends every j <= i; p = softmax(s)
         sliding layer (1): 8 key-value heads (h // 8), theta 1e4; position
           i attends j with i - 128 < j <= i, and a learned b_h a head is a
           column of the softmax that weighs no value
       MLP_0 = SwiGLU 4096 -> 16384 -> 4096
       MLP_l, l >= 1 (256 router outputs, 8 a token, no shared expert):
         s = sigmoid(h W_r); the 8 largest of s + bias chosen;
         g = s[chosen] / (sum + 1e-20)
         out = sum_k g_k SwiGLU^(e_k)(h) over the chosen experts THAT ARE
           HELD (`first_expert`..+E'): what the absent experts would add is
           left out, here as in the program
       final RMSNorm, untied head over the held rows of the vocabulary

   Attention over the whole sequence, a block of `QUERY_BLOCK` queries at a
   time against every key under a banded or a causal mask by the layer's
   kind, the sink an extra column of the scores, the rows of k (rotated) and
   v (scaled) as the configuration states them (`stated.rows`: through
   bfloat16); the MLP a block of tokens at a time, the experts a loop over
   the held ones with the gate zero outside a token's 8, an expert's
   matrices widened to float32 as the loop reaches it: so that it fits at
   the published widths and 24k positions.

   Departures from the published description, each in the configuration
   file's `assumed` or `departures`: pre-norm; a window of 128 counts the
   token itself; RoPE's pairing and which lanes rotate; gates from s without
   the bias; `attention_chunk_size` read by nothing; the three drafting
   layers and the two towers left out; seeded weights.

   `degrade` computes one part below what the configuration states or
   another mathematics (`bfloat16_stream`: the residual stream rounded to
   bfloat16 after every sublayer; `one_piece`: every product's activation
   rounded to bfloat16 first, what `lm.dot`'s second piece carries;
   `no_sink`; `sink_weighs_value`: the sink's probability weighs the
   query's own value; `window_127`, `window_129`; `rotate_all_lanes`: all
   192 lanes rotated; `thetas_swapped`; `no_value_scale`;
   `global_8_kv_heads`: a global layer's heads grouped by 8, the sliding
   layers' way; `gates_not_renormalised`): what the family's limits have to
   refuse.
2. The arithmetic of the rooflines (`gqa_attend_cost`: a global layer's
   position is 4 heads x (192 + 128) lanes of bf16; `swa_attend_cost`: a
   ring's live row 8 x (192 + 128); Kanana's `moe_experts_decode_cost` at
   this family's widths): the least a decode step must move or compute
   there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/mimo_server.py`), the tokenizer, and the check
   of what was served (`check_served`, as K-EXAONE's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
# the pieces of the reference that know nothing of a family's attention
from families.exaone import _by_blocks, _piece, _rms_norm, _swiglu
# the window's route through the engine's own programs, rows and rings alike
from families.granite import engine_logits
from families.kanana import (REQUEST_PATH, _rope,  # noqa: F401
                             _rows_and_positions, moe_experts_decode_cost,
                             request_body)
from families.kimi import CharTokenizer as _CharTokenizer
from families.solar import compare

SLIDING, GLOBAL = "sliding_attention", "full_attention"

# ----------------------------------------------------------- configuration


def layer_types(model: dict) -> list:
    """The kinds of the layers that are run: the published pattern's first
    `num_hidden_layers` (the file keeps the list whole; 0 is a global layer,
    1 a sliding one)."""
    pattern = model["hybrid_layer_pattern"][:model["num_hidden_layers"]]
    assert set(pattern) <= {0, 1}, pattern
    return [SLIDING if p else GLOBAL for p in pattern]


def rotary_lanes(model: dict) -> int:
    return int(model["head_dim"] * model["partial_rotary_factor"])


def program_sizes(config: dict) -> dict:
    """A configuration file (Hugging Face's key names under `model`, as in
    the source; the share of the deployment under `share`) in the names of
    the program's `MimoConfig`."""
    model, share = config["model"], config["share"]
    n = model["num_hidden_layers"]
    assert model["hidden_act"] == "silu" and model["scoring_func"] == "sigmoid"
    assert not model["tie_word_embeddings"] and not model["attention_bias"]
    assert model["n_group"] == 1 and model["topk_group"] == 1
    assert model["topk_method"] == "noaux_tc"
    assert model["n_shared_experts"] is None
    assert model["routed_scaling_factor"] is None
    assert model["attention_projection_layout"] == "fused_qkv"
    assert model["add_swa_attention_sink_bias"]
    assert not model["add_full_attention_sink_bias"]
    assert model["rope_scaling"]["rope_type"] == "default"
    assert model["sliding_window"] == model["sliding_window_size"]
    for swa, full in (("swa_head_dim", "head_dim"),
                      ("swa_v_head_dim", "v_head_dim"),
                      ("swa_num_attention_heads", "num_attention_heads")):
        assert model[swa] == model[full], (swa, full)
    freq = model["moe_layer_freq"][:n]
    dense = freq.index(1) if 1 in freq else n
    assert freq == [0] * dense + [1] * (n - dense), freq
    return {"vocab_size": model["vocab_size"],
            "layer_types": tuple(layer_types(model)),
            "sliding_window": model["sliding_window"],
            "n_dense_layer": dense,
            "d_model": model["hidden_size"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "swa_n_kv_head": model["swa_num_key_value_heads"],
            "head_dim": model["head_dim"],
            "v_head_dim": model["v_head_dim"],
            "rotary_dim": rotary_lanes(model),
            "rope_theta": float(model["rope_theta"]),
            "swa_rope_theta": float(model["swa_rope_theta"]),
            "value_scale": model["attention_value_scale"],
            "d_ff": model["intermediate_size"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_experts": share["router_outputs"],
            "experts_held": model["n_routed_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["num_experts_per_tok"],
            "norm_topk_prob": model["norm_topk_prob"],
            "norm_eps": model["layernorm_epsilon"]}


def program_config(config: dict):
    """The replica's `MimoConfig`, as the engine builds it."""
    from ray_tpu.models import serving_family

    deploy = config["deployment"]
    _, _, config_cls = serving_family(deploy["preset"])
    return config_cls.preset(deploy["preset"], **program_sizes(config),
                             max_seq_len=deploy["max_seq_len"])


def reference_model(config: dict) -> dict:
    """What the reference reads: the file's `model`, which of the router's
    experts are held, and the dtype the rows of k and v are stated in
    (`stated.rows`; float32 where a test's file states none)."""
    return {**config["model"], **config["share"],
            "rows": config.get("stated", {}).get("rows", "float32")}


# -------------------------------------------------------------- arithmetic


def _layers(model: dict, kind: str) -> int:
    return layer_types(model).count(kind)


def kv_heads(model: dict, kind: str) -> int:
    return model["swa_num_key_value_heads" if kind == SLIDING
                 else "num_key_value_heads"]


def experts_cost_model(model: dict) -> dict:
    """This file's keys under the names `families/kanana.py`'s
    `moe_experts_decode_cost` reads: an expert is three matrices [4096,
    2048], 50.3 MB in bf16, and a row 6 x 4,096 x 2,048 operations. The
    dense MLP is not the experts' (`mlp_dense`)."""
    return {"hidden_size": model["hidden_size"],
            "moe_intermediate_size": model["moe_intermediate_size"]}


def _attend_cost(model: dict, kind: str, rows: float) -> dict:
    lanes = model["head_dim"] + model["v_head_dim"]
    return {"bytes": rows * kv_heads(model, kind) * lanes * 2.0,
            "flops": rows * model["num_attention_heads"] * lanes * 2.0}


def gqa_attend_cost(model: dict, positions: float) -> dict:
    """The least one global layer needs to attend over `positions` cached
    positions (summed over the slots): each position's key of 192 lanes and
    value of 128 by the 4 key-value heads read once, bf16 (2,560 B), and a
    multiply-add a lane for every query head's score (192) and again for its
    weighted value (128)."""
    return _attend_cost(model, GLOBAL, positions)


def swa_attend_cost(model: dict, rows: float) -> dict:
    """The same for one sliding layer over `rows` live rows of its rings
    (summed over the slots; at most `sliding_window` a slot), 8 key-value
    heads: 5,120 B a row. No reader takes it yet (BENCHMARK.json has 128 of
    128 entries); `benchmarks/gqa_attend_blocks.py --shapes mimo-ring` reads
    the kernel alone against it."""
    return _attend_cost(model, SLIDING, rows)


def kv_bytes_per_token(model: dict) -> int:
    """What a token leaves behind for good: its key and value in the global
    layers (a sliding layer keeps a slot's last window, whatever the
    length)."""
    return int(_layers(model, GLOBAL) * gqa_attend_cost(model, 1.0)["bytes"])


def state_bytes_per_slot(model: dict) -> int:
    """The sliding layers' rings: `sliding_window` positions of keys and of
    values by their key-value heads, bf16."""
    return int(_layers(model, SLIDING) * swa_attend_cost(
        model, float(model["sliding_window"]))["bytes"])


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_stream", "one_piece", "no_sink",
           "sink_weighs_value", "window_127", "window_129",
           "rotate_all_lanes", "thetas_swapped", "no_value_scale",
           "global_8_kv_heads", "gates_not_renormalised")
QUERY_BLOCK = 128
MLP_BLOCK = 1024            # tokens of one row the MLP takes at a time


def _partly_rotated(x, positions, theta, lanes: int):
    """The first `lanes` lanes of x [T, H, p] rotated, the others as they
    are."""
    import jax.numpy as jnp

    return jnp.concatenate([_rope(x[..., :lanes], positions, theta),
                            x[..., lanes:]], axis=-1)


def _attention_row(u, p, model: dict, sliding: bool, degrade):
    """u [T, d] (normed) -> the sublayer's output [T, d], the plain form
    over the whole sequence; T a multiple of `QUERY_BLOCK` or shorter."""
    import jax.numpy as jnp

    heads, seq = model["num_attention_heads"], u.shape[0]
    groups = kv_heads(model, SLIDING if sliding else GLOBAL)
    wide, narrow = model["head_dim"], model["v_head_dim"]
    per = heads // groups
    # one short and one over, whatever the window (127, 129 as published)
    window = model["sliding_window"] + {"window_127": -1, "window_129": 1}.get(
        degrade, 0) if sliding else None
    u = _piece(u, degrade)
    q, k, v = jnp.split(u @ p["wqkv"], [heads * wide,
                                        (heads + groups) * wide], axis=-1)
    q = q.reshape(seq, heads, wide)
    k = k.reshape(seq, groups, wide)
    v = v.reshape(seq, groups, narrow)
    if degrade != "no_value_scale":
        v = v * model["attention_value_scale"]
    thetas = (float(model["swa_rope_theta"]), float(model["rope_theta"]))
    theta = thetas[(not sliding) ^ (degrade == "thetas_swapped")]
    lanes = wide if degrade == "rotate_all_lanes" else rotary_lanes(model)
    q = _partly_rotated(q, jnp.arange(seq), theta, lanes)
    k = _partly_rotated(k, jnp.arange(seq), theta, lanes)
    if model["rows"] == "bfloat16":
        # what the cache holds, as the configuration states it (`stated`)
        k, v = _through_bfloat16(k), _through_bfloat16(v)
    if degrade == "global_8_kv_heads" and not sliding:
        # the sliding layers' grouping, over the heads this layer has
        other = heads // model["swa_num_key_value_heads"]
        reads = (jnp.arange(heads) // other) % groups
        k, v, groups, per = k[:, reads], v[:, reads], heads, 1
    q = _piece(q.reshape(seq, groups, per, wide), degrade)
    sink = None
    if sliding and degrade != "no_sink":
        sink = p["sink"].reshape(groups, per)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, first = args
        scores = jnp.einsum("igrc,jgc->grij", qb, k) / math.sqrt(wide)
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(seq)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        scores = jnp.where(seen, scores, -jnp.inf)
        top = jnp.max(scores, axis=-1, keepdims=True)
        if sink is not None:
            # the sink: a column of the softmax with no row of values
            top = jnp.maximum(top, sink[:, :, None, None])
        probs = jnp.exp(scores - top)
        total = jnp.sum(probs, axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink[:, :, None, None] - top)
        probs = probs / total
        out = jnp.einsum("grij,jgc->igrc", _piece(probs, degrade), v)
        if sink is not None and degrade == "sink_weighs_value":
            own = jnp.take(v, first + jnp.arange(block), axis=0)  # [i,g,c]
            left = 1.0 - jnp.sum(probs, axis=-1)                 # [g,r,i]
            out = out + jnp.einsum("gri,igc->igrc", left, own)
        return out

    import jax

    blocks = seq // block
    o = jax.lax.map(attend, (q.reshape(blocks, block, groups, per, wide),
                             jnp.arange(blocks) * block))
    return _piece(o.reshape(seq, heads * narrow), degrade) @ p["wo"]


def _expert_block(h, moe, experts, model: dict, degrade=None):
    """h [T, d] (normed) -> (the held experts' part of the routed sum, what
    the router chose [T, K]). `experts` as the
    replica holds them: each is widened to float32 as the loop reaches
    it."""
    import jax
    import jax.numpy as jnp

    top_k, first = model["num_experts_per_tok"], model["first_expert"]
    held = experts["wg"].shape[0]
    n_experts = moe["router"].shape[1]
    assert n_experts == model["router_outputs"]
    assert held == model["n_routed_experts"]
    s = jax.nn.sigmoid(h @ moe["router"])
    _, chosen = jax.lax.top_k(s + moe["bias"], top_k)
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    if model["norm_topk_prob"] and degrade != "gates_not_renormalised":
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=h.dtype)
                    * kept[..., None], axis=-2)                    # [T, E]
    mine = jnp.moveaxis(gates[..., first:first + held], -1, 0)
    rows = _piece(h, degrade)

    def expert(acc, e):
        wg, wu, wd = (w.astype(jnp.float32) for w in e[:3])
        mid = _piece(jax.nn.silu(rows @ wg) * (rows @ wu), degrade)
        return acc + e[3][..., None] * (mid @ wd), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (experts["wg"], experts["wu"], experts["wd"], mine))
    return routed, chosen


def reference_layer(x, p, model: dict, sliding: bool, degrade=None):
    """x [R, T, d] float32 -> x after the layer whose weights are `p`
    (`init_layer`'s tree: `attn_s` or `attn_g`, and `dense` or `moe` +
    `experts`), a sliding or a global layer: R sequences, each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    eps = model["layernorm_epsilon"]
    experts = p.get("experts")
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     {k: v for k, v in p.items() if k != "experts"})

    def stream(t):
        return _through_bfloat16(t) if degrade == "bfloat16_stream" else t

    def mlp(h):
        if "dense" in p:
            return _swiglu(h, p["dense"], degrade)
        return _expert_block(h, p["moe"], experts, model, degrade)[0]

    def row(xr):
        a = p["attn_s" if sliding else "attn_g"]
        xr = stream(xr + _attention_row(
            _rms_norm(xr, a["norm"]["scale"], eps), a, model, sliding,
            degrade))
        scale = p["dense" if "dense" in p else "moe"]["norm"]["scale"]
        return stream(xr + _by_blocks(mlp, _rms_norm(xr, scale, eps),
                                      MLP_BLOCK))

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, x)


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, held vocabulary]: the final norm and the
    untied head."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["layernorm_epsilon"])
        return x @ ends["lm_head"].astype(jnp.float32)


class Reference:
    """The reference walked a layer at a time over several sequences of one
    padded length: `layer_weights(l)` makes layer l's weights (the program's
    `init_layer` from the seed, or a test's own), which are dropped before
    the next layer's are made. `model` is `reference_model(config)`."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        # one compiled program a kind of layer: the kinds' trees and masks
        # differ
        self._layer = jax.jit(
            lambda x, p, sliding: reference_layer(x, p, model, sliding,
                                                  degrade),
            static_argnums=(2,))
        # `ends` an argument: closed over, the table and the head would be
        # constants of the compiled program
        self._head = jax.jit(lambda x, ends: reference_head(x, ends, model))

    def hidden(self, rows: list):
        """rows: token id lists -> their final hidden [R, T_padded, d]
        (causal: the padding after a row cannot reach it)."""
        import jax.numpy as jnp
        import numpy as np

        width = -(-max(len(r) for r in rows) // QUERY_BLOCK) * QUERY_BLOCK
        ids = np.zeros((len(rows), width), np.int32)
        for i, row in enumerate(rows):
            ids[i, :len(row)] = row
        x = self.ends["wte"][jnp.asarray(ids)].astype(jnp.float32)
        for l, kind in enumerate(layer_types(self.model)):
            p = self.layer_weights(l)
            x = self._layer(x, p, kind == SLIDING)
            del p
        return x

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
    """`families/gpt2.py`'s one character a token id, with an end-of-text
    id inside the held slice of the vocabulary (`assumed.tokenizer`)."""

    eos_id = 19071


def engine_options(config: dict, seed: int) -> dict:
    """What the deployment hands `LLMEngine`: the replica's engine and the
    one the check builds are made alike from these."""
    deploy = config["deployment"]
    return dict(
        preset=deploy["preset"],
        model_overrides=program_sizes(config),
        max_batch=deploy["max_batch"], max_seq_len=deploy["max_seq_len"],
        seed=seed, tokenizer=CharTokenizer(),
        scheduler=deploy["scheduler"],
        enable_prefix_caching=deploy["enable_prefix_caching"],
        prefill_chunk_size=deploy["prefill_chunk_size"],
        kv_blocks=deploy["kv_blocks"],
        kv_block_size=deploy["kv_block_size"])


def build_app(config: dict, seed: int, num_tpu_chips: int):
    """`serve/llm.build_openai_app`'s deployment, option for option, with
    `BenchServer` in `OpenAIServer`'s place, as `families/solar.py` does."""
    from ray_tpu.serve.api import deployment

    from families.mimo_server import BenchServer

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
    to the readers (which see the record, not the configuration): under the
    names granite's readers know for the global layers' rows (the engine's
    `positions_attended` counts a lane's position once a step, which is a
    global layer's; the rings' cost stands apart) and Kanana's for the held
    experts."""
    experts = experts_cost_model(model)
    return {"gqa_layers": _layers(model, GLOBAL),
            "gqa_attend_per_position": gqa_attend_cost(model, 1.0),
            "swa_layers": _layers(model, SLIDING),
            "swa_attend_per_row": swa_attend_cost(model, 1.0),
            "routed_experts": model["n_routed_experts"],
            "moe_experts_per_row": moe_experts_decode_cost(experts, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(experts, 0.0, 1.0)}


# What decides `correct`, in two steps as for Solar and Nemotron
# (`families/kimi.py` has the two steps' account, `families/kanana.py` why
# the served tokens alone cannot decide).
#
# 1. What was served is what the timed programs compute: the share of served
#    tokens that are not their row's maximum in the engine's own logits,
#    taken the way the window's requests went (`engine_logits`: a pool hit
#    of rows by the block and of the rings' snapshot, a chunk step for the
#    question, then decode steps), may not pass
#    `SERVED_NOT_ENGINE_TOP_LIMIT` (Kimi's limit, for Kimi's reason: a
#    decode lane that rides a chunk step goes through the chunk program's
#    own compilation of the first lane).
# 2. Those logits are the reference's, by two numbers over the generated
#    positions, each position's the mean absolute difference of its logits:
#    the tenth percentile over the positions, the floor, may not pass
#    `ENGINE_LOGIT_FLOOR_ABS_LIMIT`, and the mean may not pass
#    `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The floor holds the precision (a
#    rounding below what the file states moves every position), the mean a
#    fault in a minority of the positions and the other mathematics
#    (`families/solar.py` has the argument).
#
#    The program's floor is 0.00030-0.00038 and its mean 0.00032-0.0015 in
#    every reading (the logits' spread is 1.28). The floor is what is left
#    of the rows' own bf16 and the pieces' remainder, and it is tight; the
#    mean has a tail, six routers choosing 8 of 256: a pair that changes
#    places between a held expert and an absent one moves its token's
#    stream (a held expert's second matrix is drawn at 0.02 so that the
#    experts' kernel shows in the logits). A stream through bfloat16 has a
#    floor of 0.0060-0.0062 (mean 0.0089-0.0125) and every product's
#    activation as one bf16 piece 0.0038-0.0045 (0.0073-0.0100): the
#    floor's limit lies 3.4 times above the program's widest floor and 2.9
#    times under the narrowest of the two, and refuses each; the mean's
#    limit lies 4 times above the program's widest reading, for the tail,
#    and under every reading of the two, the nearest by a fifth: it is not
#    what holds them. Every other mathematics is refused by both, in every
#    reading: a window of 127 or 129 reads a floor of 0.020-0.022 (mean
#    0.034-0.040), no sink 0.065-0.071 (0.083-0.089), a global layer's
#    heads grouped by 8 0.12-0.30 (0.15-0.34), no value scale 0.14-0.19
#    (0.17-0.21), gates not renormalised 0.18-0.21 (0.69-0.73), the two
#    thetas swapped 0.22-0.29 (0.24-0.33), a sink that weighs a value
#    0.25-0.26 (0.28-0.29), all 192 lanes rotated 0.32-0.40 (0.35-0.43).
#
# The readings that set the limits are the configuration file's `limits`
# (rehearse/mimo_on_chip.py on the v5e at the published widths, and the
# cell's own runs; PERF.md section 6, PR 62).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.006
ENGINE_LOGIT_FLOOR_ABS_LIMIT = 0.0013


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import serving_family

    _, module, _ = serving_family(config["deployment"]["preset"])
    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: module.init_layer(key, l, cfg),
            module.init_ends(key, cfg))


def stopped_engine(config: dict, seed: int):
    """An `LLMEngine` made as the replica's was (the seed's weights, the
    deployment, the compile cache's programs) with its loop stopped: its
    two step programs, its cache and its pool are the caller's to drive."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(**engine_options(config, seed))
    eng.shutdown()
    eng._thread.join()
    return eng


LIMITS = {"served_not_engine_top_share": SERVED_NOT_ENGINE_TOP_LIMIT,
          "engine_logit_mean_abs": ENGINE_LOGIT_MEAN_ABS_LIMIT,
          "engine_logit_floor_abs": ENGINE_LOGIT_FLOOR_ABS_LIMIT}


def verdict(readings: dict) -> dict:
    if "error" in readings:
        return {"ok": False, **readings}
    return {"ok": all(readings[name] <= limit
                      for name, limit in LIMITS.items()),
            **readings, "limits": LIMITS}


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
    reference = Reference(reference_model(config), layer_weights,
                          ends).logits(rows, at)
    return {**verdict(compare(served, engine, reference)),
            "replies": len(served),
            "seconds": {"engine_build": round(t_built - t0, 1),
                        "engine": round(t1 - t_built, 1),
                        "reference": round(time.time() - t1, 1)}}
