"""The LongCat-Flash family (`attention_method: MLA`, shortcut-connected
MoE): what the benchmark needs to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `meituan-longcat/LongCat-Flash-Chat` as its config.json and
   the published description give them, in plain `jax.numpy` and float32
   under `jax.default_matmul_precision("highest")`, MLA in its plain form (no
   absorption, no cache), no kernel, no batching, a layer at a time. It
   imports nothing from `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's
   weights as the program lays them out, which is the one thing it takes
   from the program (`attn.{norm, wqa [d, 1536], q_norm, wqb [1536, 64, 192],
   wkva [d, 576], kv_norm, wkvb [512, 64, 256], wo [8192, d]}` and
   `dense.{norm, w_in [d, 2 x 12288] (gate and up side by side), w_out}`,
   each with a leading axis of 2, the layer's two sublayers; `moe.{router
   [1, d, 768], bias [1, 768]}`; `experts.{wg, wu [E', d, 2048], wd}`). A
   layer is a DOUBLE layer. With d 6144, eps 1e-5:

       a  = x + MLA_0(RMSNorm_a0(x))
       h0 = RMSNorm_m0(a)
       r  = ExpertBlock(h0)
       b  = a + SwiGLU_0(h0)
       c  = b + MLA_1(RMSNorm_a1(b))
       x' = c + SwiGLU_1(RMSNorm_m1(c)) + r     # r lands a sublayer late
       MLA_i (64 heads, n = 128, p = 64, v = 128; r_q = 1536, r = 512):
         c_q = 2 RMSNorm_q(u W_qa);  q = c_q W_qb -> [64, 128 + 64]
         [c, k_r] = u W_kva;  c = 3.4641 RMSNorm_kv(c)
         RoPE(theta 1e7) on q[.., 128:] a head and on k_r, one key for all
         [k_nope, val] = c W_kvb -> [64, 128 + 128]
         causal softmax((q_nope . k_nope + q_r . k_r) / sqrt(192)) . val; W_o
       ExpertBlock (768 router outputs of which the last 256 zero-compute,
         12 a token, scale 6, no renormalisation, no shared expert):
         s = softmax(h0 W_r); the 12 largest of s + bias chosen; g = 6 s
         r = sum over the chosen experts THAT ARE HELD (`first_expert`..+E')
             of g_k SwiGLU^(e_k)(h0)  +  (sum_{k: e_k >= 512} g_k) h0:
         what the absent experts would add is left out, here as in the
         program, and the zero term is whole (every chip computes it alike)
       final RMSNorm, untied head over the held rows of the vocabulary

   c and k_r are held as the configuration states them (`stated.rows`:
   through bfloat16, the cache's precision; `families/solar.py` has why a
   reference shares the storage precision the file states), attention a
   block of `QUERY_BLOCK` queries at a time, the experts a loop over the
   held ones with the gate zero outside a token's 12, an expert's matrices
   widened to float32 as the loop reaches it.

   Departures from the published description, each in the configuration
   file's `assumed` or `departures`: silu and the SwiGLU form,
   `norm_topk_prob` false, where the two factors apply, untied embeddings,
   RoPE's pairing (lane i with lane i + p/2), seeded weights and bias, no
   drafting module.

   `degrade` computes one part below what the configuration states or
   another mathematics (`bfloat16_stream`: the residual stream rounded to
   bfloat16 after every sublayer's add; `one_piece`: every product's
   activation rounded to bfloat16 first, what `lm.dot`'s second piece is
   there to carry; `float8_rows`: c and k_r through float8; `no_zero_term`:
   the zero-compute experts' pairs add nothing; `unscaled_latent`: c
   without its factor 3.4641; `r_a_sublayer_early`: r added with SwiGLU_0's
   result, before MLA_1 reads the stream): what the family's limits have to
   refuse.
2. The arithmetic of the rooflines (Kanana's `mla_attend_cost` and
   `moe_experts_decode_cost` at this family's keys): the least a decode
   step must move or compute there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/longcat_server.py`), the tokenizer, and the check
   of what was served (`check_served`, as Solar's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
from families.gpt2 import CharTokenizer as _CharTokenizer
from families.kanana import (REQUEST_PATH, _rope,  # noqa: F401
                             _rows_and_positions, _through_float8,
                             engine_logits, mla_attend_cost,
                             moe_experts_decode_cost, request_body)
from families.solar import compare

# ----------------------------------------------------------- configuration


def program_sizes(config: dict) -> dict:
    """A configuration file (the source's key names under `model`; the share
    of the deployment under `share`) in the names of the program's
    `LongcatConfig`."""
    model, share, assumed = config["model"], config["share"], config["assumed"]
    assert model["attention_method"] == "MLA" and not model["attention_bias"]
    assert assumed["hidden_act"].startswith("silu")
    assert share["router_outputs"] - share["zero_compute_outputs"] \
        == config["published"]["n_routed_experts"]
    assert share["zero_compute_outputs"] == model["zero_expert_num"]
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_layers"],
            "d_model": model["hidden_size"],
            "d_ff": model["ffn_hidden_size"],
            "d_ff_expert": model["expert_ffn_hidden_size"],
            "n_experts": share["router_outputs"] - model["zero_expert_num"],
            "zero_experts": model["zero_expert_num"],
            "zero_expert_type": model["zero_expert_type"],
            "experts_held": model["n_routed_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["moe_topk"],
            "routed_scaling_factor": float(model["routed_scaling_factor"]),
            "n_head": model["num_attention_heads"],
            "q_lora_rank": model["q_lora_rank"],
            "kv_lora_rank": model["kv_lora_rank"],
            "qk_nope_head_dim": model["qk_nope_head_dim"],
            "qk_rope_head_dim": model["qk_rope_head_dim"],
            "v_head_dim": model["v_head_dim"],
            "mla_scale_q_lora": model["mla_scale_q_lora"],
            "mla_scale_kv_lora": model["mla_scale_kv_lora"],
            "rope_theta": float(model["rope_theta"]),
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `LongcatConfig`, as the engine builds it."""
    from ray_tpu.models import serving_family

    deploy = config["deployment"]
    _, _, config_cls = serving_family(deploy["preset"])
    return config_cls.preset(deploy["preset"], **program_sizes(config),
                             max_seq_len=deploy["max_seq_len"])


def reference_model(config: dict) -> dict:
    """What the reference reads: the file's `model`, which of the router's
    outputs are held experts and which zero-compute, and the dtype the rows
    are stated in (`stated.rows`; float32 where a test's file states none)."""
    return {**config["model"], **config["share"],
            "rows": config.get("stated", {}).get("rows", "float32")}


# -------------------------------------------------------------- arithmetic


def experts_cost_model(model: dict) -> dict:
    """This family's keys under the names `moe_experts_decode_cost` reads:
    an expert is three matrices [6144, 2048], 75.5 MB in bf16, and a row 6 x
    6,144 x 2,048 operations. A pair that chose a zero-compute expert is no
    row: it costs a multiply-add a lane under `moe_zero`, not here."""
    return {"hidden_size": model["hidden_size"],
            "moe_intermediate_size": model["expert_ffn_hidden_size"]}


def attention_sublayers(model: dict) -> int:
    return 2 * model["num_layers"]


def kv_bytes_per_token(model: dict) -> int:
    return (attention_sublayers(model)
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_stream", "one_piece", "float8_rows",
           "no_zero_term", "unscaled_latent", "r_a_sublayer_early")
QUERY_BLOCK = 128


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mla_row(u, p, model: dict, degrade):
    """u [T, d] (normed) -> the sublayer's output [T, d], the plain form; T
    a multiple of `QUERY_BLOCK` or shorter than it."""
    import jax
    import jax.numpy as jnp

    heads, d = model["num_attention_heads"], model["hidden_size"]
    n, shared = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, v, r_q = model["kv_lora_rank"], model["v_head_dim"], model["q_lora_rank"]
    eps, seq = model["rms_norm_eps"], u.shape[0]
    if degrade == "one_piece":
        u = _through_bfloat16(u)
    s_q = math.sqrt(d / r_q) if model["mla_scale_q_lora"] else 1.0
    s_kv = math.sqrt(d / r) if model["mla_scale_kv_lora"] else 1.0
    if degrade == "unscaled_latent":
        s_kv = 1.0
    c_q = s_q * _rms_norm(u @ p["wqa"], p["q_norm"]["scale"], eps)
    if degrade == "one_piece":
        c_q = _through_bfloat16(c_q)
    q = jnp.einsum("tr,rhk->thk", c_q, p["wqb"])            # [T, H, n + p]
    ckr = u @ p["wkva"]
    c = s_kv * _rms_norm(ckr[:, :r], p["kv_norm"]["scale"], eps)
    at = jnp.arange(seq)
    q_r = _rope(q[..., n:], at, model["rope_theta"])
    k_r = _rope(ckr[:, None, r:], at, model["rope_theta"])[:, 0]
    if degrade == "float8_rows":
        c, k_r = _through_float8(c), _through_float8(k_r)
    elif model["rows"] == "bfloat16":
        # what the cache holds, as the configuration states it (`stated`)
        c, k_r = _through_bfloat16(c), _through_bfloat16(k_r)
    kv = jnp.einsum("tr,rhk->thk", c, p["wkvb"])            # [T, H, n + v]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, qrb, first = args
        scores = (jnp.einsum("ihn,jhn->hij", qb, kv[..., :n])
                  + jnp.einsum("ihp,jp->hij", qrb, k_r)) \
            / math.sqrt(n + shared)
        seen = jnp.arange(seq)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhv->ihv", probs, kv[..., n:])

    blocks = seq // block
    o = jax.lax.map(attend, (q[..., :n].reshape(blocks, block, heads, n),
                             q_r.reshape(blocks, block, heads, shared),
                             jnp.arange(blocks) * block))
    o = o.reshape(seq, heads * v)
    if degrade == "one_piece":
        o = _through_bfloat16(o)
    return o @ p["wo"]


def _swiglu(h, p, degrade=None):
    import jax
    import jax.numpy as jnp

    if degrade == "one_piece":
        h = _through_bfloat16(h)
    a, b = jnp.split(h @ p["w_in"], 2, axis=-1)
    mid = jax.nn.silu(a) * b
    if degrade == "one_piece":
        mid = _through_bfloat16(mid)
    return mid @ p["w_out"]


def _expert_block(h, moe, experts, model: dict, degrade=None):
    """h [T, d] (normed) -> (the held experts' part of the routed sum plus
    the zero-compute experts' term, what the router chose [T, K]).
    `experts` as the replica holds them: each is widened to float32 as the
    loop reaches it."""
    import jax
    import jax.numpy as jnp

    top_k, first = model["moe_topk"], model["first_expert"]
    outputs, zero = model["router_outputs"], model["zero_compute_outputs"]
    held = experts["wg"].shape[0]
    assert moe["router"].shape[-1] == outputs
    assert held == model["n_routed_experts"]
    assert model["zero_expert_type"] == "identity"
    s = jax.nn.softmax(h @ moe["router"][0], axis=-1)
    _, chosen = jax.lax.top_k(s + moe["bias"][0], top_k)
    kept = (jnp.take_along_axis(s, chosen, axis=-1)
            * model["routed_scaling_factor"])       # not renormalised
    gates = jnp.sum(jax.nn.one_hot(chosen, outputs, dtype=h.dtype)
                    * kept[..., None], axis=-2)                # [T, 768]
    mine = jnp.moveaxis(gates[..., first:first + held], -1, 0)
    rows = _through_bfloat16(h) if degrade == "one_piece" else h

    def expert(acc, e):
        wg, wu, wd = (w.astype(jnp.float32) for w in e[:3])
        mid = jax.nn.silu(rows @ wg) * (rows @ wu)
        if degrade == "one_piece":
            mid = _through_bfloat16(mid)
        return acc + e[3][..., None] * (mid @ wd), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (experts["wg"], experts["wu"], experts["wd"], mine))
    if degrade == "no_zero_term":
        return routed, chosen
    zero_gate = jnp.sum(gates[..., outputs - zero:], axis=-1, keepdims=True)
    return routed + zero_gate * h, chosen


def reference_layer(x, p, model: dict, degrade=None):
    """x [R, T, d] float32 -> x after the double layer whose weights are `p`
    (`init_layer`'s tree): R sequences, each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    eps = model["rms_norm_eps"]
    experts = p["experts"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     {k: v for k, v in p.items() if k != "experts"})
    rows, seq, d = x.shape

    def stream(t):
        return _through_bfloat16(t) if degrade == "bfloat16_stream" else t

    def attend(t, i):
        m = jax.tree.map(lambda a: a[i], p["attn"])
        return stream(t + jax.lax.map(
            lambda row: _mla_row(row, m, model, degrade),
            _rms_norm(t, m["norm"]["scale"], eps)))

    with jax.default_matmul_precision("highest"):
        dense0, dense1 = (jax.tree.map(lambda a, i=i: a[i], p["dense"])
                          for i in (0, 1))
        a = attend(x, 0)
        h0 = _rms_norm(a, dense0["norm"]["scale"], eps)
        r = _expert_block(h0.reshape(rows * seq, d), p["moe"], experts,
                          model, degrade)[0].reshape(rows, seq, d)
        b = stream(a + _swiglu(h0, dense0, degrade))
        if degrade == "r_a_sublayer_early":
            b = b + r
        c = attend(b, 1)
        out = c + _swiglu(_rms_norm(c, dense1["norm"]["scale"], eps), dense1,
                          degrade)
        return stream(out if degrade == "r_a_sublayer_early" else out + r)


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, held vocabulary]: the final norm and the
    untied head."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["rms_norm_eps"])
        return x @ ends["lm_head"].astype(jnp.float32)


class Reference:
    """The reference walked a layer at a time over several sequences of one
    padded length: `layer_weights(l)` makes layer l's weights (the program's
    `init_layer` from the seed, or a test's own), which are dropped before
    the next layer's are made. `model` is `reference_model(config)`."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        self._layer = jax.jit(
            lambda x, p: reference_layer(x, p, model, degrade))
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
        for l in range(self.model["num_layers"]):
            p = self.layer_weights(l)
            x = self._layer(x, p)
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

    eos_id = 16383


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

    from families.longcat_server import BenchServer

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
    to the readers (which see the record, not the configuration), under the
    names Kanana's readers know: the rows at 64 heads (1,152 bytes and
    139,264 operations a position a sublayer, 8 sublayers) and the held
    experts. `moe_held_rows_pct` keeps its meaning, held pairs of all chosen
    pairs: the pairs that chose a zero-compute expert are in its
    denominator."""
    experts = experts_cost_model(model)
    return {"attention_layers": attention_sublayers(model),
            "routed_experts": model["n_routed_experts"],
            "mla_attend_per_position": mla_attend_cost(model, 1.0),
            "moe_experts_per_row": moe_experts_decode_cost(experts, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(experts, 0.0, 1.0)}


# What decides `correct`, in two steps as for Solar and Nemotron
# (`families/kimi.py` has the two steps' account, `families/kanana.py` why
# the served tokens alone cannot decide).
#
# 1. What was served is what the timed programs compute: the share of served
#    tokens that are not their row's maximum in the engine's own logits,
#    taken the way the window's requests went (`engine_logits`: Kanana's
#    route, rows alone), may not pass `SERVED_NOT_ENGINE_TOP_LIMIT` (Kimi's
#    limit, for Kimi's reason: a decode lane that rides a chunk step goes
#    through the chunk program's own compilation of the first lane).
# 2. Those logits are the reference's, by two numbers over the generated
#    positions, each position's the mean absolute difference of its logits:
#    the tenth percentile over the positions, the floor, may not pass
#    `ENGINE_LOGIT_FLOOR_ABS_LIMIT`, and the mean may not pass
#    `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The floor holds the precision (a
#    rounding below what the file states moves every position), the mean a
#    fault in a minority of the positions and the other mathematics
#    (`families/solar.py` has the argument).
#
#    The program's floor is 0.00045-0.00058 and its mean 0.00079-0.00090 in
#    every reading (the logits' spread is 1.57): what is left is the bf16
#    rounding of the queries that meet the rows and of attention's
#    probabilities, 8 sublayers of it. Every product's activation as one
#    bf16 piece has a floor of 0.0055-0.0056 (mean 0.0079-0.0082), a stream
#    through bfloat16 0.0093-0.0094 (0.0121-0.0130), rows through float8
#    0.0074-0.0095 (0.0115-0.0150): the floor's limit lies 3.1 times above
#    the program's widest floor and 3.1 times under the narrowest of the
#    three, and refuses each. The mean's limit lies 5.5 times above the
#    program's widest reading, whose tail is four routers choosing 12 of
#    768 (a pair that changes places between a zero-compute expert and an
#    absent one moves its token's stream by a tenth), and 48 times under
#    the other mathematics (the key-value latent without its factor
#    0.24-0.29, r a sublayer early 0.54, no zero term 0.76-0.78).
#
# The readings that set the limits are the configuration file's `limits`
# (rehearse/longcat_on_chip.py on the v5e at the published widths, and the
# cell's own runs; PERF.md section 6, PR 55).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.005
ENGINE_LOGIT_FLOOR_ABS_LIMIT = 0.0018


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
