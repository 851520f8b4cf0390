"""The Solar Open2 family (`model_type: solar_open2`): what the benchmark
needs to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `upstage/Solar-Open2-250B` as its config.json and the
   public Kimi Delta Attention layer describe them, in plain `jax.numpy` and
   float32 under `jax.default_matmul_precision("highest")`, no cache, no
   chunks, a layer at a time and a sequence at a time. It imports nothing
   from `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's weights as the
   program lays them out, which is the one thing it takes from the program
   (`kda.{norm, w_qkv [d, 3 x 8192], conv_w [4, 3 x 8192], w_fgb [d, 128 +
   128 + 64 (+ 64 of padding)] (W_f1, W_g1 and W_b side by side), w_f2 [128,
   8192], dt_bias, a_log [64], w_g2, g_bias, o_norm [128], w_o}`,
   `gqa.{norm, wq [d, 64 x 128], wk, wv [d, 8 x 128], w_gate [d, 8192],
   wo}`, `moe.{norm, router [d, 320], bias, shared.{w_in, w_out}}` with
   `experts.{wg, wu [E', d, 1280], wd}`). With d 4096, eps 1e-5:

       x += mixer(RMSNorm(x));  x += experts(RMSNorm(x))
       KDA, u the normed input, 64 heads of 128: `families/kimi.py`'s, line
         for line, with b = 2 sigmoid(u W_b) (kda_allow_neg_eigval)
       softmax layer, 64 query / 8 key-value heads of 128, no rotation:
         q = u W_q; k, v = u W_k, u W_v; query head h reads key-value head
         h // 8; causal softmax(q . k / sqrt(128)) . v;
         y = (o * sigmoid(u W_gate)) W_o
       experts: s = sigmoid(h W_r) over the 320; the 8 largest of s + bias
         chosen; g = s[chosen] / (sum + 1e-20) x 1.0; the sum over the
         chosen experts THAT ARE HELD (`first_expert`..+E') + the shared
         SwiGLU: what the absent experts would add is left out, here as in
         the program
       final RMSNorm, untied head over the held rows of the vocabulary

   KDA by the recurrence, a token at a time over the whole sequence from a
   zero state, attention in the plain form with keys and values by head
   (rounded to bfloat16 where the configuration states that the cache holds
   them so, `stated.rows`: the one stated precision below float32, and the
   one the routers would otherwise turn into the whole distance), a
   block of `QUERY_BLOCK` queries at a time so that 25k positions fit (a
   block's scores are [64, 128, T] floats), the experts a loop over the held
   ones with the gate zero outside a token's eight, an expert's matrices
   widened to float32 as the loop reaches it. `degrade` computes one part
   below what the configuration states or another mathematics
   (`bfloat16_state`: S rounded to bfloat16 after every token;
   `bfloat16_scores`: attention's scores rounded to bfloat16 before the
   softmax; `no_gate`: the softmax layers' output gate left out;
   `b_in_0_1`: the write strength without its factor 2, which is Kimi's):
   what the family's limits have to refuse.
2. The arithmetic of the rooflines (`gqa_attend_cost` at this family's
   heads, and `families/kimi.py`'s `kda_update_cost` and Kanana's
   `moe_experts_decode_cost`, whose keys this configuration is given): the
   least a decode step must move or compute there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/solar_server.py`), the tokenizer, and the check
   of what was served (`check_served`, as Kimi's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
# the window's route through the engine's own programs, rows and state alike
from families.granite import engine_logits
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             compare_served, moe_experts_decode_cost,
                             request_body)
from families.kimi import CharTokenizer as _CharTokenizer
from families.kimi import kda_update_cost

# ----------------------------------------------------------- configuration


def program_sizes(config: dict) -> dict:
    """A configuration file (Hugging Face's key names under `model`, as in
    the source; the share of the deployment under `share`) in the names of
    the program's `KimiConfig`, which serves this family."""
    model, share = config["model"], config["share"]
    lin = model["linear_attn_config"]
    assert not model["use_rope"] and model["use_gqa_gate"]
    assert model["kda_allow_neg_eigval"] and not model["kda_use_full_proj"]
    assert model["first_k_dense_replace"] == 0
    assert not model["tie_word_embeddings"]
    assert all(0 <= l < model["num_hidden_layers"]
               for l in model["gqa_layers"])
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "mla_layers": (), "gqa_layers": tuple(model["gqa_layers"]),
            "n_dense_layer": model["first_k_dense_replace"],
            "d_model": model["hidden_size"],
            "d_ff": model["intermediate_size"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_experts": share["router_outputs"],
            "experts_held": model["n_routed_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["num_experts_per_tok"],
            "n_shared_experts": model["n_shared_experts"],
            "norm_topk_prob": model["norm_topk_prob"],
            "router_scoring": config["assumed_sizes"]["router_scoring"],
            "routed_scaling_factor": float(model["routed_scaling_factor"]),
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "gqa_head_dim": model["head_dim"],
            "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "kda_conv": lin["short_conv_kernel_size"],
            "kda_rank": config["assumed_sizes"]["kda_gate_rank"],
            "kda_neg_eigval": model["kda_allow_neg_eigval"],
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `KimiConfig`, as the engine builds it."""
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


def _kda_layers(model: dict) -> int:
    return model["num_hidden_layers"] - len(model["gqa_layers"])


def gqa_attend_cost(model: dict, positions: float) -> dict:
    """The least one softmax layer needs to attend over `positions` cached
    positions (summed over the slots): each position's key and value by the
    8 key-value heads read once, bf16 (4,096 B), and a multiply-add a lane
    for every query head's score and again for its weighted value."""
    lanes = model["head_dim"]
    return {"bytes": positions * 2 * model["num_key_value_heads"] * lanes
            * 2.0,
            "flops": positions * 2 * model["num_attention_heads"] * lanes
            * 2.0}


def experts_cost_model(model: dict) -> dict:
    """This file's keys under the names `families/kanana.py`'s
    `moe_experts_decode_cost` reads."""
    return {"hidden_size": model["hidden_size"],
            "moe_intermediate_size": model["moe_intermediate_size"]}


def kv_bytes_per_token(model: dict) -> int:
    return (len(model["gqa_layers"]) * 2 * model["num_key_value_heads"]
            * model["head_dim"] * 2)


def state_bytes_per_slot(model: dict) -> int:
    return int(_kda_layers(model) * kda_update_cost(model, 1.0)["bytes"] / 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_state", "bfloat16_scores", "no_gate", "b_in_0_1")
QUERY_BLOCK = 128


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _kda(u, p, model: dict, degrade):
    """u [T, d] (normed) -> the mixer's output [T, d]."""
    import jax
    import jax.numpy as jnp

    lin = model["linear_attn_config"]
    heads, lanes = lin["num_heads"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    seq = u.shape[0]
    qkv = u @ p["w_qkv"]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][k] * padded[k:k + seq]
                          for k in range(taps)))
    q, k, v = (t.reshape(seq, heads, lanes)
               for t in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(lanes)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    rank = p["w_f2"].shape[0]
    w_f1, w_g1, w_b = (p["w_fgb"][:, :rank], p["w_fgb"][:, rank:2 * rank],
                       p["w_fgb"][:, 2 * rank:2 * rank + heads])
    rate = jax.nn.softplus((u @ w_f1) @ p["w_f2"] + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                * rate.reshape(seq, heads, lanes))
    b = jax.nn.sigmoid(u @ w_b)                                   # [T, H]
    if degrade != "b_in_0_1":
        b = 2.0 * b

    def token(s, args):                                     # s [H, N, P]
        qt, kt, vt, at, bt = args
        s = at[..., None] * s
        seen = jnp.einsum("hnp,hn->hp", s, kt)
        s = s + kt[..., None] * (bt[..., None] * (vt - seen))[:, None, :]
        if degrade == "bfloat16_state":
            s = _through_bfloat16(s)
        return s, jnp.einsum("hnp,hn->hp", s, qt)

    _, o = jax.lax.scan(token, jnp.zeros((heads, lanes, lanes), jnp.float32),
                        (q, k, v, a, b))
    o = _rms_norm(o, p["o_norm"]["scale"], model["rms_norm_eps"])  # [T,H,P]
    gate = jax.nn.sigmoid((u @ w_g1) @ p["w_g2"] + p["g_bias"])
    return (o.reshape(seq, heads * lanes) * gate) @ p["w_o"]


def _gqa(u, p, model: dict, degrade):
    """u [T, d] (normed) -> the mixer's output [T, d], the plain form, no
    rotation; T a multiple of `QUERY_BLOCK` or shorter than it."""
    import jax
    import jax.numpy as jnp

    heads, groups = model["num_attention_heads"], model["num_key_value_heads"]
    lanes, seq = model["head_dim"], u.shape[0]
    per = heads // groups
    q = (u @ p["wq"]).reshape(seq, groups, per, lanes)
    k = (u @ p["wk"]).reshape(seq, groups, lanes)
    v = (u @ p["wv"]).reshape(seq, groups, lanes)
    if model["rows"] == "bfloat16":
        # what the cache holds, as the configuration states it (`stated`)
        k, v = _through_bfloat16(k), _through_bfloat16(v)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, first = args
        scores = jnp.einsum("igrc,jgc->grij", qb, k) / math.sqrt(lanes)
        if degrade == "bfloat16_scores":
            scores = _through_bfloat16(scores)
        seen = jnp.arange(seq)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grij,jgc->igrc", probs, v)

    blocks = seq // block
    o = jax.lax.map(attend, (q.reshape(blocks, block, groups, per, lanes),
                             jnp.arange(blocks) * block))
    o = o.reshape(seq, heads * lanes)
    if degrade != "no_gate":
        o = o * jax.nn.sigmoid(u @ p["w_gate"])
    return o @ p["wo"]


def _swiglu(h, p):
    import jax
    import jax.numpy as jnp

    a, b = jnp.split(h @ p["w_in"], 2, axis=-1)
    return (jax.nn.silu(a) * b) @ p["w_out"]


def _expert_block(h, moe, experts, model: dict):
    """h [T, d] (normed) -> (the held experts' part of the routed sum plus
    the shared expert, what the router chose [T, K]). `experts` as the
    replica holds them: each is widened to float32 as the loop reaches it."""
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
    if model["norm_topk_prob"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    kept = kept * model["routed_scaling_factor"]
    gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=h.dtype)
                    * kept[..., None], axis=-2)                    # [T, E]
    mine = jnp.moveaxis(gates[..., first:first + held], -1, 0)

    def expert(acc, e):
        wg, wu, wd = (w.astype(jnp.float32) for w in e[:3])
        y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
        return acc + e[3][..., None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (experts["wg"], experts["wu"], experts["wd"], mine))
    return routed + _swiglu(h, moe["shared"]), chosen


def _reference_row(x, p, model: dict, degrade):
    """One sequence x [T, d] through the layer whose weights are `p`."""
    eps = model["rms_norm_eps"]
    kind = "kda" if "kda" in p else "gqa"
    m = p[kind]
    u = _rms_norm(x, m["norm"]["scale"], eps)
    x = x + (_kda if kind == "kda" else _gqa)(u, m, model, degrade)
    m = p["moe"]
    return x + _expert_block(_rms_norm(x, m["norm"]["scale"], eps), m,
                             p["experts"], model)[0]


def reference_layer(x, p, model: dict, degrade=None):
    """x [R, T, d] float32 -> x after the layer whose weights are `p` (its
    mixer by `kda` or `gqa`, its MLP by `moe` + `experts`): R sequences, each
    its own and each computed alone, so that what a layer holds beside its
    weights is one sequence's (at 25k positions q, k, v and the decay of a
    KDA layer are 0.84 GB each)."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = {**jax.tree.map(lambda a: a.astype(jnp.float32),
                        {k: v for k, v in p.items() if k != "experts"}),
         "experts": p["experts"]}
    with jax.default_matmul_precision("highest"):
        return jax.lax.map(lambda row: _reference_row(row, p, model, degrade),
                           x)


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
        for l in range(self.model["num_hidden_layers"]):
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

    eos_id = 24575


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
    `BenchServer` in `OpenAIServer`'s place, as `families/kimi.py` does."""
    from ray_tpu.serve.api import deployment

    from families.solar_server import BenchServer

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
    names granite's readers know for the softmax layers, Kanana's for the
    held experts, and Kimi's for the delta rule."""
    experts = experts_cost_model(model)
    return {"gqa_layers": len(model["gqa_layers"]),
            "gqa_attend_per_position": gqa_attend_cost(model, 1.0),
            "routed_experts": model["n_routed_experts"],
            "moe_experts_per_row": moe_experts_decode_cost(experts, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(experts, 0.0, 1.0),
            "kda_layers": _kda_layers(model),
            "kda_update_per_slot": kda_update_cost(model, 1.0)}


# What decides `correct`, in two steps as for Kimi (`families/kimi.py` has
# the two steps' account, `families/kanana.py` why the served tokens alone
# cannot decide).
#
# 1. What was served is what the timed programs compute: the share of served
#    tokens that are not their row's maximum in the engine's own logits,
#    taken the way the window's requests went (`engine_logits`), may not
#    pass `SERVED_NOT_ENGINE_TOP_LIMIT`. The cell reads 0 of ~1,400 tokens a
#    run; another slot's, seed's or model's tokens read 100%. Kimi's limit,
#    for Kimi's reason (a decode lane that rides a chunk step goes through
#    the chunk program's own compilation of the first lane).
# 2. Those logits are the reference's, by two numbers over the generated
#    positions, each position's the mean absolute difference of its logits
#    (their spread is 1.28): the tenth percentile over the positions, the
#    floor, may not pass `ENGINE_LOGIT_FLOOR_ABS_LIMIT`, and the mean may not
#    pass `ENGINE_LOGIT_MEAN_ABS_LIMIT`.
#    The reference holds the rows of k and v as the configuration states
#    them, through bfloat16 (`stated.rows`): the softmax layer is layer 0,
#    and against float32 rows the program read 0.0040-0.0138 in the mean,
#    which was the keys' rounding (a score moves by ~0.002 a position)
#    turned by four routers of 320 outputs into another expert for some
#    token, a number that lower precision passed (REVIEW, PR 49; the
#    reference with float32 rows reads the same 0.0040-0.0138 from the
#    stated one). Against the stated rows the program's floor is
#    0.000104-0.000119 in every reading (twenty-three), and its mean that
#    floor in most and 0.0007-0.0026 in eight of thirty: where a router still
#    chooses the other of two experts a hair apart, that token's state
#    carries the difference on through the rest of its reply, a quarter of
#    what a check reads.
#    The floor holds the precision, because a rounding moves every position:
#    the reference with S through bfloat16 after every token has a floor of
#    0.0147-0.0154 (mean 0.0168-0.0186), with its scores through bfloat16
#    0.0053-0.0068 (mean 0.0120-0.0181), at contexts of 16,384 and of
#    24,576 and on the cell's own replies alike; q and the probabilities as
#    one bf16 piece each (granite's form) have a floor of 0.0020-0.0027
#    (mean 0.0053-0.0095), scores through bfloat16 by another road: the
#    second piece is what the stated float32 q costs in the plain form (13%
#    of the cell's rate) and the floor's limit holds it. That limit lies
#    6.7 times above the program's widest floor and 6.6 times under the
#    narrowest of the two roundings'.
#    The mean holds what the floor cannot see, a fault in a minority of the
#    positions (a chunk's boundary, a slot's last block), and does not hold
#    the precision: both roundings pass it. Its limit lies 11.7 times above
#    the program's widest reading, whose tail is the routers', and 16 times
#    under the other mathematics (without the gate 0.60-0.64, with b in
#    (0, 1) 0.49-0.51; their floors 0.58-0.62 and 0.48-0.49).
#
# Readings on the v5e at the published widths: rehearse/solar_on_chip.py
# (seeds 1-3 at both ends of the cell's contexts, and runs of the cell's own
# replies with each rounding put through the same comparison) and the cell's
# own runs (PERF.md section 6, PR 49); the configuration file's `limits` has
# each.
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.03
ENGINE_LOGIT_FLOOR_ABS_LIMIT = 0.0008


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


def compare(served: list, engine: list, reference: list) -> dict:
    """`compare_served`'s readings and, beside its mean, the floor: the
    tenth percentile over the generated positions of a position's mean
    absolute logit difference."""
    import numpy as np

    readings = compare_served(served, engine, reference)
    if "error" in readings:
        return readings
    apart = np.concatenate([np.abs(p - r).mean(axis=-1)
                            for p, r in zip(engine, reference)])
    return {**readings,
            "engine_logit_floor_abs": float(np.quantile(apart, 0.1))}


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
