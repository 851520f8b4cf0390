"""The Kimi Linear family (`model_type: kimi_linear`): what the benchmark
needs to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `moonshotai/Kimi-Linear-48B-A3B-Instruct` as its
   config.json and the public Kimi Delta Attention layer describe them, in
   plain `jax.numpy` and float32 under
   `jax.default_matmul_precision("highest")`, no cache, no chunks, a layer
   at a time. It imports nothing from `ray_tpu.models` or `ray_tpu.ops`; it
   reads a layer's weights as the program lays them out, which is the one
   thing it takes from the program (`kda.{norm, w_qkv [d, 3 x 4096],
   conv_w [4, 3 x 4096], w_fgb [d, 128 + 128 + 32 (+ 96 of padding)] (W_f1,
   W_g1 and W_b side by side), w_f2 [128, 4096], dt_bias, a_log [32], w_g2,
   g_bias, o_norm [128], w_o}`, `mla.{norm, wq [d, 32 x 192], wkva [d,
   576], kv_norm, w_uk [32, 128, 512], w_uv [32, 512, 128] (W_kvb's two
   halves by head), wo}`, `dense.{norm, w_in [d, 2 x 9216], w_out}` or `moe.{norm,
   router [d, 256], bias, shared.{w_in, w_out}}` with `experts.{wg, wu [E',
   d, 1024], wd}`). With d 2304, eps 1e-5:

       x += mixer(RMSNorm(x));  x += mlp(RMSNorm(x))
       KDA, u the normed input, 32 heads of 128:
         q, k, v = silu(conv1d_causal_4(u W_q | W_k | W_v)); q, k of length
         1 a head; q / sqrt(128)
         a = exp(-exp(A_log) softplus((u W_f1) W_f2 + dt_bias)), a channel
         b = sigmoid(u W_b), a head
         S~ = Diag(a_t) S_{t-1};  S_t = S~ + b_t k_t (v_t - S~^T k_t)^T
         o_t = S_t^T q_t;  y = (RMSNorm_128(o) sigmoid((u W_g1) W_g2 + c)) W_o
       MLA without rotation, plain form: q = u W_q -> [32, 128 + 64];
         [c, k_r] = u W_kva; c = RMSNorm(c); [k_nope, val] = c W_kvb;
         causal softmax((q_nope . k_nope + q_r . k_r) / sqrt(192)) . val; W_o
       experts: s = sigmoid(h W_g) over the 256; the 8 largest of s + bias
         chosen; g = s[chosen] / (sum + 1e-20) * 2.446; the sum over the
         chosen experts THAT ARE HELD (`first_expert`..+E') + the shared
         SwiGLU: what the absent experts would add is left out, here as in
         the program
       final RMSNorm, untied head over the held rows of the vocabulary

   KDA by the recurrence, a token at a time over the whole sequence from a
   zero state (never the chunked form the program's chunk step uses, nor
   its kernel's layout), MLA in its plain form (keys and values by head,
   never the latent products the program computes) a block of queries at a
   time, the experts a loop over the held ones with the gate zero outside a
   token's eight. `degrade` computes one part below what the configuration
   states or another mathematics (`bfloat16_state`: S rounded to bfloat16
   after every token; `scalar_decay`: the mean of a head's 128 decays in
   place of the vector, which is a gated delta net and not KDA; `no_delta`:
   `S~^T k` left out, which is gated linear attention; `float8_rows`: c and
   k_r through float8): what the family's limits have to refuse.
2. The arithmetic of the rooflines (`kda_update_cost`, and Kanana's
   `mla_attend_cost` and `moe_experts_decode_cost`, whose keys this
   configuration shares): the least a decode step must move or compute
   there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/kimi_server.py`), the tokenizer, and the check of
   what was served (`check_served`, as Kanana's, Brumby's and Granite's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
from families.gpt2 import CharTokenizer as _CharTokenizer
# the window's route through the engine's own programs, rows and state alike
from families.granite import engine_logits
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             _through_float8, compare_served,
                             mla_attend_cost, moe_experts_decode_cost,
                             request_body)

# ----------------------------------------------------------- configuration


def program_sizes(config: dict) -> dict:
    """A configuration file (Hugging Face's key names under `model`, as in
    the source; the share of the deployment under `share`) in the names of
    the program's `KimiConfig`."""
    model, share = config["model"], config["share"]
    lin = model["linear_attn_config"]
    layers = model["num_hidden_layers"]
    assert sorted(lin["full_attn_layers"] + lin["kda_layers"]) == list(
        range(1, layers + 1))
    assert model["hidden_act"] == "silu" and model["mla_use_nope"]
    assert model["q_lora_rank"] is None and model["moe_layer_freq"] == 1
    assert model["moe_router_activation_func"] == "sigmoid"
    assert model["num_expert_group"] == model["topk_group"] == 1
    assert not model["tie_word_embeddings"]
    assert model["num_nextn_predict_layers"] == 0
    return {"vocab_size": model["vocab_size"], "n_layer": layers,
            "mla_layers": tuple(lin["full_attn_layers"]),
            "n_dense_layer": model["first_k_dense_replace"],
            "d_model": model["hidden_size"],
            "d_ff": model["intermediate_size"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_experts": share["router_outputs"],
            "experts_held": model["num_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["num_experts_per_token"],
            "n_shared_experts": model["num_shared_experts"],
            "norm_topk_prob": model["moe_renormalize"],
            "router_scoring": model["moe_router_activation_func"],
            "routed_scaling_factor": model["routed_scaling_factor"],
            "n_head": model["num_attention_heads"],
            "kv_lora_rank": model["kv_lora_rank"],
            "qk_nope_head_dim": model["qk_nope_head_dim"],
            "qk_rope_head_dim": model["qk_rope_head_dim"],
            "v_head_dim": model["v_head_dim"],
            "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
            "kda_conv": lin["short_conv_kernel_size"],
            "kda_rank": config["assumed_sizes"]["kda_gate_rank"],
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `KimiConfig`, as the engine builds it."""
    from ray_tpu.models import kimi

    deploy = config["deployment"]
    return kimi.KimiConfig.preset(
        deploy["preset"], **program_sizes(config),
        max_seq_len=deploy["max_seq_len"])


def reference_model(config: dict) -> dict:
    """What the reference reads: the file's `model` and which of the
    router's experts are held."""
    return {**config["model"], **config["share"]}


# -------------------------------------------------------------- arithmetic


def _kda_layers(model: dict) -> int:
    return len(model["linear_attn_config"]["kda_layers"])


def _mla_layers(model: dict) -> int:
    return len(model["linear_attn_config"]["full_attn_layers"])


def kda_update_cost(model: dict, slots: float) -> dict:
    """The least one KDA layer's one-token update-and-read-out needs for
    `slots` slots: every head's S [128, 128] and the convolutions' window
    [3, 3 x 4096] read once and written once, float32, and for each entry of
    S the decay, a multiply-add into each of the two read-outs and a
    multiply-add of the correction. Bound by the bytes on a v5e (7
    operations an entry against 8 bytes)."""
    lin = model["linear_attn_config"]
    entries = lin["num_heads"] * lin["head_dim"] ** 2
    window = (lin["short_conv_kernel_size"] - 1) * 3 * (
        lin["num_heads"] * lin["head_dim"])
    return {"bytes": slots * (entries + window) * 4.0 * 2,
            "flops": slots * entries * 7.0}


def kv_bytes_per_token(model: dict) -> int:
    return _mla_layers(model) * (model["kv_lora_rank"]
                                 + model["qk_rope_head_dim"]) * 2


def state_bytes_per_slot(model: dict) -> int:
    return int(_kda_layers(model) * kda_update_cost(model, 1.0)["bytes"] / 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_state", "scalar_decay", "no_delta", "float8_rows")
QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _kda(u, p, model: dict, degrade):
    """u [R, T, d] (normed) -> the mixer's output [R, T, d]."""
    import jax
    import jax.numpy as jnp

    lin = model["linear_attn_config"]
    heads, lanes = lin["num_heads"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    rows, seq = u.shape[0], u.shape[1]
    qkv = u @ p["w_qkv"]
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(p["conv_w"][k] * padded[:, k:k + seq]
                          for k in range(taps)))
    q, k, v = (t.reshape(rows, seq, heads, lanes)
               for t in jnp.split(qkv, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) \
        / math.sqrt(lanes)
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    rank = p["w_f2"].shape[0]
    w_f1, w_g1, w_b = (p["w_fgb"][:, :rank], p["w_fgb"][:, rank:2 * rank],
                       p["w_fgb"][:, 2 * rank:2 * rank + heads])
    rate = jax.nn.softplus((u @ w_f1) @ p["w_f2"] + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                * rate.reshape(rows, seq, heads, lanes))
    if degrade == "scalar_decay":
        a = jnp.broadcast_to(jnp.mean(a, axis=-1, keepdims=True), a.shape)
    b = jax.nn.sigmoid(u @ w_b)                                # [R, T, H]

    def token(s, args):                                  # s [R, H, N, P]
        qt, kt, vt, at, bt = args
        s = at[..., None] * s
        seen = jnp.einsum("rhnp,rhn->rhp", s, kt)
        if degrade == "no_delta":
            seen = jnp.zeros_like(seen)
        s = s + kt[..., None] * (bt[..., None] * (vt - seen))[:, :, None, :]
        if degrade == "bfloat16_state":
            s = _through_bfloat16(s)
        return s, jnp.einsum("rhnp,rhn->rhp", s, qt)

    _, o = jax.lax.scan(
        token, jnp.zeros((rows, heads, lanes, lanes), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, a, b)))
    o = _rms_norm(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"],
                  model["rms_norm_eps"])                       # [R,T,H,P]
    gate = jax.nn.sigmoid((u @ w_g1) @ p["w_g2"] + p["g_bias"])
    return (o.reshape(rows, seq, heads * lanes) * gate) @ p["w_o"]


def _mla(u, p, model: dict, degrade):
    """u [R, T, d] (normed) -> the mixer's output [R, T, d], the plain
    form, no rotation; T a multiple of `QUERY_BLOCK` or shorter than it."""
    import jax
    import jax.numpy as jnp

    heads = model["num_attention_heads"]
    n, shared = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, v = model["kv_lora_rank"], model["v_head_dim"]
    rows, seq = u.shape[0], u.shape[1]
    q = (u @ p["wq"]).reshape(rows, seq, heads, n + shared)
    ckr = u @ p["wkva"]
    c = _rms_norm(ckr[..., :r], p["kv_norm"]["scale"], model["rms_norm_eps"])
    k_r = ckr[..., r:]
    if degrade == "float8_rows":
        c, k_r = _through_float8(c), _through_float8(k_r)
    kv = jnp.concatenate([jnp.einsum("btr,hnr->bthn", c, p["w_uk"]),
                          jnp.einsum("btr,hrv->bthv", c, p["w_uv"])],
                         axis=-1)                            # [R,T,H,n+v]
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, first, keys, shared_key, values = args
        scores = (jnp.einsum("ihn,jhn->hij", qb[..., :n], keys)
                  + jnp.einsum("ihp,jp->hij", qb[..., n:], shared_key)) \
            / math.sqrt(n + shared)
        seen = (jnp.arange(seq)[None, :]
                <= first + jnp.arange(block)[:, None])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhv->ihv", probs, values)

    def row(args):
        qr, kvr, krr = args
        blocks = seq // block
        out = jax.lax.map(
            lambda a: attend((a[0], a[1], kvr[..., :n], krr, kvr[..., n:])),
            (qr.reshape(blocks, block, heads, n + shared),
             jnp.arange(blocks) * block))
        return out.reshape(seq, heads * v)

    return jax.lax.map(row, (q, kv, k_r)) @ p["wo"]


def _swiglu(h, p):
    import jax
    import jax.numpy as jnp

    a, b = jnp.split(h @ p["w_in"], 2, axis=-1)
    return (jax.nn.silu(a) * b) @ p["w_out"]


def _expert_block(h, moe, experts, model: dict):
    """h [R, T, d] (normed) -> (the held experts' part of the routed sum
    plus the shared expert, what the router chose [R, T, K])."""
    import jax
    import jax.numpy as jnp

    top_k, first = model["num_experts_per_token"], model["first_expert"]
    held = experts["wg"].shape[0]
    n_experts = moe["router"].shape[1]
    assert n_experts == model["router_outputs"]
    assert held == model["num_experts"]
    s = jax.nn.sigmoid(h @ moe["router"])
    _, chosen = jax.lax.top_k(s + moe["bias"], top_k)
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    if model["moe_renormalize"]:
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    kept = kept * model["routed_scaling_factor"]
    gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=h.dtype)
                    * kept[..., None], axis=-2)                # [R, T, E]
    mine = jnp.moveaxis(gates[..., first:first + held], -1, 0)

    def expert(acc, e):
        wg, wu, wd, gate = e
        y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
        return acc + gate[..., None] * y, None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(h),
        (experts["wg"], experts["wu"], experts["wd"], mine))
    return routed + _swiglu(h, moe["shared"]), chosen


def reference_layer(x, p, model: dict, degrade=None):
    """x [R, T, d] float32 -> x after the layer whose weights are `p` (its
    mixer by `kda` or `mla`, its MLP by `dense` or `moe` + `experts`): R
    sequences side by side, each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = model["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        if "kda" in p:
            m = p["kda"]
            x = x + _kda(_rms_norm(x, m["norm"]["scale"], eps), m, model,
                         degrade)
        else:
            m = p["mla"]
            x = x + _mla(_rms_norm(x, m["norm"]["scale"], eps), m, model,
                         degrade)
        if "dense" in p:
            m = p["dense"]
            return x + _swiglu(_rms_norm(x, m["norm"]["scale"], eps), m)
        m = p["moe"]
        return x + _expert_block(_rms_norm(x, m["norm"]["scale"], eps), m,
                                 p["experts"], model)[0]


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
    """`families/gpt2.py`'s one character a token id (it reaches 196,608
    ids), with an end-of-text id inside the held slice of the vocabulary
    (`assumed.tokenizer`)."""

    eos_id = 40959


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
    `BenchServer` in `OpenAIServer`'s place, as `families/granite.py` does."""
    from ray_tpu.serve.api import deployment

    from families.kimi_server import BenchServer

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
    names Kanana's readers know for the MLA layers and the held experts, and
    the delta rule's own."""
    return {"attention_layers": _mla_layers(model),
            "routed_experts": model["num_experts"],
            "mla_attend_per_position": mla_attend_cost(model, 1.0),
            "moe_experts_per_row": moe_experts_decode_cost(model, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(model, 0.0, 1.0),
            "kda_layers": _kda_layers(model),
            "kda_update_per_slot": kda_update_cost(model, 1.0)}


# What decides `correct`, in two steps as for Kanana, Brumby and Granite
# (`families/kanana.py` says why the served tokens alone cannot: with seeded
# weights the largest logit changes on rounding).
#
# 1. What was served is what the timed programs compute. With the chip
#    free, an engine made as the replica's was takes the sampled replies the
#    way the window's requests went (`engine_logits`): each prompt's whole
#    blocks prefilled in chunks in one slot from a zeroed state, the state
#    and the rows there pooled between two chunk steps, found again and
#    copied into another slot (the snapshot and its row blocks), the rest of
#    the prompt as a chunk, and the served tokens decoded one step each, the
#    sampled replies live in their slots at once. The share of served tokens
#    that are not their row's maximum may not pass
#    `SERVED_NOT_ENGINE_TOP_LIMIT`. The cell reads 0-0.08% (a decode lane
#    that rides a chunk step goes through the decode program's own
#    operations, the chunk program's first lane, compiled a second time: 3
#    near-ties of 3,618 tokens fell the other way). A reference with its
#    state through bfloat16 would choose another token than the reference
#    at 7.2-10.4% of positions, one with a scalar decay or without the
#    delta at 99%: the limit lies 18 times above the cell's widest reading
#    and 5 times under the mildest of the three.
# 2. Those logits, the timed programs' own, are the reference's: their mean
#    absolute difference at the generated positions (the logits' spread is
#    0.96) may not pass `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The program reads
#    4.1e-4 to 2.55e-3 over its seeds (every product's activation goes as
#    two bf16 pieces, the experts' rows too; what is left is the bf16
#    rounding of the latent rows, the absorbed queries and attention's
#    weights in the two MLA layers); the reference with S through bfloat16
#    after every token 2.69e-2 to 2.88e-2, with the mean of a head's decays
#    in place of the vector 0.81-0.82, without `S~^T k` 0.73-0.77: the limit
#    lies 3.1 times above the program's widest reading (of twenty) and 3.4
#    times under the state's through bfloat16, and refuses all three. Rows
#    through float8 read 1.66e-3 to 2.03e-3, inside the program's own range
#    (two layers of nine hold rows, and their rounding to bf16 is most of
#    what the program reads): no limit can tell them apart, and ISSUE 40 asks
#    for the other three.
#
# Readings on the v5e at the published widths: rehearse/kimi_on_chip.py
# (seeds 1-3) and the cell's own runs (PERF.md, PR 40).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 8e-3


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import kimi

    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: kimi.init_layer(key, l, cfg),
            kimi.init_ends(key, cfg))


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
    reference = Reference(reference_model(config), layer_weights,
                          ends).logits(rows, at)
    return {**verdict(compare_served(served, engine, reference)),
            "replies": len(served),
            "seconds": {"engine_build": round(t_built - t0, 1),
                        "engine": round(t1 - t_built, 1),
                        "reference": round(time.time() - t1, 1)}}
