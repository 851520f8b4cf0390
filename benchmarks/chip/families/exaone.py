"""The K-EXAONE family (`model_type: exaone_moe`): what the benchmark needs to
know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `LGAI-EXAONE/K-EXAONE-236B-A23B` as its config.json and the
   family's published attention describe them, in plain `jax.numpy` and
   float32 under `jax.default_matmul_precision("highest")`, no kernel, no
   cache, no ring, no chunks, a layer at a time. It imports nothing from
   `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's weights as the
   program lays them out, which is the one thing it takes from the program
   (`attn.{norm, wq [d, 64 x 128], wk, wv [d, 8 x 128], q_norm, k_norm
   [128], wo}`, and `dense.{norm, w_in [d, 2 x 18432] (gate and up side by
   side), w_out}` or `moe.{norm, router [d, 128], bias, shared.{w_in [d, 2 x
   2048], w_out}}` with `experts.{wg, wu [E', d, 2048], wd [E', 2048, d]}`).
   With d 6144, eps 1e-5:

       x += Attn_l(RMSNorm_a(x));  x += MLP_l(RMSNorm_m(x))      (pre-norm)
       Attn_l (64 query / 8 key-value heads of 128, head h reads key-value
         head h // 8):
         q = RMSNorm_q(u W_q), k = RMSNorm_k(u W_k) over a head's 128 lanes;
         v = u W_v
         sliding layer (`layer_types[l] == sliding_attention`): q, k rotated
           (theta 1e6, lane i with lane i + 64); position i attends j with
           i - 128 < j <= i
         global layer: no rotation; position i attends every j <= i
         softmax(q . k / sqrt(128)) . v;  W_o
       MLP_0 = SwiGLU 6144 -> 18432 -> 6144
       MLP_l, l >= 1 (128 router outputs, 8 a token, one shared expert):
         s = sigmoid(h W_r); the 8 largest of s + bias chosen;
         g = 2.5 s[chosen] / (sum + 1e-20)
         out = sum_k g_k SwiGLU^(e_k)(h) over the chosen experts THAT ARE
           HELD (`first_expert`..+E'): what the absent experts would add is
           left out, here as in the program; + SwiGLU^shared(h), whole
       final RMSNorm, untied head over the held rows of the vocabulary

   Attention over the whole sequence, a block of `QUERY_BLOCK` queries at a
   time against every key under a banded or a causal mask by the layer's
   kind, the rows of k (normed and, in a sliding layer, rotated) and v as
   the configuration states them (`stated.rows`: through bfloat16); the MLP
   a block of tokens at a time, the experts a loop over the held ones with
   the gate zero outside a token's 8, an expert's matrices widened to
   float32 as the loop reaches it: so that it fits at the published widths
   and 10k positions.

   Departures from the published description, each in the configuration
   file's `assumed` or `departures`: pre-norm; the norm a head before the
   rotation, and rotation in sliding layers only; a window of 128 counts
   the token itself; RoPE's pairing; gates from s without the bias; the
   shared expert ungated; the multi-token prediction module left out; seeded
   weights.

   `degrade` computes one part below what the configuration states or
   another mathematics (`bfloat16_stream`: the residual stream rounded to
   bfloat16 after every sublayer; `one_piece`: every product's activation
   rounded to bfloat16 first, what `lm.dot`'s second piece carries;
   `window_127`, `window_129`; `rotate_global`: the global layers rotated
   too; `unrotated_sliding`: no layer rotated; `no_head_norm`: q and k as
   projected; `gates_not_renormalised`: 2.5 s; `gates_unscaled`: without the
   2.5; `no_shared_expert`): what the family's limits have to refuse.
2. The arithmetic of the rooflines (Solar's `gqa_attend_cost` at this
   family's heads over the global layers, Kanana's `moe_experts_decode_cost`
   at LongCat's widths, and `swa_attend_cost`, a ring's live rows): the
   least a decode step must move or compute there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/exaone_server.py`), the tokenizer, and the check
   of what was served (`check_served`, as Solar's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
# the window's route through the engine's own programs, rows and rings alike
from families.granite import engine_logits
from families.kanana import (REQUEST_PATH, _rope,  # noqa: F401
                             _rows_and_positions, moe_experts_decode_cost,
                             request_body)
from families.kimi import CharTokenizer as _CharTokenizer
from families.solar import compare, gqa_attend_cost

SLIDING, GLOBAL = "sliding_attention", "full_attention"

# ----------------------------------------------------------- configuration


def layer_types(model: dict) -> list:
    """The kinds of the layers that are run: the published list's first
    `num_hidden_layers` (the file keeps the list whole)."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    assert set(kinds) <= {SLIDING, GLOBAL}, kinds
    return kinds


def program_sizes(config: dict) -> dict:
    """A configuration file (Hugging Face's key names under `model`, as in
    the source; the share of the deployment under `share`) in the names of
    the program's `ExaoneConfig`."""
    model, share = config["model"], config["share"]
    n, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    assert model["hidden_act"] == "silu" and model["scoring_func"] == "sigmoid"
    assert not model["tie_word_embeddings"]
    assert model["n_group"] == 1 and model["topk_group"] == 1
    assert model["num_nextn_predict_layers"] == 0, "no drafting module"
    assert model["mlp_layer_types"][:n] == (
        ["dense"] * dense + ["sparse"] * (n - dense))
    kinds = layer_types(model)
    assert model["sliding_windows"][:n] == [
        model["sliding_window"] if k == SLIDING else 0 for k in kinds]
    assert model["rope_parameters"]["rope_type"] == "default"
    return {"vocab_size": model["vocab_size"],
            "layer_types": tuple(kinds),
            "sliding_window": model["sliding_window"],
            "n_dense_layer": dense,
            "d_model": model["hidden_size"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "d_ff": model["intermediate_size"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_shared_experts": model["num_shared_experts"],
            "n_experts": share["router_outputs"],
            "experts_held": model["num_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["num_experts_per_tok"],
            "norm_topk_prob": model["norm_topk_prob"],
            "routed_scaling_factor": float(model["routed_scaling_factor"]),
            "rope_theta": float(model["rope_parameters"]["rope_theta"]),
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `ExaoneConfig`, as the engine builds it."""
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


def experts_cost_model(model: dict) -> dict:
    """This file's keys under the names `families/kanana.py`'s
    `moe_experts_decode_cost` reads: an expert is three matrices [6144,
    2048], 75.5 MB in bf16, and a row 6 x 6,144 x 2,048 operations
    (LongCat's very shape). The shared expert and the dense MLP are not the
    experts' (`moe_shared`, `mlp_dense`)."""
    return {"hidden_size": model["hidden_size"],
            "moe_intermediate_size": model["moe_intermediate_size"]}


def swa_attend_cost(model: dict, rows: float) -> dict:
    """The least one sliding layer needs to attend over `rows` live rows of
    its rings (summed over the slots; at most `sliding_window` a slot): each
    row's key and value by the 8 key-value heads read once, bf16 (4,096 B),
    and a multiply-add a lane for every query head's score and again for its
    weighted value. Solar's count of a position, at a ring's rows: no reader
    takes it yet (BENCHMARK.json has 128 of 128 entries)."""
    return gqa_attend_cost(model, rows)


def kv_bytes_per_token(model: dict) -> int:
    """What a token leaves behind for good: its key and value in the global
    layers (a sliding layer keeps a slot's last window, whatever the
    length)."""
    return (_layers(model, GLOBAL) * 2 * model["num_key_value_heads"]
            * model["head_dim"] * 2)


def state_bytes_per_slot(model: dict) -> int:
    """The sliding layers' rings: `sliding_window` rows of keys and of
    values by the key-value heads, bf16."""
    return (_layers(model, SLIDING) * 2 * model["num_key_value_heads"]
            * model["sliding_window"] * model["head_dim"] * 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_stream", "one_piece", "window_127", "window_129",
           "rotate_global", "unrotated_sliding", "no_head_norm",
           "gates_not_renormalised", "gates_unscaled", "no_shared_expert")
QUERY_BLOCK = 128
MLP_BLOCK = 1024            # tokens of one row the MLP takes at a time


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _piece(a, degrade):
    return _through_bfloat16(a) if degrade == "one_piece" else a


def _attention_row(u, p, model: dict, sliding: bool, degrade):
    """u [T, d] (normed) -> the sublayer's output [T, d], the plain form
    over the whole sequence; T a multiple of `QUERY_BLOCK` or shorter."""
    import jax
    import jax.numpy as jnp

    heads, groups = model["num_attention_heads"], model["num_key_value_heads"]
    lanes, seq, eps = model["head_dim"], u.shape[0], model["rms_norm_eps"]
    per = heads // groups
    # one short and one over, whatever the window (127, 129 as published)
    window = model["sliding_window"] + {"window_127": -1, "window_129": 1}.get(
        degrade, 0) if sliding else None
    u = _piece(u, degrade)
    q = (u @ p["wq"]).reshape(seq, heads, lanes)
    k = (u @ p["wk"]).reshape(seq, groups, lanes)
    v = (u @ p["wv"]).reshape(seq, groups, lanes)
    if degrade != "no_head_norm":
        q = _rms_norm(q, p["q_norm"]["scale"], eps)
        k = _rms_norm(k, p["k_norm"]["scale"], eps)
    rotated = {"rotate_global": True, "unrotated_sliding": False}.get(
        degrade, sliding)
    if rotated:
        theta = float(model["rope_parameters"]["rope_theta"])
        q = _rope(q, jnp.arange(seq), theta)
        k = _rope(k, jnp.arange(seq), theta)
    if model["rows"] == "bfloat16":
        # what the cache holds, as the configuration states it (`stated`)
        k, v = _through_bfloat16(k), _through_bfloat16(v)
    q = _piece(q.reshape(seq, groups, per, lanes), degrade)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, first = args
        scores = jnp.einsum("igrc,jgc->grij", qb, k) / math.sqrt(lanes)
        i = first + jnp.arange(block)[:, None]
        j = jnp.arange(seq)[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (j > i - window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grij,jgc->igrc", _piece(probs, degrade), v)

    blocks = seq // block
    o = jax.lax.map(attend, (q.reshape(blocks, block, groups, per, lanes),
                             jnp.arange(blocks) * block))
    return _piece(o.reshape(seq, heads * lanes), degrade) @ p["wo"]


def _swiglu(h, p, degrade=None):
    import jax
    import jax.numpy as jnp

    a, b = jnp.split(_piece(h, degrade) @ p["w_in"], 2, axis=-1)
    return _piece(jax.nn.silu(a) * b, degrade) @ p["w_out"]


def _expert_block(h, moe, experts, model: dict, degrade=None):
    """h [T, d] (normed) -> (the held experts' part of the routed sum plus
    the shared expert, what the router chose [T, K]). `experts` as the
    replica holds them: each is widened to float32 as the loop reaches
    it."""
    import jax
    import jax.numpy as jnp

    top_k, first = model["num_experts_per_tok"], model["first_expert"]
    held = experts["wg"].shape[0]
    n_experts = moe["router"].shape[1]
    assert n_experts == model["router_outputs"]
    assert held == model["num_experts"]
    s = jax.nn.sigmoid(h @ moe["router"])
    _, chosen = jax.lax.top_k(s + moe["bias"], top_k)
    kept = jnp.take_along_axis(s, chosen, axis=-1)
    if model["norm_topk_prob"] and degrade != "gates_not_renormalised":
        kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
    if degrade != "gates_unscaled":
        kept = kept * model["routed_scaling_factor"]
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
    if degrade == "no_shared_expert":
        return routed, chosen
    return routed + _swiglu(h, moe["shared"], degrade), chosen


def _by_blocks(fn, x, block: int):
    """fn, a function of each token alone, over x [T, d] a block of tokens
    at a time; the last block is padded with zero rows that are dropped (a
    block that divided T instead would be 128 tokens for most lengths, and
    every block widens the held experts' matrices again)."""
    import jax
    import jax.numpy as jnp

    seq, d = x.shape
    block = min(block, seq)
    padded = jnp.pad(x, ((0, -seq % block), (0, 0)))
    return jax.lax.map(fn, padded.reshape(-1, block, d)).reshape(
        padded.shape[0], -1)[:seq]


def reference_layer(x, p, model: dict, sliding: bool, degrade=None):
    """x [R, T, d] float32 -> x after the layer whose weights are `p`
    (`init_layer`'s tree: `attn`, and `dense` or `moe` + `experts`), a
    sliding or a global layer: R sequences, each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    eps = model["rms_norm_eps"]
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
        a = p["attn"]
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

    eos_id = 19199


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

    from families.exaone_server import BenchServer

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
            "routed_experts": model["num_experts"],
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
#    The program's floor is 0.00026-0.00049 and its mean 0.00028-0.0060 in
#    every reading (the logits' spread is 1.57). The floor is what is left
#    of the rows' own bf16 and the pieces' remainder, and it is tight; the
#    mean has a tail, seven routers choosing 8 of 128: a pair that changes
#    places between a held expert and an absent one moves its token's
#    stream by a fifth (a held expert's second matrix is drawn at 0.02 so
#    that the experts' kernel shows in the logits). A stream through
#    bfloat16 has a floor of 0.012 (mean 0.031-0.049) and every product's
#    activation as one bf16 piece 0.0077-0.0107 (0.023-0.049): the floor's
#    limit lies 4.1 times above the program's widest floor and 3.9 times
#    under the narrowest of the two, and refuses each; the mean's limit is
#    not set to hold them (it lies 5 times above the program's widest
#    reading, for the tail, and over some of their means). Every other
#    mathematics is refused by both: a global layer rotated reads a floor
#    of 0.022 (mean 0.060), a window of 127 or 129 0.069-0.071 (0.21-0.22),
#    gates not scaled 0.32 (0.50), the sliding layers un-rotated 0.62
#    (0.81), no shared expert 0.88 (0.99), gates not renormalised 1.18
#    (1.37), no norm a head 1.27 (1.34).
#
# The readings that set the limits are the configuration file's `limits`
# (rehearse/exaone_on_chip.py on the v5e at the published widths, and the
# cell's own runs; PERF.md section 6, PR 59).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.03
ENGINE_LOGIT_FLOOR_ABS_LIMIT = 0.002


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
