"""The Granite 4.0-H family (`model_type: granitemoehybrid`, dense): what the
benchmark needs to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `ibm-granite/granite-4.0-h-micro` as its config.json and
   the published Mamba-2 layer describe them, in plain `jax.numpy` and
   float32 under `jax.default_matmul_precision("highest")`, no cache, no
   chunks, a layer at a time. It imports nothing from `ray_tpu.models` or
   `ray_tpu.ops`; it reads a layer's weights as the program lays them out,
   which is the one thing it takes from the program (`ssm.{w_zx [d, 4096 +
   4352], w_dt [d, 64] (W_in's columns, in two arrays), w_out [4096, d],
   dt_bias, a_log, d [64], conv_w [4, 4352], conv_b, norm}`,
   `attn.{wq [d, 32 64], wk, wv [d, 8 64], wo}`,
   `mlp.{w_in [d, 2 8192], w_out}`, `mixer_norm`, `mlp_norm`). With d 2048,
   eps 1e-5:

       h0 = 12 E[token]
       h += 0.22 mixer(RMSNorm(h));  h += 0.22 mlp(RMSNorm(h))
       mlp(u) = W_out (silu(a) * b), [a, b] = W_in u
       attention: 32 query heads over 8 key-value heads of 64 (head j reads
         j // 4), no positions, scores times 0.015625, causal softmax, W_o
       Mamba-2: [z, xBC, dt] = W_in u; xBC = silu(conv1d_causal(xBC, 4));
         dt = softplus(dt + dt_bias); A = -exp(A_log);
         S_t = exp(dt A) S_{t-1} + dt x_t B_t^T;  y_t = S_t C_t + D x_t;
         y = RMSNorm_4096(y * silu(z)) * g;  W_out
       logits = RMSNorm(h) E^T / 8

   The Mamba-2 layer by the recurrence, a token at a time over the whole
   sequence from a zero state (never the chunked form the program's chunk
   step uses, nor its state's layout), attention over the whole sequence a
   block of queries at a time. `degrade` computes one part below what the
   configuration states (`bfloat16_state`: S rounded to bfloat16 after
   every token, as a bf16 state would hold it; `float8_rows`: keys and
   values through float8) or another mathematics (`sqrt_scale`: scores
   times 1/8, what attention without the model's multiplier would do):
   what the family's limits have to refuse.
2. The arithmetic of the rooflines (`ssm_update_cost`, `gqa_attend_cost`):
   the least a decode step must move or compute there, whatever implements
   it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/granite_server.py`), the tokenizer, and the
   check of what was served (`check_served`, as Brumby's and Kanana's).
"""

from __future__ import annotations

from families.brumby import _through_bfloat16
from families.gpt2 import CharTokenizer as _CharTokenizer
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             _through_float8, compare_served, request_body)

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `GraniteConfig`."""
    assert model["num_local_experts"] == 0, "the dense model is what is built"
    assert model["hidden_act"] == "silu" and not model["attention_bias"]
    assert model["position_embedding_type"] == "nope"
    assert model["tie_word_embeddings"] and model["mamba_conv_bias"]
    assert not model["mamba_proj_bias"]
    assert model["normalization_function"] == "rmsnorm"
    assert (model["mamba_expand"] * model["hidden_size"]
            == model["mamba_n_heads"] * model["mamba_d_head"])
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "layer_types": tuple(model["layer_types"]),
            "d_model": model["hidden_size"],
            "d_ff": model["shared_intermediate_size"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "ssm_heads": model["mamba_n_heads"],
            "ssm_head_dim": model["mamba_d_head"],
            "ssm_state": model["mamba_d_state"],
            "ssm_groups": model["mamba_n_groups"],
            "ssm_conv": model["mamba_d_conv"],
            "embedding_multiplier": float(model["embedding_multiplier"]),
            "residual_multiplier": model["residual_multiplier"],
            "attention_multiplier": model["attention_multiplier"],
            "logits_scaling": float(model["logits_scaling"]),
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `GraniteConfig`, as the engine builds it."""
    from ray_tpu.models import granite

    deploy = config["deployment"]
    return granite.GraniteConfig.preset(
        deploy["preset"], **program_sizes(config["model"]),
        max_seq_len=deploy["max_seq_len"])


# -------------------------------------------------------------- arithmetic


def _layers(model: dict, kind: str) -> int:
    return sum(t == kind for t in model["layer_types"])


def ssm_update_cost(model: dict, slots: float) -> dict:
    """The least one Mamba-2 layer's one-token update-and-read-out needs
    for `slots` slots: every head's S [64, 128] and the convolution's
    window [3, 4352] read once and written once, float32, and for each
    entry of S a multiplication by the decay, a multiply-add of the input's
    and B's entries and a multiply-add into the read-out. Bound by the
    bytes on a v5e (5 operations an entry against 8 bytes)."""
    entries = (model["mamba_n_heads"] * model["mamba_d_head"]
               * model["mamba_d_state"])
    window = (model["mamba_d_conv"] - 1) * (
        model["mamba_n_heads"] * model["mamba_d_head"]
        + 2 * model["mamba_n_groups"] * model["mamba_d_state"])
    return {"bytes": slots * (entries + window) * 4.0 * 2,
            "flops": slots * entries * 5.0}


def gqa_attend_cost(model: dict, positions: float) -> dict:
    """The least one attention layer needs to attend over `positions`
    cached positions (summed over the slots): each position's key and value
    by the 8 key-value heads read once, bf16, and a multiply-add a lane for
    every query head's score and again for its weighted value."""
    lanes = model["hidden_size"] // model["num_attention_heads"]
    return {"bytes": positions * 2 * model["num_key_value_heads"] * lanes
            * 2.0,
            "flops": positions * 2 * model["num_attention_heads"] * lanes
            * 2.0}


def kv_bytes_per_token(model: dict) -> int:
    lanes = model["hidden_size"] // model["num_attention_heads"]
    return (_layers(model, "attention") * 2 * model["num_key_value_heads"]
            * lanes * 2)


def state_bytes_per_slot(model: dict) -> int:
    return int(_layers(model, "mamba")
               * ssm_update_cost(model, 1.0)["bytes"] / 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_state", "float8_rows", "sqrt_scale")
QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _mamba(u, p, model: dict, degrade):
    """u [R, T, d] (normed) -> the mixer's output [R, T, d]."""
    import jax
    import jax.numpy as jnp

    heads, lanes = model["mamba_n_heads"], model["mamba_d_head"]
    n, taps = model["mamba_d_state"], model["mamba_d_conv"]
    inner = heads * lanes
    rows, seq = u.shape[0], u.shape[1]
    z, xbc = jnp.split(u @ p["w_zx"], [inner], axis=-1)
    dt = u @ p["w_dt"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][k] * padded[:, k:k + seq] for k in range(taps)))
    x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    x = x.reshape(rows, seq, heads, lanes)
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [R, T, H]
    a = -jnp.exp(p["a_log"])                                   # [H]

    def token(s, args):                                  # s [R, H, P, N]
        xt, bt, ct, dtt = args          # [R,H,P] [R,N] [R,N] [R,H]
        s = (jnp.exp(dtt * a)[:, :, None, None] * s
             + (dtt[:, :, None] * xt)[..., None] * bt[:, None, None, :])
        if degrade == "bfloat16_state":
            s = _through_bfloat16(s)
        return s, jnp.einsum("rhpn,rn->rhp", s, ct)

    _, y = jax.lax.scan(
        token, jnp.zeros((rows, heads, lanes, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["d"][:, None] * x            # [R,T,H,P]
    y = y.reshape(rows, seq, inner) * jax.nn.silu(z)
    return _rms_norm(y, p["norm"]["scale"], model["rms_norm_eps"]) \
        @ p["w_out"]


def _attention(u, p, model: dict, degrade):
    """u [R, T, d] (normed) -> the mixer's output [R, T, d]; T a multiple
    of `QUERY_BLOCK` or shorter than it."""
    import jax
    import jax.numpy as jnp

    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    lanes = model["hidden_size"] // heads
    rows, seq = u.shape[0], u.shape[1]
    scale = (1.0 / lanes ** 0.5 if degrade == "sqrt_scale"
             else model["attention_multiplier"])
    q = (u @ p["wq"]).reshape(rows, seq, kv, heads // kv, lanes)
    k = (u @ p["wk"]).reshape(rows, seq, kv, lanes)
    v = (u @ p["wv"]).reshape(rows, seq, kv, lanes)
    if degrade == "float8_rows":
        k, v = _through_float8(k), _through_float8(v)
    block = min(QUERY_BLOCK, seq)
    assert seq % block == 0, (seq, block)

    def attend(args):
        qb, first, kr, vr = args         # [block,G,R,p], scalar, [T,G,p] x2
        scores = jnp.einsum("igrp,jgp->grij", qb, kr) * scale
        seen = (jnp.arange(seq)[None, :]
                <= first + jnp.arange(block)[:, None])
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("grij,jgp->igrp", probs, vr)

    def row(args):
        qr, kr, vr = args
        blocks = seq // block
        out = jax.lax.map(
            lambda a: attend((a[0], a[1], kr, vr)),
            (qr.reshape(blocks, block, kv, heads // kv, lanes),
             jnp.arange(blocks) * block))
        return out.reshape(seq, heads * lanes)

    return jax.lax.map(row, (q, k, v)) @ p["wo"]


def reference_layer(x, p, model: dict, kind: str, degrade=None):
    """x [R, T, d] float32 -> x after the layer of `kind` whose weights are
    `p`: R sequences side by side, each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps, by = model["rms_norm_eps"], model["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, p["mixer_norm"]["scale"], eps)
        if kind == "mamba":
            x = x + by * _mamba(u, p["ssm"], model, degrade)
        else:
            x = x + by * _attention(u, p["attn"], model, degrade)
        u = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        a, b = jnp.split(u @ p["mlp"]["w_in"], 2, axis=-1)
        return x + by * ((jax.nn.silu(a) * b) @ p["mlp"]["w_out"])


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, vocab]: the final norm and the tied table."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["rms_norm_eps"])
        return x @ ends["wte"].astype(jnp.float32).T / model["logits_scaling"]


class Reference:
    """The reference walked a layer at a time over several sequences of one
    padded length: `layer_weights(l)` makes layer l's weights (the program's
    `init_layer` from the seed, or a test's own), which are dropped before
    the next layer's are made."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        self._layer = {kind: jax.jit(
            lambda x, p, kind=kind: reference_layer(x, p, model, kind,
                                                    degrade))
            for kind in ("mamba", "attention")}
        # `ends` an argument: closed over, the table would be a constant
        # of the compiled program
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
        x = self.ends["wte"][jnp.asarray(ids)].astype(jnp.float32) \
            * self.model["embedding_multiplier"]
        for l, kind in enumerate(self.model["layer_types"]):
            p = self.layer_weights(l)
            x = self._layer[kind](x, p)
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
    ids), with this vocabulary's end-of-text id (`<|end_of_text|>`, 100257
    in Granite 4.0's tokenizer: `assumed.tokenizer`)."""

    eos_id = 100257


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
    `BenchServer` in `OpenAIServer`'s place, as `families/brumby.py` does."""
    from ray_tpu.serve.api import deployment

    from families.granite_server import BenchServer

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
    to the readers (which see the record, not the configuration): a reader
    multiplies by the slots a decode step had live, or by the positions it
    attended over."""
    return {"ssm_layers": _layers(model, "mamba"),
            "ssm_update_per_slot": ssm_update_cost(model, 1.0),
            "gqa_layers": _layers(model, "attention"),
            "gqa_attend_per_position": gqa_attend_cost(model, 1.0)}


# What decides `correct`, in two steps as for Kanana and Brumby
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
#    `SERVED_NOT_ENGINE_TOP_LIMIT`. Here it reads 0 in every run: a decode
#    lane that rides a chunk step goes through the decode program's own
#    operations (the chunk program's first lane), so nothing rounds
#    elsewhere (Kanana's cell reads 0.6-2.5% and Brumby's 1.3-1.7% of a
#    limit of 0.06). A reference with its state through bfloat16 would
#    choose another token than the reference at 3.0-7.2% of positions, one
#    with the other scale at 3.3-5.0%: the limit lies under both.
# 2. Those logits, the timed programs' own, are the reference's: their mean
#    absolute difference at the generated positions (the logits' spread is
#    0.028) may not pass `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The program reads
#    7.9e-7 to 1.9e-6 over its seeds (what is left is the bf16 rounding of
#    keys, values and attention's weights; with the activations' rounding in
#    every product it read 3.1e-4, beside 2.5e-4 for a state through
#    bfloat16); the mildest degradation, keys and values through float8,
#    7.9e-6 to 8.0e-6; the state through bfloat16 2.5e-4 to 7.3e-4; the
#    other scale 3.7e-4 (PERF.md, PR 38, has every reading). The limit lies
#    between the first two, twice the one and half the other.
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 4e-6


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import granite

    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: granite.init_layer(key, l, cfg),
            granite.init_ends(key, cfg))


def stopped_engine(config: dict, seed: int):
    """An `LLMEngine` made as the replica's was (the seed's weights, the
    deployment, the compile cache's programs) with its loop stopped: its
    two step programs, its cache and its pool are the caller's to drive."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(**engine_options(config, seed))
    eng.shutdown()
    eng._thread.join()
    return eng


def engine_logits(eng, served: list) -> list:
    """For each served sequence the float32 logits [generated positions,
    vocab] of the engine's own two compiled programs, by the route a
    request of the window took (`families/brumby.py`'s, which drives the
    pool through `store_prefix`, `match_prefix` and `copy_into_slot` and so
    carries rows and state alike): as many sequences as half the slots or
    the pool's snapshots allow are live in the same steps."""
    from families.brumby import _engine_logits_together

    half = min(eng.max_batch // 2, eng.kv.num_snapshots)
    return [rows for k in range(0, len(served), half)
            for rows in _engine_logits_together(eng, served[k:k + half])]


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
    engine = engine_logits(stopped_engine(config, seed), served)
    gc.collect()                        # the engine's weights and cache
    t1 = time.time()
    layer_weights, ends = seeded_weights(config, seed)
    rows, at = _rows_and_positions(served)
    reference = Reference(config["model"], layer_weights, ends).logits(rows,
                                                                       at)
    return {**verdict(compare_served(served, engine, reference)),
            "replies": len(served),
            "seconds": {"engine": round(t1 - t0, 1),
                        "reference": round(time.time() - t1, 1)}}
