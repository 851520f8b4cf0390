"""The Kanana-2 family (`model_type: deepseek_v3`): what the benchmark needs
to know about one model family.

1. The plain reference (`reference_layer`, `reference_logits`): the layer of
   `kakaocorp/kanana-2-30b-a3b-instruct-2601` as published, in plain
   `jax.numpy` and float32 under `jax.default_matmul_precision("highest")`,
   one sequence, no cache, no batching, a layer at a time (a layer's float32
   weights are 2.6 GB at the published widths; the caller hands each layer's
   weights in and lets them go). It imports nothing from `ray_tpu.models`;
   it reads a layer's weights as the program lays them out, which is the
   one thing it takes from the program (`attn.{wq [d,H,n+p], wkva [d,r+p],
   kv_norm, wkvb [r,H,n+v], wo [H v,d]}`, `mlp` or `moe` + `shared`). With
   d 2048, H 32, n 128, p 64, v 128, r 512, eps 1e-6, theta 1e6:

       h = RMSNorm(x)
       q = h W_q -> [H, n + p];  [c, k_r] = h W_kva;  c = RMSNorm_kv(c)
       RoPE on q's last p lanes (each head) and on k_r (one key, all heads)
       [k_nope, val] = c W_kvb -> [H, n + v];  k = [k_nope ; k_r]
       causal softmax(q . k / sqrt(n + p)) . val;  x += concat(o) W_o
       layer 0:   x += SwiGLU_6144(RMSNorm(x))
       the others, h = RMSNorm(x):  s = sigmoid(h W_g); the 6 largest of
       s + b are chosen; g = s[chosen] / (sum + 1e-20) * 2.448
       x += sum_k g_k SwiGLU_768^(e_k)(h) + SwiGLU_1536^shared(h)
       final RMSNorm, untied head

   The plain form of attention (keys and values by head, never the latent
   products the program computes), the experts a loop over all 128 with
   the gate zero outside a token's six. RoPE pairs lane i with lane
   i + p/2, as the program does (`assumed` in the configuration file).
   `degrade` computes one part below what the configuration states
   (`float8_experts`: the routed experts' weights rounded to float8 e4m3's
   three bits of mantissa; `float8_cache`: c and k_r, what the cache holds,
   rounded so): what
   the family's two limits have to refuse.
2. The arithmetic of the two rooflines (`mla_attend_cost`,
   `moe_experts_decode_cost`): the least a decode step must move or
   multiply.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/kanana_server.py`), the tokenizer, and the check
   of what was served (`check_served`: the served tokens against the
   logits the engine's own two programs give for them with the chip free,
   those against the reference's).
"""

from __future__ import annotations

import math

from families.gpt2 import CharTokenizer as _CharTokenizer

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `DeepseekConfig`."""
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "n_dense_layer": model["first_k_dense_replace"],
            "n_head": model["num_attention_heads"],
            "d_model": model["hidden_size"],
            "d_ff": model["intermediate_size"],
            "d_ff_expert": model["moe_intermediate_size"],
            "n_experts": model["n_routed_experts"],
            "experts_per_token": model["num_experts_per_tok"],
            "n_shared_experts": model["n_shared_experts"],
            "norm_topk_prob": model["norm_topk_prob"],
            "router_scoring": model["scoring_func"],
            "routed_scaling_factor": model["routed_scaling_factor"],
            "kv_lora_rank": model["kv_lora_rank"],
            "qk_nope_head_dim": model["qk_nope_head_dim"],
            "qk_rope_head_dim": model["qk_rope_head_dim"],
            "v_head_dim": model["v_head_dim"],
            "rope_theta": float(model["rope_theta"]),
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `DeepseekConfig`, as the engine builds it."""
    from ray_tpu.models import deepseek

    deploy = config["deployment"]
    return deepseek.DeepseekConfig.preset(
        deploy["preset"], **program_sizes(config["model"]),
        max_seq_len=deploy["max_seq_len"])


# -------------------------------------------------------------- arithmetic


def mla_attend_cost(model: dict, positions: float) -> dict:
    """The least one layer's absorbed attention needs for `positions`
    attended positions (summed over the slots of a step): each position's
    latent and rotary key read once (r + p values of 2 bytes; the program
    reads the latent twice, for the scores and for the weighted sum, and a
    fused kernel would not), and 2 H (r + p) operations for the scores
    plus 2 H r for the weighted latents."""
    r, p = model["kv_lora_rank"], model["qk_rope_head_dim"]
    heads = model["num_attention_heads"]
    return {"bytes": positions * (r + p) * 2.0,
            "flops": positions * 2.0 * heads * (2 * r + p)}


def moe_experts_decode_cost(model: dict, rows: float,
                            experts_touched: float) -> dict:
    """The least one expert layer's three grouped products need for `rows`
    (lane, expert) rows over `experts_touched` experts with at least one
    row: each touched expert's three matrices read once (bf16), each row
    read and written once at the model's width, and 6 d F operations a
    row. The untouched experts' weights are not counted: the kernel skips
    them."""
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    return {"bytes": experts_touched * 3.0 * d * f * 2 + rows * 2.0 * d * 2,
            "flops": rows * 6.0 * d * f}


# --------------------------------------------------------------- reference

DEGRADE = (None, "float8_experts", "float8_cache")


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x [T, H, p] at `positions` [T]: lane i turns with lane i + p/2."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(0, 2 * half, 2, dtype=jnp.float32)
                          / (2 * half))
    angle = positions.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _through_float8(a):
    """`a` as a float8 e4m3 array with one scale would hold it (three bits
    of mantissa, the largest magnitude at the top of the format's range),
    as float8 deployments store weights and caches. `reduce_precision`,
    not a pair of conversions: those are the compiler's to remove, and on
    the TPU it removed the weights' (PERF.md, PR 29)."""
    import jax
    import jax.numpy as jnp

    scale = 240.0 / jnp.max(jnp.abs(a))
    return jax.lax.reduce_precision(a * scale, exponent_bits=4,
                                    mantissa_bits=3) / scale


def _swiglu(h, p):
    import jax

    return (jax.nn.silu(h @ p["wg"]) * (h @ p["wu"])) @ p["wd"]


def reference_layer(x, p, model: dict, degrade=None):
    """x [T, d] float32 -> (x after the layer whose weights are `p`, what
    its router chose [T, K] or None): a dense layer where `p` has `mlp`,
    an expert layer where it has `moe`."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    heads = model["num_attention_heads"]
    n, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    r, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    seq = x.shape[0]
    positions = jnp.arange(seq)
    with jax.default_matmul_precision("highest"):
        a = p["attn"]
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("td,dhk->thk", h, a["wq"])              # [T, H, n+p]
        ckr = h @ a["wkva"]
        c = _rms_norm(ckr[:, :r], a["kv_norm"]["scale"], eps)
        k_r = _rope(ckr[:, None, r:], positions, theta)        # [T, 1, p]
        if degrade == "float8_cache":
            c, k_r = _through_float8(c), _through_float8(k_r)
        q = jnp.concatenate([q[..., :n], _rope(q[..., n:], positions, theta)],
                            axis=-1)
        kv = jnp.einsum("tr,rhk->thk", c, a["wkvb"])           # [T, H, n+v]
        k = jnp.concatenate(
            [kv[..., :n], jnp.broadcast_to(k_r, (seq, heads, rope))], axis=-1)
        scores = jnp.einsum("qhk,thk->hqt", q, k) / math.sqrt(n + rope)
        scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                           -jnp.inf)
        o = jnp.einsum("hqt,thv->qhv", jax.nn.softmax(scores, axis=-1),
                       kv[..., n:])
        x = x + o.reshape(seq, -1) @ a["wo"]

        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        if "mlp" in p:
            return x + _swiglu(h, p["mlp"]), None
        m = p["moe"]
        top_k, n_experts = model["num_experts_per_tok"], m["router"].shape[1]
        assert model["scoring_func"] == "sigmoid"
        assert model["topk_method"] == "noaux_tc"
        assert model["n_group"] == model["topk_group"] == 1   # no group limit
        s = jax.nn.sigmoid(h @ m["router"])
        _, chosen = jax.lax.top_k(s + m["bias"], top_k)
        kept = jnp.take_along_axis(s, chosen, axis=-1)
        if model["norm_topk_prob"]:
            kept = kept / (jnp.sum(kept, axis=-1, keepdims=True) + 1e-20)
        kept = kept * model["routed_scaling_factor"]
        gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=x.dtype)
                        * kept[..., None], axis=-2)            # [T, E]
        weights = (m["wg"], m["wu"], m["wd"])
        if degrade == "float8_experts":
            weights = tuple(_through_float8(w) for w in weights)

        def expert(acc, e):
            wg, wu, wd, gate = e
            y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
            return acc + gate[:, None] * y, None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                                 (*weights, gates.T))
        return x + routed + _swiglu(h, p["shared"]), chosen


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, vocab]: the final norm and the untied head."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["rms_norm_eps"])
        return x @ ends["lm_head"].astype(jnp.float32)


class Reference:
    """The reference walked a layer at a time over several sequences of
    one padded length: `layer_weights(l)` makes layer l's weights (the
    program's `init_layer` from the seed, or a test's own), which are
    dropped before the next layer's are made."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        self._layer = jax.jit(
            lambda x, p: reference_layer(x, p, model, degrade))
        # `ends` an argument: closed over, the table and the head would be
        # a gigabyte of constants in the compiled program
        self._head = jax.jit(lambda x, ends: reference_head(x, ends, model))

    def hidden(self, rows: list) -> list:
        """rows: token id lists -> each row's final hidden [T_padded, d]
        (causal: the padding after a row cannot reach it)."""
        import jax.numpy as jnp
        import numpy as np

        width = -(-max(len(r) for r in rows) // 128) * 128
        table = self.ends["wte"]
        xs = []
        for row in rows:
            ids = np.zeros((width,), np.int32)
            ids[:len(row)] = row
            xs.append(table[jnp.asarray(ids)].astype(jnp.float32))
        for l in range(self.model["num_hidden_layers"]):
            p = self.layer_weights(l)
            xs = [self._layer(x, p)[0] for x in xs]
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
    ids), with this vocabulary's end-of-text id (Llama 3's
    `<|end_of_text|>`, which Kanana's tokenizer keeps)."""

    eos_id = 128001


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
    `BenchServer` in `OpenAIServer`'s place, as `families/gpt2.py` does."""
    from ray_tpu.serve.api import deployment

    from families.kanana_server import BenchServer

    actor_options = {"num_cpus": 1}
    if num_tpu_chips:
        actor_options["num_tpu_chips"] = num_tpu_chips
    model_id = config["name"]
    dep = deployment(BenchServer, name=f"openai-{model_id}",
                     num_replicas=1, ray_actor_options=actor_options,
                     max_ongoing_requests=config["deployment"]["max_batch"] * 2,
                     slo_config=None)
    return dep.bind(model_id=model_id, checkpoint=None,
                    **engine_options(config, seed),
                    roofline_costs=roofline_costs(config["model"]))


def roofline_costs(model: dict) -> dict:
    """The two cost functions at one unit each, for the replica's `stats()`
    to carry to the readers (which see the record, not the configuration):
    a reader multiplies them by what the engine's counters counted."""
    return {"attention_layers": model["num_hidden_layers"],
            "routed_experts": model["n_routed_experts"],
            "mla_attend_per_position": mla_attend_cost(model, 1.0),
            "moe_experts_per_row": moe_experts_decode_cost(model, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_decode_cost(model, 0.0, 1.0)}


def request_body(request: dict) -> dict:
    """The `/v1/completions` body of one generated request."""
    return {"prompt_ids": request["prompt_ids"],
            "max_tokens": request["max_tokens"],
            "temperature": request["temperature"],
            "top_p": request["top_p"], "stream": True}


REQUEST_PATH = "/v1/completions"

# What decides `correct`, in two steps, because the served tokens alone
# cannot: with seeded weights any rounding becomes a different expert for
# some token within a few layers, so a bf16 program's greedy token differs
# from the float32 reference's at 0.3-5.3% of positions and a float8 one's
# at 5-8% (near-ties), and over the 1,500 tokens a run checks the means of
# how far below the maximum they lie overlap (0.0001-0.0027 against
# 0.0012-0.0045).
#
# 1. What was served is what the timed programs compute. With the chip
#    free, an engine made as the replica's was (`stopped_engine`: the seed,
#    the deployment, the compile cache's two programs) takes the sampled
#    replies the way the window's requests went: each prompt's whole blocks
#    prefilled in chunks and pooled, then copied from the pool into another
#    slot, the rest of the prompt as a chunk, and the served tokens decoded
#    one step each, all the sampled replies live in their slots at once
#    (`engine_logits`). The share of served tokens that are not their row's
#    maximum may not pass `SERVED_NOT_ENGINE_TOP_LIMIT`. It is not 0: a row
#    of a step depends on no other row and the pool's route gives the plain
#    prefill's logits to the bit, but whenever another slot prefills, the
#    window's decode lanes ride the chunk program as chunks of one token,
#    which rounds elsewhere (and so do the latents it writes), and the
#    replies do not say which steps those were. One decode step in sixteen
#    through the chunk program moves the greedy choice at 1.0% of seeded
#    positions; the cell's replies read 0.6-2.5% over nine runs, 0.44-0.71
#    of what separates them from the float32 reference (1.0-4.5%; up to
#    5.2% in earlier runs, so up to ~4% here). On the seeded positions the
#    choices of a reference with float8 experts read 5.3% and with a float8
#    cache 9.8% (6.9-8.4% and 12-15% over whole documents); tokens of
#    another slot, seed or model 100%. The limit lies above twice the
#    widest reading (one wrong refusal costs a PR) and under what a float8
#    part reads over documents; it is the second limit that refuses those
#    with room.
# 2. Those logits, the timed programs' own, are the reference's: their mean
#    absolute difference at the generated positions may not pass
#    `ENGINE_LOGIT_MEAN_ABS_LIMIT`, which lies between the program's widest
#    reading (0.0104; 0.0080-0.0099 by the engine's programs) and what the
#    reference reads with its routed experts' weights (0.0151-0.0178), or
#    its cached latents and rotary keys (0.031-0.039), through float8: it
#    refuses both, in every run.
#
# Readings on the v5e at the published widths: rehearse/kanana_on_chip.py
# and the cell's own runs (PERF.md, PR 29).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.06
FAR_BELOW = 0.1         # a tenth of the logits' spread (their rms is 0.9)
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.0125


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import deepseek

    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: deepseek.init_layer(key, l, cfg),
            deepseek.init_ends(key, cfg))


def _rows_and_positions(served: list) -> tuple:
    rows = [s["prompt_ids"] + s["token_ids"] for s in served]
    at = [list(range(len(s["prompt_ids"]) - 1,
                     len(s["prompt_ids"]) - 1 + len(s["token_ids"])))
          for s in served]
    return rows, at


def stopped_engine(config: dict, seed: int):
    """An `LLMEngine` made as the replica's was (the seed's weights, the
    deployment, the compile cache's programs) with its loop stopped: its
    two step programs, cache and pool are the caller's to drive."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(**engine_options(config, seed))
    eng.shutdown()
    eng._thread.join()
    return eng


def engine_logits(eng, served: list) -> list:
    """For each served sequence the float32 logits [generated positions,
    vocab] of the engine's own two compiled programs (`LLMEngine._chunk_step`,
    `_step`; `eng` a `stopped_engine`), by the route a request of the window
    took: sequence i's whole prompt blocks are prefilled in slot 2i a chunk
    at a time and pooled (`store_prefix`), found again and copied into slot
    2i + 1 (`match_prefix`, `copy_into_slot`), where the rest of the prompt
    goes as chunks and then the served tokens a decode step each. As
    many sequences as half the slots are live in the same steps."""
    half = eng.max_batch // 2
    return [rows for k in range(0, len(served), half)
            for rows in _engine_logits_together(eng, served[k:k + half])]


def _engine_logits_together(eng, served: list) -> list:
    import numpy as np

    B, C = eng.max_batch, eng.prefill_chunk_size

    def chunks(slots: list, start: list, texts: list) -> list:
        """texts[i][start[i]:] into slots[i], C tokens a step: the logits
        after each text's last token."""
        pos, last = list(start), [None] * len(texts)
        while any(p < len(t) for p, t in zip(pos, texts)):
            tokens = np.zeros((B, C), np.int32)
            pos0, length = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
            for i, (slot, text) in enumerate(zip(slots, texts)):
                take = text[pos[i]:pos[i] + C]
                tokens[slot, :len(take)] = take
                pos0[slot], length[slot] = pos[i], len(take)
                pos[i] += len(take)
            logits, eng.cache = eng._chunk_step(
                eng.params, eng.cache, tokens, pos0, length, length > 0)
            ended = [i for i, (slot, text) in enumerate(zip(slots, texts))
                     if length[slot] and pos[i] == len(text)]
            if ended:
                got = np.asarray(logits[np.asarray([slots[i] for i in ended])])
                for i, row in zip(ended, got):
                    last[i] = row
        return last

    prompts = [s["prompt_ids"] for s in served]
    donors = [2 * i for i in range(len(served))]
    slots = [2 * i + 1 for i in range(len(served))]
    block = eng.kv.block_size
    chunks(donors, [0] * len(served),
           [p[:(len(p) - 1) // block * block] for p in prompts])
    start = []
    for prompt, donor, slot in zip(prompts, donors, slots):
        eng.kv.store_prefix(prompt, eng.cache, donor)
        n_hit, blocks = eng.kv.match_prefix(prompt[:-1])
        if n_hit:
            eng.cache = eng.kv.copy_into_slot(eng.cache, slot, blocks)
        start.append(n_hit)
    rows = [[row] for row in chunks(slots, start, prompts)]
    at = np.asarray(slots)
    pos = [len(p) for p in prompts]
    for j in range(max(len(s["token_ids"]) for s in served) - 1):
        tokens, where = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        for i, (slot, s) in enumerate(zip(slots, served)):
            if j < len(s["token_ids"]) - 1:
                tokens[slot], where[slot] = s["token_ids"][j], pos[i] + j
                live[slot] = True
        logits, eng.cache = eng._step(eng.params, eng.cache, tokens, where,
                                      live)
        step = np.asarray(logits[at])
        for i, slot in enumerate(slots):
            if live[slot]:
                rows[i].append(step[i])
    return [np.stack(r) for r in rows]


def compare_served(served: list, engine: list, reference: list) -> dict:
    """The two readings that decide `correct` (the limits above say why
    two), and for the record how far the served tokens lie below the
    reference's own maximum. `engine` and `reference`: each sequence's
    logits at its generated positions."""
    import numpy as np

    below_engine, below_reference, apart = [], [], []
    for s, p, r in zip(served, engine, reference):
        if not (np.all(np.isfinite(p)) and np.all(np.isfinite(r))):
            return {"error": "non-finite logits"}
        tokens, n = np.asarray(s["token_ids"]), len(s["token_ids"])
        below_engine.append(p.max(axis=-1) - p[np.arange(n), tokens])
        below_reference.append(r.max(axis=-1) - r[np.arange(n), tokens])
        apart.append(np.abs(p - r).mean(axis=-1))
    below_engine, below_reference, apart = (
        np.concatenate(a) for a in (below_engine, below_reference, apart))
    return {"served_not_engine_top_share": float((below_engine > 0).mean()),
            "engine_logit_mean_abs": float(apart.mean()),
            # for the record: a choice another rounding makes lies close
            # below the maximum, a token of another slot or model far
            "served_below_engine_top_mean": float(below_engine.mean()),
            "served_far_below_engine_top_share": float(
                (below_engine > FAR_BELOW).mean()),
            "served_not_reference_top_share": float(
                (below_reference > 0).mean()),
            "served_below_reference_top_mean": float(below_reference.mean()),
            "served_below_reference_top_worst": float(below_reference.max()),
            "tokens_checked": len(apart)}


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
