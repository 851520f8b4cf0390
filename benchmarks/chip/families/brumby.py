"""The Brumby family (`model_type: brumby`): what the benchmark needs to know
about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layer of `manifestai/Brumby-14B-Base` as described (Manifest AI's
   model card; arXiv:2507.04239; Qwen3-14B's shapes), in plain `jax.numpy`
   and float32 under `jax.default_matmul_precision("highest")`, one
   sequence, no state, no cache, no chunks, a layer at a time. It imports
   nothing from `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's
   weights as the program lays them out, which is the one thing it takes
   from the program (`attn.{wq [d,H,128], wk, wv [d,G,128], wg [d,G], bg,
   q_norm, k_norm, wo [H 128,d]}`, `mlp.{wg, wu, wd}`). With d 5120, H 40,
   G 8, eps 1e-6, theta 1e6:

       h = RMSNorm(x)
       q = h W_q, k = h W_k, v = h W_v; q, k: RMSNorm over the 128 lanes,
       then RoPE (rotate-half);  log g = logsigmoid(h W_g + b_g), a head
       G_ij = exp(sum_{j < m <= i} log g_m), for j <= i
       y_i = sum_{j<=i} G_ij (q_i . k_j)^2 v_j
             / (sum_{j<=i} G_ij (q_i . k_j)^2 + 1e-6)
       x += concat(y) W_o;  x += SwiGLU_17408(RMSNorm(x))
       final RMSNorm, untied head

   The quadratic form, never the state the program carries: all T x T
   weights of a key-value head's five query heads at a time (205 MB at
   3,200 tokens), the heads one after another. `degrade` computes one part
   below what the configuration states (`bfloat16_state`: the recurrence
   with S and z rounded to bfloat16 after every token, as a bf16 state
   would hold them, through an expansion of its own) or leaves one part out
   (`no_normaliser`): what the family's limits have to refuse.
2. The arithmetic of the kernel's roofline (`retention_update_cost`): the
   least a decode step's state update must move or compute.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/brumby_server.py`), the tokenizer, and the check
   of what was served (`check_served`: the served tokens against the logits
   the engine's own two programs give for them with the chip free, by the
   route a request of the window took, those against the reference's).
"""

from __future__ import annotations

from families.gpt2 import CharTokenizer as _CharTokenizer
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             compare_served, request_body)

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `BrumbyConfig`."""
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "d_model": model["hidden_size"],
            "d_ff": model["intermediate_size"],
            "rope_theta": float(model["rope_theta"]),
            "norm_eps": model["rms_norm_eps"]}


def program_config(config: dict):
    """The replica's `BrumbyConfig`, as the engine builds it."""
    from ray_tpu.models import brumby

    deploy = config["deployment"]
    return brumby.BrumbyConfig.preset(
        deploy["preset"], **program_sizes(config["model"]),
        max_seq_len=deploy["max_seq_len"])


# -------------------------------------------------------------- arithmetic

RETENTION_EPS = 1e-6


def content_width(model: dict) -> int:
    """Entries of the symmetric second power of a head's 128 lanes."""
    d = model["head_dim"]
    return d * (d + 1) // 2


def retention_update_cost(model: dict, slots: float) -> dict:
    """The least one layer's one-token update-and-read-out needs for `slots`
    slots: every key-value head's S [D, 128] and z [D] (D = 8,256, the
    content; float32) read once and written once, and for each entry of S a
    multiplication by the gate, one by the key's and the value's entries,
    an addition, and a multiply-add for each of the query heads that share
    the head. Bound by the bytes on a v5e (13 operations an entry against 8
    bytes; the chip's 197 TFLOP/s are the MXU's, which this does not use:
    the share says how near the pass over the state is to the HBM's peak)."""
    heads, d = model["num_key_value_heads"], model["head_dim"]
    per_kv = model["num_attention_heads"] // heads
    entries = slots * heads * content_width(model) * (d + 1)
    return {"bytes": entries * 4.0 * 2, "flops": entries * (3.0 + 2 * per_kv)}


def state_bytes_per_slot(model: dict) -> int:
    """The content a slot's state holds over the configuration's layers
    (the program's padded layout holds 0.78% more and says so itself)."""
    return (model["num_hidden_layers"] * model["num_key_value_heads"]
            * content_width(model) * (model["head_dim"] + 1) * 4)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_state", "no_normaliser")


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


def _through_bfloat16(a):
    """`reduce_precision`, not a pair of conversions, which are the
    compiler's to remove (PERF.md, PR 29)."""
    import jax

    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _second_power(a):
    """a [..., d] -> [..., d (d + 1) / 2]: `a_i a_j` for i <= j, the
    off-diagonal entries times sqrt 2, in the upper triangle's row order
    (not the program's layout: any order gives the same products)."""
    import jax.numpy as jnp
    import numpy as np

    rows, cols = np.triu_indices(a.shape[-1])
    scale = np.where(rows == cols, 1.0, np.sqrt(2.0)).astype(np.float32)
    return a[..., rows] * a[..., cols] * scale


def _retention_quadratic(q, k, v, log_g, degrade):
    """q [T, G, R, d], k, v [T, G, d], log_g [T, G] -> y [T, G, R, d]."""
    import jax
    import jax.numpy as jnp

    seq = q.shape[0]
    cum = jnp.cumsum(log_g, axis=0)                                # [T, G]
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def head(args):
        qh, kh, vh, ch = args                   # [T,R,d] [T,d] [T,d] [T]
        decay = jnp.exp(jnp.where(causal, ch[:, None] - ch[None, :],
                                  -jnp.inf))
        weight = jnp.einsum("ird,jd->rij", qh, kh) ** 2 * decay[None]
        num = jnp.einsum("rij,jd->ird", weight, vh)
        if degrade == "no_normaliser":
            return num
        return num / (jnp.sum(weight, axis=-1).T[..., None] + RETENTION_EPS)

    out = jax.lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                             jnp.moveaxis(v, 1, 0), cum.T))
    return jnp.moveaxis(out, 0, 1)


def _retention_recurrent_bfloat16(q, k, v, log_g):
    """The same sum as a recurrence whose state is rounded to bfloat16 after
    every token: what a replica that held S and z in bf16 would compute."""
    import jax
    import jax.numpy as jnp

    heads, d = k.shape[1], k.shape[2]
    width = d * (d + 1) // 2

    def token(carry, args):
        s, z = carry                                     # [G,D,d] [G,D]
        qt, kt, vt, gt = args                  # [G,R,d] [G,d] [G,d] [G]
        pk, gate = _second_power(kt), jnp.exp(gt)
        s = _through_bfloat16(gate[:, None, None] * s
                              + pk[:, :, None] * vt[:, None, :])
        z = _through_bfloat16(gate[:, None] * z + pk)
        pq = _second_power(qt)                                   # [G,R,D]
        num = jnp.einsum("grw,gwd->grd", pq, s)
        den = jnp.einsum("grw,gw->gr", pq, z)
        return (s, z), num / (den[..., None] + RETENTION_EPS)

    zero = (jnp.zeros((heads, width, d), jnp.float32),
            jnp.zeros((heads, width), jnp.float32))
    _, y = jax.lax.scan(token, zero, (q, k, v, log_g))
    return y


def reference_layer(x, p, model: dict, degrade=None):
    """x [T, d] float32 -> x after the layer whose weights are `p`."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    heads, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    theta = float(model["rope_theta"])
    assert model["hidden_act"] == "silu" and not model["attention_bias"]
    seq = x.shape[0]
    positions = jnp.arange(seq)
    with jax.default_matmul_precision("highest"):
        a = p["attn"]
        h = _rms_norm(x, p["attn_norm"]["scale"], eps)
        q = jnp.einsum("td,dhk->thk", h, a["wq"])
        k = jnp.einsum("td,dhk->thk", h, a["wk"])
        v = jnp.einsum("td,dhk->thk", h, a["wv"])
        log_g = jax.nn.log_sigmoid(h @ a["wg"] + a["bg"])          # [T, G]
        q = _rope(_rms_norm(q, a["q_norm"]["scale"], eps), positions, theta)
        k = _rope(_rms_norm(k, a["k_norm"]["scale"], eps), positions, theta)
        # query head j reads key-value head j // (H / G), as grouped heads do
        q = q.reshape(seq, kv, heads // kv, d)
        if degrade == "bfloat16_state":
            y = _retention_recurrent_bfloat16(q, k, v, log_g)
        else:
            y = _retention_quadratic(q, k, v, log_g, degrade)
        x = x + y.reshape(seq, heads * d) @ a["wo"]
        h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
        m = p["mlp"]
        return x + (jax.nn.silu(h @ m["wg"]) * (h @ m["wu"])) @ m["wd"]


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
    padded length: `layer_weights(l)` makes layer l's weights (the program's
    `init_layer` from the seed, or a test's own), which are dropped before
    the next layer's are made (1.3 GB in float32 at the published widths)."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        self._layer = jax.jit(
            lambda x, p: reference_layer(x, p, model, degrade))
        # `ends` an argument: closed over, the table and the head would be
        # gigabytes of constants in the compiled program
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
    ids), with this vocabulary's end-of-text id (Qwen's `<|endoftext|>`,
    which Brumby's tokenizer keeps)."""

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
    `BenchServer` in `OpenAIServer`'s place, as `families/kanana.py` does."""
    from ray_tpu.serve.api import deployment

    from families.brumby_server import BenchServer

    # a program without this family says so here, in the phase's own
    # process, and not in a replica that the deployment starts again
    program_config(config)
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
    """The cost function at one unit, for the replica's `stats()` to carry
    to the readers (which see the record, not the configuration): a reader
    multiplies it by the slots a decode step had live."""
    return {"retention_layers": model["num_hidden_layers"],
            "retention_update_per_slot": retention_update_cost(model, 1.0),
            "state_content_bytes_per_slot": state_bytes_per_slot(model)}


# What decides `correct`, in two steps as for Kanana (`families/kanana.py`
# says why the served tokens alone cannot: with seeded weights the largest
# logit changes on rounding).
#
# 1. What was served is what the timed programs compute. With the chip
#    free, an engine made as the replica's was takes the sampled replies the
#    way the window's requests went (`engine_logits`): each prompt's whole
#    blocks prefilled in chunks in one slot, the state there snapshotted
#    into the pool between two chunk steps, found again and copied into
#    another slot, the rest of the prompt as a chunk, and the served tokens
#    decoded one step each through the state-update kernel, the sampled
#    replies live in their slots at once. The share of served tokens that
#    are not their row's maximum may not pass
#    `SERVED_NOT_ENGINE_TOP_LIMIT`. It is not 0: a row of a step depends on
#    no other row, but whenever another slot prefills, the window's decode
#    lanes ride the chunk program as chunks of one token, which rounds
#    elsewhere than the kernel, and the replies do not say which steps those
#    were (one step in 48 here; one in 16 for Kanana, whose limit this
#    keeps). Tokens of another slot, seed or model read 100%.
# 2. Those logits, the timed programs' own, are the reference's: their mean
#    absolute difference at the generated positions may not pass
#    `ENGINE_LOGIT_MEAN_ABS_LIMIT`, which lies between the program's widest
#    reading over its seeds and what the reference reads with its state
#    through bfloat16 (PERF.md, PR 33, has both readings); a dropped
#    normaliser reads hundreds of times the limit.
SERVED_NOT_ENGINE_TOP_LIMIT = 0.06
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.02


def seeded_weights(config: dict, seed: int):
    """(`layer_weights(l)`, ends): the seed's weights as the replica makes
    them, a layer at a time, through the program's own `init_layer`."""
    import jax

    from ray_tpu.models import brumby

    cfg = program_config(config)
    key = jax.random.key(seed)
    return (lambda l: brumby.init_layer(key, l, cfg),
            brumby.init_ends(key, cfg))


def stopped_engine(config: dict, seed: int):
    """An `LLMEngine` made as the replica's was (the seed's weights, the
    deployment, the compile cache's programs) with its loop stopped: its
    two step programs, its state and its pool are the caller's to drive."""
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
    at a time from a zeroed state, the state there is pooled
    (`store_prefix`), found again and copied into slot 2i + 1
    (`match_prefix`, `copy_into_slot`), where the rest of the prompt goes as
    chunks and then the served tokens a decode step each. As many sequences
    as half the slots are live in the same steps; the pool's entries are
    freed between groups."""
    half = min(eng.max_batch // 2, eng.kv.num_blocks)
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
    for slot in donors + slots:
        eng.cache = eng._reset_slot(eng.cache, slot)
    whole = [p[:(len(p) - 1) // block * block] for p in prompts]
    chunks(donors, [0] * len(served), whole)
    start = []
    for prompt, head, donor, slot in zip(prompts, whole, donors, slots):
        if head:
            eng.kv.store_prefix(head, eng.cache, donor)
        n_hit, entry = eng.kv.match_prefix(prompt[:-1])
        assert n_hit == len(head), (n_hit, len(head))
        if n_hit:
            eng.cache = eng.kv.copy_into_slot(eng.cache, slot, entry)
        start.append(n_hit)
    rows = [[row] for row in chunks(slots, start, prompts)]
    at = np.asarray(slots)
    pos = [len(p) for p in prompts]
    unread: list = []         # (a step's rows on their way to the host, live)

    def read_oldest():
        picked, live = unread.pop(0)
        step = np.asarray(picked)
        for i, slot in enumerate(slots):
            if live[slot]:
                rows[i].append(step[i])

    for j in range(max(len(s["token_ids"]) for s in served) - 1):
        tokens, where = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        for i, (slot, s) in enumerate(zip(slots, served)):
            if j < len(s["token_ids"]) - 1:
                tokens[slot], where[slot] = s["token_ids"][j], pos[i] + j
                live[slot] = True
        logits, eng.cache = eng._step(eng.params, eng.cache, tokens, where,
                                      live)
        picked = logits[at]
        picked.copy_to_host_async()
        unread.append((picked, live))
        # the step before is read only now, with this one dispatched: the
        # device never waits for the host's conversion (PR 45), and no more
        # than two steps' rows wait on the device
        if len(unread) == 2:
            read_oldest()
    while unread:
        read_oldest()
    return [np.stack(r) for r in rows]


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
    gc.collect()                        # the engine's weights and state
    t1 = time.time()
    layer_weights, ends = seeded_weights(config, seed)
    rows, at = _rows_and_positions(served)
    reference = Reference(config["model"], layer_weights, ends).logits(rows,
                                                                       at)
    return {**verdict(compare_served(served, engine, reference)),
            "replies": len(served),
            "seconds": {"engine": round(t1 - t0, 1),
                        "reference": round(time.time() - t1, 1)}}
