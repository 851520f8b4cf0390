"""The Nemotron-H family (`model_type: nemotron_h`): what the benchmark needs
to know about one model family.

1. The plain reference (`reference_layer`, `reference_head`, `Reference`):
   the layers of `nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16` as its
   config.json and the published Mamba-2 layer describe them, in plain
   `jax.numpy` and float32 under `jax.default_matmul_precision("highest")`,
   no kernel, no cache, no chunks, a layer at a time. It imports nothing from
   `ray_tpu.models` or `ray_tpu.ops`; it reads a layer's weights as the
   program lays them out, which is the one thing it takes from the program
   (`mamba.{norm, ssm.{w_zx [d, 8192 + 10240], w_dt [d, 128] (W_in's
   columns, in two arrays), w_out [8192, d], dt_bias, a_log, d [128], conv_w
   [4, 10240], conv_b, norm}}`, `attention.{norm, wq [d, 32 x 128], wk, wv
   [d, 2 x 128], wo}`, `moe.{norm, router [d, 512], bias, w_down [d, 1024],
   w_back [1024, d], shared.{w_in [d, 5376], w_out}}` with `experts.{wu [E',
   1024, 2688], wd [E', 2688, 1024]}`). A layer is ONE sublayer. With d
   4096, eps 1e-5:

       x += mixer_l(RMSNorm_l(x)),  mixer_l by the pattern's letter
       M (Mamba-2; H = 128 heads of P = 64 lanes, G = 8 groups, N = 128):
         [z, xBC, dt] = u W_in;  xBC = silu(conv1d_causal_4(xBC) + b)
         x [128, 64], B [8, 128], C [8, 128] = split(xBC)
         dt = softplus(dt + dt_bias);  A = -exp(A_log);  g(h) = h // 16
         S_t = exp(dt A) S_{t-1} + dt x_t B_{g(h),t}^T
         y_t = S_t C_{g(h),t} + D x_t
         y = RMSNorm_by_group(y * silu(z)) * w: the gate first, then the
           norm over each group's 1,024 lanes;  W_out
       * (32 query / 2 key-value heads of 128, no positions, no gate):
         q = u W_q; k, v = u W_k, u W_v; head h reads key-value head
         h // 16; causal softmax(q . k / sqrt(128)) . v;  W_o
       E (512 router outputs, 22 a token, one shared; relu2 = relu^2):
         s = sigmoid(u W_r); the 22 largest of s + bias chosen;
         g = s[chosen] / (sum + 1e-20) x 5.0
         c = u W_down;  r = sum_k g_k relu2(c W_up^(e_k)) W_dn^(e_k) over
           the chosen experts THAT ARE HELD (`first_expert`..+E'): what the
           absent experts would add is left out, here as in the program
         out = r W_back + relu2(u W_su) W_sd
       final RMSNorm, untied head over the held rows of the vocabulary

   The Mamba-2 layer by the recurrence, a token at a time over the whole
   sequence from a zero state (never the SSD form the program's chunk step
   uses, nor its state's layout), attention in the plain form a block of
   `QUERY_BLOCK` queries at a time with the rows of k and v as the
   configuration states them (`stated.rows`: through bfloat16), the experts
   a loop over the held ones with the gate zero outside a token's 22, an
   expert's matrices widened to float32 as the loop reaches it.

   Departures from the published description, each in the configuration
   file's `assumed` or `departures`: where the two latent projections stand
   (round the routed experts alone); no rotation in attention; the gate
   before a norm by group; `time_step_limit` (0, inf); the multi-token
   prediction module left out; seeded weights.

   `degrade` computes one part below what the configuration states or
   another mathematics (`bfloat16_state`: S rounded to bfloat16 after every
   token; `bfloat16_latent`: the latent row c and the routed sum r rounded
   to bfloat16, what a dispatch of bf16 rows would hold; `bfloat16_scores`:
   attention's scores rounded to bfloat16 before the softmax;
   `norm_over_all`: the gated norm over all 8,192 lanes, one group;
   `one_group`: every head reads group 0's B and C): what the family's
   limits have to refuse.
2. The arithmetic of the rooflines (`ssm_update_cost`, `moe_experts_cost`,
   and Solar's `gqa_attend_cost` at this family's heads): the least a decode
   step must move or compute there, whatever implements it.
3. How the program serves this family through its normal entry points
   (`build_app`, `families/nemotron_server.py`), the tokenizer, and the
   check of what was served (`check_served`, as Solar's).
"""

from __future__ import annotations

import math

from families.brumby import _through_bfloat16
# the window's route through the engine's own programs, rows and state alike
from families.granite import engine_logits
from families.kanana import (REQUEST_PATH, _rows_and_positions,  # noqa: F401
                             request_body)
from families.kimi import CharTokenizer as _CharTokenizer
from families.solar import compare, gqa_attend_cost  # noqa: F401

# ----------------------------------------------------------- configuration


def program_sizes(config: dict) -> dict:
    """A configuration file (Hugging Face's key names under `model`, as in
    the source; the share of the deployment under `share`) in the names of
    the program's `NemotronConfig`."""
    model, share = config["model"], config["share"]
    assert model["mlp_hidden_act"] == "relu2"
    assert model["mamba_hidden_act"] == "silu" and model["use_conv_bias"]
    assert not (model["mamba_proj_bias"] or model["mlp_bias"]
                or model["attention_bias"] or model["use_bias"])
    assert not model["tie_word_embeddings"]
    assert model["n_group"] == 1 and model["topk_group"] == 1
    assert model["n_shared_experts"] == 1
    assert model["num_nextn_predict_layers"] == 0, "no drafting module"
    assert len(model["hybrid_override_pattern"]) == model["num_hidden_layers"]
    assert (model["expand"] * model["hidden_size"]
            == model["mamba_num_heads"] * model["mamba_head_dim"])
    return {"vocab_size": model["vocab_size"],
            "pattern": model["hybrid_override_pattern"],
            "d_model": model["hidden_size"],
            "ssm_heads": model["mamba_num_heads"],
            "ssm_head_dim": model["mamba_head_dim"],
            "ssm_state": model["ssm_state_size"],
            "ssm_groups": model["n_groups"],
            "ssm_conv": model["conv_kernel"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "head_dim": model["head_dim"],
            "n_experts": share["router_outputs"],
            "experts_held": model["n_routed_experts"],
            "first_expert": share["first_expert"],
            "experts_per_token": model["num_experts_per_tok"],
            "d_ff_expert": model["moe_intermediate_size"],
            "d_latent": model["moe_latent_size"],
            "d_ff_shared": model["moe_shared_expert_intermediate_size"],
            "norm_topk_prob": model["norm_topk_prob"],
            "routed_scaling_factor": float(model["routed_scaling_factor"]),
            "norm_eps": model["layer_norm_epsilon"]}


def program_config(config: dict):
    """The replica's `NemotronConfig`, as the engine builds it."""
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


def _layers(model: dict, letter: str) -> int:
    return model["hybrid_override_pattern"].count(letter)


def _ssm_inner(model: dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def _conv_width(model: dict) -> int:
    return _ssm_inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def ssm_update_cost(model: dict, slots: float) -> dict:
    """The least one Mamba-2 layer's one-token update-and-read-out needs for
    `slots` slots (`families/granite.py`'s count at this family's keys):
    every head's S [64, 128] and the convolution's window [3, 10240] read
    once and written once, float32, and for each entry of S a multiplication
    by the decay, a multiply-add of the input's and B's entries and a
    multiply-add into the read-out. Bound by the bytes on a v5e."""
    entries = _ssm_inner(model) * model["ssm_state_size"]
    window = (model["conv_kernel"] - 1) * _conv_width(model)
    return {"bytes": slots * (entries + window) * 4.0 * 2,
            "flops": slots * entries * 5.0}


def moe_experts_cost(model: dict, rows: float,
                     experts_touched: float) -> dict:
    """The least one expert layer's routed part needs for `rows` (lane,
    expert) rows over `experts_touched` held experts with at least one row:
    each touched expert's two matrices [1024, 2688] read once (bf16, 11.01
    MB), each row's latent read and written once (bf16: the least, whatever
    the program moves), and 4 c F operations a row. The latent projections
    and the shared expert are not the experts' (`moe_latent`,
    `moe_shared`)."""
    c, f = model["moe_latent_size"], model["moe_intermediate_size"]
    return {"bytes": experts_touched * 2.0 * c * f * 2 + rows * 2.0 * c * 2,
            "flops": rows * 4.0 * c * f}


def kv_bytes_per_token(model: dict) -> int:
    return (_layers(model, "*") * 2 * model["num_key_value_heads"]
            * model["head_dim"] * 2)


def state_bytes_per_slot(model: dict) -> int:
    return int(_layers(model, "M") * ssm_update_cost(model, 1.0)["bytes"] / 2)


# --------------------------------------------------------------- reference

DEGRADE = (None, "bfloat16_state", "bfloat16_latent", "bfloat16_scores",
           "norm_over_all", "one_group")
QUERY_BLOCK = 128


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _relu2(a):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(a, 0.0))


def _mamba(u, p, model: dict, degrade):
    """u [R, T, d] (normed) -> the mixer's output [R, T, d]."""
    import jax
    import jax.numpy as jnp

    heads, lanes = model["mamba_num_heads"], model["mamba_head_dim"]
    n, taps, groups = (model["ssm_state_size"], model["conv_kernel"],
                       model["n_groups"])
    inner, per = heads * lanes, heads // groups
    rows, seq = u.shape[0], u.shape[1]
    z, xbc = jnp.split(u @ p["w_zx"], [inner], axis=-1)
    dt = u @ p["w_dt"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][k] * padded[:, k:k + seq] for k in range(taps)))
    x, b, c = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(rows, seq, groups, per, lanes)
    b, c = (t.reshape(rows, seq, groups, n) for t in (b, c))
    if degrade == "one_group":
        b, c = (jnp.broadcast_to(t[:, :, :1], t.shape) for t in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(rows, seq, groups, per)
    a = -jnp.exp(p["a_log"]).reshape(groups, per)

    def token(s, args):                             # s [R, G, H/G, P, N]
        xt, bt, ct, dtt = args      # [R,G,H/G,P] [R,G,N] [R,G,N] [R,G,H/G]
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, None, :])
        if degrade == "bfloat16_state":
            s = _through_bfloat16(s)
        return s, jnp.einsum("rghpn,rgn->rghp", s, ct)

    _, y = jax.lax.scan(
        token, jnp.zeros((rows, groups, per, lanes, n), jnp.float32),
        tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt)))
    y = jnp.moveaxis(y, 0, 1) + p["d"].reshape(groups, per)[..., None] * x
    y = y.reshape(rows, seq, inner) * jax.nn.silu(z)
    eps, scale = model["layer_norm_epsilon"], p["norm"]["scale"]
    if degrade == "norm_over_all":
        y = _rms_norm(y, scale, eps)
    else:
        # a group of 16 heads x 64 lanes at a time
        y = _rms_norm(y.reshape(rows, seq, groups, inner // groups),
                      scale.reshape(groups, inner // groups),
                      eps).reshape(rows, seq, inner)
    return y @ p["w_out"]


def _attention_row(u, p, model: dict, degrade):
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
    return o.reshape(seq, heads * lanes) @ p["wo"]


def _expert_block(h, moe, experts, model: dict, degrade=None):
    """h [T, d] (normed) -> (the held experts' part of the routed sum
    through the latent plus the shared expert, what the router chose [T,
    K]). `experts` as the replica holds them: each is widened to float32 as
    the loop reaches it."""
    import jax
    import jax.numpy as jnp

    top_k, first = model["num_experts_per_tok"], model["first_expert"]
    held = experts["wu"].shape[0]
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
    c = h @ moe["w_down"]                                   # the latent row
    if degrade == "bfloat16_latent":
        c = _through_bfloat16(c)

    def expert(acc, e):
        wu, wd = (w.astype(jnp.float32) for w in e[:2])
        y = _relu2(c @ wu) @ wd
        if degrade == "bfloat16_latent":
            y = _through_bfloat16(y)
        return acc + e[2][..., None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(c),
                             (experts["wu"], experts["wd"], mine))
    shared = _relu2(h @ moe["shared"]["w_in"]) @ moe["shared"]["w_out"]
    return routed @ moe["w_back"] + shared, chosen


def reference_layer(x, p, model: dict, degrade=None):
    """x [R, T, d] float32 -> x after the one sublayer whose weights are `p`
    (its kind by `mamba`, `attention` or `moe` + `experts`): R sequences,
    each its own."""
    import jax
    import jax.numpy as jnp

    assert degrade in DEGRADE, degrade
    eps = model["layer_norm_epsilon"]
    experts = p.get("experts")
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     {k: v for k, v in p.items() if k != "experts"})
    with jax.default_matmul_precision("highest"):
        if "mamba" in p:
            m = p["mamba"]
            return x + _mamba(_rms_norm(x, m["norm"]["scale"], eps),
                              m["ssm"], model, degrade)
        if "attention" in p:
            m = p["attention"]
            return x + jax.lax.map(
                lambda row: _attention_row(row, m, model, degrade),
                _rms_norm(x, m["norm"]["scale"], eps))
        m = p["moe"]
        rows, seq, d = x.shape
        h = _rms_norm(x, m["norm"]["scale"], eps).reshape(rows * seq, d)
        return x + _expert_block(h, m, experts, model,
                                 degrade)[0].reshape(rows, seq, d)


def reference_head(x, ends, model: dict):
    """x [T, d] -> logits [T, held vocabulary]: the final norm and the
    untied head."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, ends["final_norm"]["scale"].astype(jnp.float32),
                      model["layer_norm_epsilon"])
        return x @ ends["lm_head"].astype(jnp.float32)


class Reference:
    """The reference walked a layer at a time over several sequences of one
    padded length: `layer_weights(l)` makes layer l's weights (the program's
    `init_layer` from the seed, or a test's own), which are dropped before
    the next layer's are made. `model` is `reference_model(config)`."""

    def __init__(self, model: dict, layer_weights, ends, degrade=None):
        import jax

        self.model, self.layer_weights, self.ends = model, layer_weights, ends
        # one compiled program a kind of layer: the kinds' trees differ
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

    eos_id = 32767


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

    from families.nemotron_server import BenchServer

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
    names granite's readers know for the Mamba-2 and the attention layers
    and Kanana's for the held experts."""
    return {"ssm_layers": _layers(model, "M"),
            "ssm_update_per_slot": ssm_update_cost(model, 1.0),
            "gqa_layers": _layers(model, "*"),
            "gqa_attend_per_position": gqa_attend_cost(model, 1.0),
            "routed_experts": model["n_routed_experts"],
            "moe_experts_per_row": moe_experts_cost(model, 1.0, 0.0),
            "moe_experts_per_touched_expert":
                moe_experts_cost(model, 0.0, 1.0)}


# What decides `correct`, in two steps as for Solar and Kimi
# (`families/kimi.py` has the two steps' account, `families/kanana.py` why
# the served tokens alone cannot decide).
#
# 1. What was served is what the timed programs compute: the share of served
#    tokens that are not their row's maximum in the engine's own logits,
#    taken the way the window's requests went (`engine_logits`), may not
#    pass `SERVED_NOT_ENGINE_TOP_LIMIT` (Kimi's limit, for Kimi's reason: a
#    decode lane that rides a chunk step goes through the chunk program's
#    own compilation of the first lane).
# 2. Those logits are the reference's, by two numbers over the generated
#    positions, each position's the mean absolute difference of its logits:
#    the tenth percentile over the positions, the floor, may not pass
#    `ENGINE_LOGIT_FLOOR_ABS_LIMIT`, and the mean may not pass
#    `ENGINE_LOGIT_MEAN_ABS_LIMIT`. The floor holds the precision (a
#    rounding below what the file states moves every position), the mean a
#    fault in a minority of the positions and the other mathematics
#    (`families/solar.py` has the argument; five routers of 512 outputs
#    choosing 22 make the mean's tail longer here: a token has 110 chances
#    a pass that two experts a hair apart change places).
#
# The readings that set the limits are the configuration file's `limits`
# (rehearse/nemotron_on_chip.py on the v5e at the published widths, and the
# cell's own runs; PERF.md section 6, PR 53).
SERVED_NOT_ENGINE_TOP_LIMIT = 0.015
ENGINE_LOGIT_MEAN_ABS_LIMIT = 0.005
ENGINE_LOGIT_FLOOR_ABS_LIMIT = 0.00016


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
