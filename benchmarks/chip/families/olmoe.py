"""The OLMoE family: what the benchmark needs to know about one model family.

1. The plain reference (`reference_forward`, `reference_sums`,
   `reference_loss`): OLMoE as published (Muennighoff et al. 2024,
   arXiv:2409.02060; `allenai/OLMoE-1B-7B-0125-Instruct/config.json`), in
   plain `jax.numpy` and float32 under
   `jax.default_matmul_precision("highest")`. It imports nothing from
   `ray_tpu.models`; it reads the program's parameter tree, whose layout
   (`wte`, `blocks` stacked on a leading layer axis, `final_norm`,
   `lm_head`) is the one thing it takes from the program. A layer:

       h = RMSNorm(x)
       q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk)   over the whole projection,
       v = h Wv                                   before the split into heads
       RoPE on q and k (rotate-half, theta 10000); causal softmax attention
       x += o Wo
       h = RMSNorm(x)
       p = softmax_64(h Wr)                       float32
       the 8 largest p_e are kept as they are (norm_topk_prob false)
       x += sum_e p_e (silu(h Wg_e) * (h Wu_e)) Wd_e     no token dropped

   The experts are a plain loop over all 64, the gate zero outside a
   token's top 8. The loss is cross-entropy + 0.01 x load-balancing loss +
   0.001 x router z-loss, each layer's router losses averaged over layers.
2. The arithmetic: `train_flops_per_token`, `experts_flops_per_token`,
   `flash_attention_cost` (what the Pallas attention kernels execute and
   move).
3. How the program trains this family (`build_train`) through its normal
   entry points: `train/spmd.compile_model_train(moe, cfg, mesh)`.
"""

from __future__ import annotations

import math

# ----------------------------------------------------------- configuration


def program_sizes(model: dict) -> dict:
    """A configuration file's `model` object (Hugging Face's key names, as
    in the source) in the names of the program's `MoEConfig`."""
    return {"vocab_size": model["vocab_size"],
            "n_layer": model["num_hidden_layers"],
            "n_head": model["num_attention_heads"],
            "n_kv_head": model["num_key_value_heads"],
            "d_model": model["hidden_size"],
            "d_ff": model["intermediate_size"],
            "n_experts": model["num_experts"],
            "experts_per_token": model["num_experts_per_tok"],
            "norm_topk_prob": model["norm_topk_prob"],
            "max_seq_len": model["max_position_embeddings"],
            "rope_theta": float(model["rope_theta"]),
            "norm_eps": model["rms_norm_eps"],
            "tie_embeddings": model["tie_word_embeddings"]}


def program_config(model: dict, assumed: dict, **extra):
    """`assumed`: the configuration file's `job.router_losses` (the paper's
    two weights) and QK-norm, which the source's config.json has no key
    for."""
    from ray_tpu.models import moe

    return moe.MoEConfig(**program_sizes(model), qk_norm=assumed["qk_norm"],
                         aux_loss_weight=assumed["load_balancing_weight"],
                         z_loss_weight=assumed["z_loss_weight"], **extra)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Floating-point operations the forward and backward passes need for
    one token of a `seq_len` sequence: 6 for each weight of a matrix
    multiplication the token passes through (per layer 4·d² of attention,
    the router's d·E, and 3·d·F for each of its K experts; the
    unembedding's V·d) plus 12·L·d·T for the attention scores and their
    product with the values (PaLM's convention: the causal mask is not
    discounted). Not counted: norms, RoPE, the embedding gather, the
    routing's sort and gathers, the optimizer, and anything recomputed by
    rematerialization."""
    d, layers = model["hidden_size"], model["num_hidden_layers"]
    per_layer = (4 * d * d + d * model["num_experts"]
                 + model["num_experts_per_tok"] * 3 * d
                 * model["intermediate_size"])
    matmul_weights = layers * per_layer + model["vocab_size"] * d
    return 6.0 * matmul_weights + 12.0 * layers * d * seq_len


def experts_flops_per_token(model: dict) -> float:
    """The grouped matmuls alone, forward and backward, for one token:
    18·d·F·K a layer (three products of 2·d·F each for K experts, once
    forward and twice backward). Recomputation is not counted."""
    return (18.0 * model["hidden_size"] * model["intermediate_size"]
            * model["num_experts_per_tok"] * model["num_hidden_layers"])


def flash_attention_cost(model: dict, batch: int, seq_len: int,
                         block: int = 128) -> dict:
    """What one training step's three Pallas attention calls (forward,
    dq, dk/dv: `ops/flash_attention.py`) execute and move, per layer
    times layers. A causal kernel visits, for `n = T / block` blocks a
    side, n(n+1)/2 block pairs of a head and skips the rest; a visited
    pair costs 2·block²·head_dim for each product: 2 in the forward (QK',
    PV), 3 in dq (QK', dO V', dS K), 4 in dk/dv (QK', dO V', dS' Q,
    P' dO): 18·block²·head_dim. Bytes: each call reads q, k, v (and dO)
    once (K/V stay resident across a head's q blocks) and writes its
    outputs, in bf16, with the float32 row statistics."""
    heads = model["num_attention_heads"]
    head_dim = model["hidden_size"] // heads
    n = seq_len // block
    pairs = batch * heads * n * (n + 1) // 2
    rows = batch * heads * seq_len
    return {"flops": model["num_hidden_layers"] * 18.0 * pairs
            * block * block * head_dim,
            "bytes": model["num_hidden_layers"]
            * (15.0 * rows * head_dim * 2 + 5.0 * rows * 4)}


# --------------------------------------------------------------- reference


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [B, T, H, Dh]: rotate-half RoPE (Hugging Face's convention)."""
    import jax.numpy as jnp

    seq, head = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, head, 2, dtype=jnp.float32) / head)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :head // 2], x[..., head // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def reference_forward(params, tokens, model: dict, dtype="float32"):
    """tokens [B, T] int32 -> (logits [B, T, vocab], every layer's
    routing: what its router saw (`inputs` [L, B, T, D]) and gave (`logits`
    [L, B, T, E], the kept `gates` and the chosen `experts` [L, B, T, K]),
    the final norm's output [B, T, D] that the unembedding multiplies).
    `dtype` is float32 for the reference; "bfloat16" computes every step
    of it (router, softmaxes and norms too) below what the configuration
    states, which the limits below have to tell from the program."""
    import jax
    import jax.numpy as jnp

    f32 = lambda tree: jax.tree.map(lambda a: a.astype(dtype), tree)
    n_head = model["num_attention_heads"]
    n_kv = model["num_key_value_heads"]
    top_k, n_experts = model["num_experts_per_tok"], model["num_experts"]
    eps, theta = model["rms_norm_eps"], float(model["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"])[tokens]
        batch, seq, width = x.shape
        head = width // n_head
        causal = jnp.tril(jnp.ones((seq, seq), bool))

        def block(x, p):
            p = f32(p)
            a = p["attn"]
            h = _rms_norm(x, p["attn_norm"]["scale"], eps)
            q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
            if "q_norm" in a:
                q = _rms_norm(q, a["q_norm"]["scale"], eps)
                k = _rms_norm(k, a["k_norm"]["scale"], eps)
            q = _rope(q.reshape(batch, seq, n_head, head), theta)
            k = _rope(k.reshape(batch, seq, n_kv, head), theta)
            v = v.reshape(batch, seq, n_kv, head)
            k, v = (jnp.repeat(t, n_head // n_kv, axis=2) for t in (k, v))
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(head)
            scores = jnp.where(causal, scores, -jnp.inf)
            mixed = (jax.nn.softmax(scores, axis=-1) @ v).transpose(
                0, 2, 1, 3).reshape(batch, seq, width)
            x = x + mixed @ a["wo"]

            h = _rms_norm(x, p["mlp_norm"]["scale"], eps)
            m = p["moe"]
            router_logits = h @ m["router"]
            probs = jax.nn.softmax(router_logits, axis=-1)
            kept, chosen = jax.lax.top_k(probs, top_k)
            if model["norm_topk_prob"]:
                kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
            gates = jnp.sum(jax.nn.one_hot(chosen, n_experts, dtype=x.dtype)
                            * kept[..., None], axis=-2)      # [B, T, E]

            def expert(acc, e):
                wg, wu, wd, gate = e
                y = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
                return acc + gate[..., None] * y, None

            moe_out, _ = jax.lax.scan(
                expert, jnp.zeros_like(x),
                (m["wg"], m["wu"], m["wd"], jnp.moveaxis(gates, -1, 0)))
            return x + moe_out, {"inputs": h, "logits": router_logits,
                                 "gates": kept, "experts": chosen}

        x, routing = jax.lax.scan(block, x, params["blocks"])
        x = _rms_norm(x, f32(params["final_norm"]["scale"]), eps)
        head_w = (f32(params["lm_head"]) if "lm_head" in params
                  else f32(params["wte"]).T)
        return x @ head_w, routing, x


def token_nll(logits, targets):
    """logits [B, T, V], targets [B, T] -> each token's negative log
    likelihood [B, T], in the precision the logits come in."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def sums_of(logits, routing, tokens, model: dict) -> dict:
    """What the three-term loss is made of, as sums over `tokens`
    [B, T+1], so that a batch can be walked a few sequences at a time
    (the load-balancing loss multiplies two means over the batch, so it is
    not the mean of its slices' values): the cross-entropy's sum, per
    layer the z-loss's sum, the count of (token, slot) choices of each
    expert and the sum of each expert's router probability, and the
    number of tokens."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)    # the sums themselves: float32
    nll = f32(token_nll(logits, tokens[:, 1:]))
    n_experts = model["num_experts"]
    router_logits, chosen = routing["logits"], routing["experts"]
    sums = {"ce": jnp.sum(nll),
            "z": jnp.sum(f32(jax.nn.logsumexp(router_logits, axis=-1)) ** 2,
                         axis=(1, 2)),
            "count": jnp.sum(jax.nn.one_hot(chosen, n_experts),
                             axis=(1, 2, 3)),
            "prob": jnp.sum(f32(jax.nn.softmax(router_logits, axis=-1)),
                            axis=(1, 2)),
            "tokens": nll.size}
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), sums)


def reference_sums(params, tokens, model: dict, dtype="float32") -> dict:
    """tokens [B, T+1] -> `sums_of` the reference's forward pass."""
    return sums_of(*reference_forward(params, tokens[:, :-1], model,
                                      dtype)[:2], tokens, model)


def choices_differ_pct(chosen, reference_chosen) -> float:
    """Per cent of the (layer, token, slot) choices [L, B, T, K] that are
    not among the reference's choices for that token."""
    import numpy as np

    a = np.asarray(chosen)[..., :, None]
    b = np.asarray(reference_chosen)[..., None, :]
    return 100.0 * float(1.0 - (a == b).any(axis=-1).mean())


def float32_island_gaps(seen: dict, model: dict) -> dict:
    """How far a forward pass's float32 islands are from float32, token by
    token, whatever precision the products around them ran in. From what
    the pass itself saw (`reference_pass`, `program_pass`) the router's
    product, softmax and top-k are computed again in float32 (`highest`),
    and from its own final hidden state and unembedding matrix each
    token's negative log likelihood:

    - `router_logit_gap`: the largest distance of a router logit from the
      float32 product of the same input (a bfloat16 product: half a step
      of bfloat16 at the logit's size, 0.008 at 2 to 4);
    - `router_gate_gap`: the largest relative distance of a kept gate from
      the float32 softmax's (a bfloat16 softmax: a per cent);
    - `router_choices_differ_pct`: the choices that are not among the
      float32 top-k of the same input (exact ties apart, none);
    - `token_nll_gap`: the mean distance of a token's negative log
      likelihood from the float32 log-softmax of the float32 product (a
      float32 loss over logits rounded to bfloat16: the rounding of one
      logit of order 1, 1e-3; a bfloat16 log-softmax: the rounding of a
      log-sum-exp of 11, 2e-2).

    Call it in a program of its own: inside the pass's program the
    compiler would merge the second computation with the first."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    routing = seen["routing"]
    with jax.default_matmul_precision("highest"):
        logits = jnp.einsum("lbtd,lde->lbte", f32(routing["inputs"]),
                            f32(seen["router"]))
        kept, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     model["num_experts_per_tok"])
        if model["norm_topk_prob"]:
            kept = kept / jnp.sum(kept, axis=-1, keepdims=True)
        among = (routing["experts"][..., :, None]
                 == chosen[..., None, :]).any(axis=-1)
        want_nll = token_nll(f32(seen["hidden"]) @ f32(seen["head"]),
                             seen["targets"])
    return {"router_logit_gap": jnp.max(jnp.abs(f32(routing["logits"])
                                                - logits)),
            "router_gate_gap": jnp.max(jnp.abs(f32(routing["gates"]) - kept)
                                       / kept),
            "router_choices_differ_pct": 100.0 * (1.0 - jnp.mean(f32(among))),
            "token_nll_gap": jnp.mean(jnp.abs(f32(seen["nll"]) - want_nll))}


def reference_pass(params, tokens, model: dict, dtype="float32") -> dict:
    """What `float32_island_gaps` reads, of the reference's forward pass
    over tokens [B, T+1]: no gap at float32, and at "bfloat16" what the
    limits have to refuse."""
    logits, routing, hidden = reference_forward(params, tokens[:, :-1],
                                                model, dtype)
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    return {"routing": routing,
            "router": params["blocks"]["moe"]["router"].astype(dtype),
            "hidden": hidden, "head": head.astype(dtype),
            "nll": token_nll(logits, tokens[:, 1:]), "targets": tokens[:, 1:]}


def program_pass(params, batch: dict, cfg, mesh=None) -> dict:
    """What `float32_island_gaps` reads, of the program's forward pass
    over `batch`, put together from the program's own pieces. The step's
    loss is the mean of the same `lm.token_nll` over the same product,
    1,024 positions at a time."""
    import contextlib

    from ray_tpu.models import llama, lm, moe
    from ray_tpu.parallel.mesh import use_mesh

    inputs, targets = lm.split_lm_batch(batch)
    with use_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        x, _, routing = moe.hidden_states(params, inputs, cfg)
        hidden, head = llama.final_hidden(params, x, cfg)
        nll = lm.token_nll(llama.unembed(params, x, cfg), targets)
    return {"routing": routing, "router": params["blocks"]["moe"]["router"],
            "hidden": hidden, "head": head, "nll": nll, "targets": targets}


def loss_from_sums(sums: dict, model: dict, weights: dict) -> dict:
    """The three-term loss from `reference_sums` added over a batch's
    slices (numpy or jax arrays alike). The load-balancing loss of a
    layer is E · sum_e f_e · P_e, f_e the share of the N·K choices that
    fell on expert e and P_e its mean router probability (1 under uniform
    routing: the normalisation of megablocks, which OLMoE trained with);
    both router losses are averaged over the layers."""
    n = sums["tokens"]
    top_k, n_experts = model["num_experts_per_tok"], model["num_experts"]
    load = sums["count"] / (n * top_k)                        # [L, E]
    balance = (n_experts * (load * sums["prob"] / n).sum(axis=-1)).mean()
    z = (sums["z"] / n).mean()
    ce = sums["ce"] / n
    return {"loss": ce + weights["load_balancing_weight"] * balance
            + weights["z_loss_weight"] * z,
            "cross_entropy": ce, "load_balancing_loss": balance,
            "z_loss": z,
            "load_max_over_mean": (load.max(axis=-1) * n_experts).mean()}


def reference_loss(params, tokens, model: dict, weights: dict,
                   dtype="float32") -> dict:
    return loss_from_sums(reference_sums(params, tokens, model, dtype),
                          model, weights)


# ---------------------------------------------------------------- training

# Three limits, each set from two readings on the v5e at the published
# widths (PR 25; PERF.md §6 has every reading): the widest the program gave
# over its seeds, and what the reference gives below the precision the
# configuration states (bfloat16 products; float32 router, softmaxes,
# norms and loss), which has to come out as not correct. Below it are
# (a) the reference computed in bfloat16 throughout, its float32 islands
# gone, and (b) the reference in float32 with its weights rounded to the
# three mantissa bits of float8_e4m3, the nearest format below bfloat16.
#
# 1. The step program's own three-term loss on its first batch (at the
# seed's initial weights, before any update) against the float32
# reference's on the same batch. Over thirty readings the program
# differed by 1.2e-5 to 8.7e-4 at a loss of 11.1-11.5, either way (root
# mean square 3.3e-4; half a per cent of the tokens take another expert,
# below): the limit is 1.7 times the widest. (b) is off by 3.9e-4 to
# 9.2e-3 (eight readings, six outside); (a) by 3.6e-4 to 4.1e-3 (six,
# three outside). A mean over 32,768 tokens averages rounding away, so
# this limit is for what moves the loss: a wrong mask, a missing norm or
# loss term (0.02 each), renormalised gates, dropped tokens. Limits 2 and
# 3 are for precision.
TRAIN_LOSS_TOLERANCE = 1.5e-3
# 2. The share of the first slice's (token, slot) expert choices that are
# not among the float32 reference's for that token. The program (bf16
# activations into a float32 router) differed in 0.39-0.64% over
# thirty-two readings: near-ties that the bf16 input tips. (b): 4.6-5.9%,
# outside every time. (a): 0.57-0.83%, inside: its flips are the bf16
# input's too, which the program shares; limit 3 is for (a).
ROUTING_DIFFER_TOLERANCE_PCT = 1.0
# 3. `float32_island_gaps` of the first slice, token by token, in a
# program of their own. The program over ten seeds / (a) over six
# seeds of two sequences: router_logit_gap 0 (the two programs' float32
# products are the same to the bit; the float32 reference's own pass reads
# up to 5e-6 against itself) / 0.0078-0.0155; router_gate_gap 0 /
# 0.0117-0.0137; router_choices_differ_pct 0 / 0.22-0.37; token_nll_gap
# 1.8e-5-2.0e-5 / 0.0169-0.0176. Each limit lies between its two readings;
# token_nll_gap's leaves room for the other form the configuration's
# "bfloat16 products" admit: the compiler kept the logits in float32
# here, and a float32 loss over logits rounded to bfloat16 would read
# 1e-3. (b) has float32 islands and reads what the float32 reference
# reads: limits 1 and 2 are for (b).
FLOAT32_ISLAND_LIMITS = {"router_logit_gap": 1e-4, "router_gate_gap": 1e-4,
                         "router_choices_differ_pct": 0.02,
                         "token_nll_gap": 2e-3}


def seeded_params(cfg, seed: int, out_shardings=None):
    """The seed's initial weights, made on the device in one jitted call
    of the program's own `init_params`: what the trainer's `init_fn` and
    the reference check start from."""
    import jax

    from ray_tpu.models import moe

    kwargs = {} if out_shardings is None else {"out_shardings": out_shardings}
    return jax.jit(lambda key: moe.init_params(key, cfg), **kwargs)(
        jax.random.key(seed))


class TrainProgram:
    """The program's train step for one configuration on this process's
    devices, built through `train/spmd.compile_model_train`."""

    def __init__(self, model: dict, job: dict, devices, seed: int):
        import jax

        from ray_tpu.models import moe
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train.spmd import compile_model_train, default_optimizer

        self.jax, self.model, self.seed = jax, model, seed
        self.seq_len = job["seq_len"]
        self.global_batch = job["global_batch"]
        self.weights = job["router_losses"]
        self.cfg = program_config(model, self.weights, remat=job["remat"])
        mesh_axes = job.get("mesh") or {"dp": len(devices)}
        self.mesh = build_mesh(MeshConfig(**mesh_axes), devices=devices)
        self.program = compile_model_train(
            moe, self.cfg, self.mesh,
            optimizer=default_optimizer(total_steps=job["total_steps"]))
        self.batch_sharding = self.program.batch_sharding

    def init_state(self):
        return self.program.init_fn(self.jax.random.key(self.seed))

    def compile_step(self, state):
        """The step compiled ahead of time for the job's batch: the
        executable the loop calls, whose `memory_analysis()` sizes it."""
        import jax.numpy as jnp

        data = {"tokens": self.jax.ShapeDtypeStruct(
            (self.global_batch, self.seq_len + 1), jnp.int32,
            sharding=self.batch_sharding)}
        return self.program.step_fn.lower(state, data).compile()

    def put_batch(self, tokens):
        return {"tokens": self.jax.device_put(tokens, self.batch_sharding)}

    def check_against_reference(self, tokens, step_loss: float,
                                slice_size: int) -> dict:
        """The reference's three-term loss on the whole of the step
        program's first batch, at the seed's initial weights, against the
        loss the compiled step itself reported for that batch (its first
        step computes it before any update). The reference takes the
        batch `slice_size` sequences at a time and its sums are added up.
        Beside it, on the first slice: the share of the program's expert
        choices that are not the reference's, and how far the program's
        float32 islands are from float32 (`float32_island_gaps` of
        `program_pass`, in a program of its own). And what the
        program's own loss function counts of the whole batch's routing
        (`moe_dropped_pct`, `moe_load_max_over_mean` read these), the
        reference's count of the same, and what one step costs in the
        kernels whose roofline shares the benchmark reads."""
        import numpy as np

        from ray_tpu.models import moe
        from ray_tpu.parallel.mesh import use_mesh

        jax = self.jax
        params = seeded_params(self.cfg, self.seed,
                               self.program.state_sharding.params)

        def reference(p, b):
            logits, routing, _ = reference_forward(
                p, b["tokens"][:, :-1], self.model)
            return sums_of(logits, routing, b["tokens"],
                           self.model), routing["experts"]

        def program(p, b):
            return program_pass(p, b, self.cfg, self.mesh)

        gaps_of = jax.jit(lambda seen: float32_island_gaps(seen, self.model))
        reference = jax.jit(reference)
        total = differ = gaps = None
        for at in range(0, len(tokens), slice_size):
            part = self.put_batch(tokens[at:at + slice_size])
            sums, chosen = jax.device_get(reference(params, part))
            total = sums if total is None else {
                k: total[k] + sums[k] for k in sums}
            if differ is None:            # the first slice's routing
                seen = jax.jit(program)(params, part)
                differ = choices_differ_pct(jax.device_get(
                    seen["routing"]["experts"]), chosen)
                gaps = {k: float(v) for k, v in jax.device_get(
                    gaps_of(seen)).items()}
                del seen
        want = {k: float(v) for k, v in loss_from_sums(
            {k: np.asarray(v, np.float64) for k, v in total.items()},
            self.model, self.weights).items()}

        def program_aux(p, b):
            with use_mesh(self.mesh):
                return moe.loss_fn(p, b, self.cfg)[1]

        aux = {k: float(v) for k, v in jax.device_get(
            jax.jit(program_aux)(params, self.put_batch(tokens))).items()}
        step_tokens = self.global_batch * self.seq_len
        costs = {"experts": {"flops": experts_flops_per_token(self.model)
                             * step_tokens},
                 "flash_attention": flash_attention_cost(
                     self.model, self.global_batch, self.seq_len)}
        return {"program_loss": step_loss, "reference_loss": want["loss"],
                "reference": want, "program_routing": aux, "costs": costs,
                "sequences": len(tokens), "tolerance": TRAIN_LOSS_TOLERANCE,
                "choices_differ_pct": differ,
                "routing_tolerance_pct": ROUTING_DIFFER_TOLERANCE_PCT,
                "float32_island_gaps": gaps,
                "float32_island_limits": FLOAT32_ISLAND_LIMITS,
                "ok": bool(abs(step_loss - want["loss"])
                           <= TRAIN_LOSS_TOLERANCE
                           and differ <= ROUTING_DIFFER_TOLERANCE_PCT
                           and all(gaps[k] <= limit for k, limit
                                   in FLOAT32_ISLAND_LIMITS.items())
                           and aux["moe_dropped_frac"] == 0.0
                           and np.isfinite(want["loss"]))}


def build_train(model: dict, job: dict, devices, seed: int) -> TrainProgram:
    return TrainProgram(model, job, devices, seed)
