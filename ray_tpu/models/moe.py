"""Sparse Mixture-of-Experts transformer: OLMoE and Mixtral from one block.

Capability parity: the reference exposes expert parallelism only as vLLM
engine flags plus placement groups (SURVEY.md §2.13, `python/ray/llm/_internal/
serve/deployments/llm/vllm/vllm_models.py`) — it ships no MoE math. Here the
framework owns a TPU-first sparse-MoE layer:

- experts are STACKED (`[n_experts, ...]` leading dim; logical axis
  "expert": on a mesh with an `ep` axis the experts' weights, gradients and
  optimizer state are sharded over it, and the partitioner brings a
  layer's weights together for its grouped matmuls);
- the router runs in float32 (logits, softmax or sigmoid, top-k); the top-k
  gates are kept as they are or renormalised, as the published
  `norm_topk_prob` says (OLMoE: false; Mixtral: true), chosen with a bias
  and scaled where the model has them (`models/deepseek.py`);
- routing is DROPLESS, on every mesh: the (token, slot) pairs are sorted
  by expert, their rows gathered, one grouped matmul
  (`ops/grouped_matmul.py`: JAX's megablox Pallas kernel `gmm` on the tpu
  backend, forward and both backward products; `jax.lax.ragged_dot` on the
  cpu test backend) runs over the ragged groups for each of `wg`, `wu`,
  `wd`, and the outputs go back to token order, are scaled by their gates
  and summed per token. No `[., T, E, C]` tensor, no capacity, no token
  ever dropped, one path;
- load-balancing auxiliary loss + router z-loss, returned with the loss
  as `aux` so that a train step's metrics carry them;
- attention (with OLMoE's QK-norm where the block has the scales), norms
  and RoPE are reused from `ray_tpu.models.llama`.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import llama as _llama, lm
from ray_tpu.ops.expert_mlp import expert_mlp
from ray_tpu.ops.grouped_matmul import TILE_M, grouped_matmul
from ray_tpu.ops.pieces import pieces
from ray_tpu.parallel.mesh import constrain, current_mesh, logical_to_spec

Params = Any


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 8
    d_model: int = 4096
    d_ff: int = 14336                # per-expert SwiGLU hidden size
    n_experts: int = 8
    experts_per_token: int = 2       # top-k routing
    norm_topk_prob: bool = True      # renormalise the top-k gates to sum to 1
    router_scoring: str = "softmax"  # or "sigmoid" (DeepSeek-V3's scoring_func)
    routed_scaling_factor: float = 1.0   # the gates' factor after that
    qk_norm: bool = False            # RMSNorm on the q and k projections
    aux_loss_weight: float = 0.01    # load-balancing loss
    z_loss_weight: float = 1e-3      # router logit z-loss
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "auto"
    tie_embeddings: bool = False

    # llama-block compatibility
    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def q_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "MoEConfig":
        presets = {
            "mixtral-8x7b": dict(n_layer=32, n_head=32, n_kv_head=8,
                                 d_model=4096, d_ff=14336, n_experts=8,
                                 experts_per_token=2, vocab_size=32000,
                                 norm_topk_prob=True),
            # allenai/OLMoE-1B-7B-0125-Instruct config.json; QK-norm and
            # the two loss weights from the paper (arXiv:2409.02060)
            "olmoe-1b-7b": dict(n_layer=16, n_head=16, n_kv_head=16,
                                d_model=2048, d_ff=1024, n_experts=64,
                                experts_per_token=8, vocab_size=50304,
                                max_seq_len=4096, rope_theta=10000.0,
                                norm_eps=1e-5, norm_topk_prob=False,
                                qk_norm=True, tie_embeddings=False,
                                aux_loss_weight=0.01, z_loss_weight=1e-3),
            "moe-tiny": dict(n_layer=2, n_head=4, n_kv_head=2, d_model=128,
                             d_ff=256, n_experts=4, experts_per_token=2,
                             vocab_size=512, max_seq_len=128),
        }
        return cls(**{**presets[name], **overrides})


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: MoEConfig) -> Params:
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    pd = cfg.param_dtype
    D, Dh, E, F = cfg.d_model, cfg.head_dim, cfg.n_experts, cfg.d_ff
    kv_dim = cfg.n_kv_head * Dh
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(pd)

    def init_block(k):
        ks = jax.random.split(k, 8)
        attn = {
            "wq": norm(ks[0], (D, D)),
            "wk": norm(ks[1], (D, kv_dim)),
            "wv": norm(ks[2], (D, kv_dim)),
            "wo": norm(ks[3], (D, D), resid_std),
        }
        if cfg.qk_norm:
            attn["q_norm"] = {"scale": jnp.ones((D,), pd)}
            attn["k_norm"] = {"scale": jnp.ones((kv_dim,), pd)}
        return {
            "attn_norm": {"scale": jnp.ones((D,), pd)},
            "attn": attn,
            "mlp_norm": {"scale": jnp.ones((D,), pd)},
            "moe": {
                "router": norm(ks[4], (D, E)),
                "wg": norm(ks[5], (E, D, F)),
                "wu": norm(ks[6], (E, D, F)),
                "wd": norm(ks[7], (E, F, D), resid_std),
            },
        }

    blocks = jax.vmap(init_block)(jax.random.split(k_blocks, cfg.n_layer))
    params = {
        "wte": norm(k_emb, (cfg.vocab_size, D)),
        "blocks": blocks,
        "final_norm": {"scale": jnp.ones((D,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k_head, (D, cfg.vocab_size))
    return params


def param_logical_axes(cfg: MoEConfig) -> Params:
    attn = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": ("heads",)}
        attn["k_norm"] = {"scale": ("kv",)}
    block = {
        "attn_norm": {"scale": ("embed",)},
        "attn": attn,
        "mlp_norm": {"scale": ("embed",)},
        "moe": {
            "router": ("embed", None),       # tiny; replicated
            "wg": ("expert", "embed", "mlp"),
            "wu": ("expert", "embed", "mlp"),
            "wd": ("expert", "mlp", "embed"),
        },
    }
    block = jax.tree.map(lambda axes: ("layers",) + axes, block,
                         is_leaf=lambda x: isinstance(x, tuple))
    axes = {
        "wte": ("vocab", "embed"),
        "blocks": block,
        "final_norm": {"scale": ("embed",)},
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_specs(cfg: MoEConfig, rules=None) -> Params:
    return jax.tree.map(
        lambda axes: logical_to_spec(*axes, rules=rules),
        param_logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Sparse MoE layer
# ---------------------------------------------------------------------------

def _route(x2, router, cfg, bias=None):
    """x2 [N, D] -> (logits [N, E], scores [N, E], gates [N, K], experts
    [N, K]), all of it in float32: `Precision.HIGHEST`, because a float32
    product runs in bfloat16 passes on the TPU unless it is asked for.

    `cfg.router_scoring` is `softmax` over the experts (OLMoE, Mixtral) or
    an independent `sigmoid` an expert (DeepSeek-V3). With a `bias` [E]
    (DeepSeek-V3's `e_score_correction_bias`, `topk_method: noaux_tc`) the
    K experts are chosen by score + bias and weighted by the score alone.
    The chosen scores are renormalised where `cfg.norm_topk_prob` says so
    and scaled by `cfg.routed_scaling_factor`. `n_group: 1, topk_group: 1`
    make DeepSeek-V3's group limit a no-op; it is not built."""
    with jax.named_scope("moe_router"):
        logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        sigmoid = cfg.router_scoring == "sigmoid"
        if not sigmoid and cfg.router_scoring != "softmax":
            raise ValueError(f"router_scoring {cfg.router_scoring!r}")
        probs = (jax.nn.sigmoid(logits) if sigmoid
                 else jax.nn.softmax(logits, axis=-1))
        if bias is None:
            gates, experts = lax.top_k(probs, cfg.experts_per_token)
        else:
            _, experts = lax.top_k(probs + bias.astype(jnp.float32),
                                   cfg.experts_per_token)
            gates = jnp.take_along_axis(probs, experts, axis=-1)
        if cfg.norm_topk_prob:
            total = jnp.sum(gates, axis=-1, keepdims=True)
            # the published sigmoid router guards its sum, softmax's cannot
            # be zero
            gates = gates / (total + 1e-20 if sigmoid else total)
        if cfg.routed_scaling_factor != 1.0:
            gates = gates * cfg.routed_scaling_factor
        return logits, probs, gates, experts


def _router_losses(logits, probs, load, cfg: MoEConfig):
    """(load-balancing loss, z-loss). `load` [E]: the share of the N·K
    (token, slot) assignments each expert received. E · sum_e load_e ·
    mean_prob_e is 1 under uniform routing (megablocks' normalisation,
    which OLMoE trained with; Switch's form at K=1)."""
    with jax.named_scope("moe_router"):
        E = cfg.n_experts
        mean_prob = jnp.mean(probs.reshape(-1, E), axis=0)
        aux_loss = E * jnp.sum(load * mean_prob)
        z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        return aux_loss, z_loss


@jax.custom_vjp
def _permute_rows(x, perm, inverse):
    """x[perm] for a permutation `perm` of x's rows whose inverse is
    `inverse`: the backward pass is the gather `g[inverse]`, never the
    scatter-add that the transpose of a general gather would be."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute_rows.defvjp(_permute_fwd, _permute_bwd)


def _rows_times_experts(xs, w, group_sizes, first_expert):
    """`grouped_matmul` of the sorted rows xs [R, K] with the experts'
    matrices w. Rows and matrices of one dtype go as they are. Float32 rows
    against matrices held narrower (a served program whose activations stay
    float32, `models/kimi.py`) go as the two pieces of the matrices' dtype
    that add up to them (`ops/pieces.py`), a row's two side by side so that
    the groups stay sorted, and come back float32: one pass of the
    matrices, none of the rows' rounding in the result. The pieces and both
    pieces' products pass through HBM here: it is the form of the `cpu`
    backend, of many rows a group, and what `ops/expert_mlp.py` is tested
    against; a served program's few float32 rows a group take that kernel
    on the chip (`_one_kernel`), where the pieces never leave VMEM."""
    if xs.dtype == w.dtype:
        return grouped_matmul(xs, w, group_sizes, first_expert)
    both = grouped_matmul(
        pieces(xs, w.dtype, axis=1).reshape(-1, xs.shape[-1]), w,
        2 * group_sizes, first_expert, out_dtype=jnp.float32)
    return both[0::2] + both[1::2]


def _three_products(xs, wg, wu, wd, group_sizes, first_expert):
    """The experts' MLP of the sorted rows as grouped matmuls: three for a
    SwiGLU, two for the form without a gate matrix (`wg` None), relu(x
    W_up)^2 W_down (Nemotron-H's `relu2`)."""
    if wg is None:
        h = jnp.square(jax.nn.relu(
            _rows_times_experts(xs, wu, group_sizes, first_expert)))
    else:
        g = _rows_times_experts(xs, wg, group_sizes, first_expert)
        u = _rows_times_experts(xs, wu, group_sizes, first_expert)
        h = jax.nn.silu(g) * u
    return _rows_times_experts(h, wd, group_sizes, first_expert)


def _one_kernel(xs, w) -> bool:
    """Whether the experts' MLP (either form) goes as `ops/expert_mlp.py`'s
    one kernel and not as grouped matmuls: on the tpu backend, float32 rows
    against matrices held narrower (`_rows_times_experts`' two-piece case,
    where the three calls write and read back both pieces' float32
    products) and fewer than `TILE_M` rows a group (`grouped_matmul`'s own
    few-rows rule; the kernel holds an expert's weight tile while its rows
    go by, which many rows an expert would make the MXU's work). Rows in
    the matrices' dtype (`models/deepseek.py`, training) keep the three
    calls, which have a backward pass."""
    return (jax.default_backend() == "tpu" and xs.dtype == jnp.float32
            and w.dtype != xs.dtype and xs.shape[0] < w.shape[0] * TILE_M)


# what a zero-compute expert (LongCat-Flash's `zero_expert_type`) returns for
# its input: nothing else is known here, and another type is refused by name
ZERO_EXPERT_TYPES = ("identity",)


def _experts(x, gates, experts, wg, wu, wd, cfg: MoEConfig,
             first_expert=None, zero_experts: int = 0,
             zero_type: str = "identity"):
    """The experts' part of the layer for the tokens x [B,T,D] (`gates`,
    `experts` [B,T,K], the ids counted over all E): sort the (token, slot)
    pairs by expert, one grouped matmul over the ragged groups for each
    weight, and back to token order, scaled by the gates and summed per
    token. Rows move by permutation gathers in both directions of both
    passes. `wg`, `wu` [E',D,F'], `wd` [E',F',D] may be a shard: experts
    `first_expert`..+E' of the E and F' of an expert's columns. The
    grouped matmul then leaves the other experts' rows unwritten, so they
    are zeroed on the way in (for the backward pass) and out, and the
    result is this shard's part of the sum.

    `wg` None is an expert of two matrices, relu(x W_up)^2 W_down; x is then
    whatever the experts read (Nemotron-H's are [latent, F]: x is the latent
    and so is the result, a quarter of the hidden size's bytes a pair).
    Which branch runs which widths: `_one_kernel` (the chip's few float32
    rows a group: Kimi 1,024, Keye and Kanana 768, Solar 1,280, Nemotron-H
    2,688 in its two-matrix form, LongCat-Flash 6,144 x 2,048) or the
    grouped matmuls (the `cpu` backend, training, many rows a group).

    `zero_experts` Z > 0: the last Z of the E ids are zero-compute experts of
    `zero_type` (`identity`: such an expert returns its input, so all of a
    token's pairs that chose one are the sum of their gates times x). They
    have no matrices: the stacks hold ids before E - Z, their pairs take the
    road an absent expert's take (sorted, given no row of any expert, zeroed
    on the way out) and their term is added under the scope `moe_zero`. With
    0 nothing of this is traced."""
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    N = B * T
    if zero_experts:
        if zero_type not in ZERO_EXPERT_TYPES:
            raise ValueError(
                f"zero-compute experts of type {zero_type!r}: this layer "
                f"knows {ZERO_EXPERT_TYPES}")
        if first_expert is None:        # the stacks end before the zero ids
            first_expert = jnp.int32(0)

    with jax.named_scope("moe_dispatch"):
        flat = experts.reshape(N * K)                  # pair (n, k) at n*K+k
        order = jnp.argsort(flat)                      # stable: by expert
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(N * K, dtype=order.dtype))
        group_sizes = jnp.sum(jax.nn.one_hot(flat, E, dtype=jnp.int32),
                              axis=0)
        pairs = jnp.broadcast_to(x.reshape(N, 1, D), (N, K, D)).reshape(
            N * K, D)
        xs = _permute_rows(pairs, order, inverse)      # rows by expert
        if first_expert is not None:
            local = flat[order] - first_expert
            mine = ((local >= 0) & (local < wu.shape[0]))[:, None]
            xs = jnp.where(mine, xs, 0)

    with jax.named_scope("moe_experts"):
        form = expert_mlp if _one_kernel(xs, wu) else _three_products
        ys = form(xs, wg, wu, wd, group_sizes, first_expert)

    with jax.named_scope("moe_dispatch"):
        if first_expert is not None:
            ys = jnp.where(mine, ys, 0)
        back = _permute_rows(ys, inverse, order).reshape(N, K, D)
        # float32 rows keep their gates whole; a product of two bf16
        # operands is exact in float32 whatever the precision says
        out = jnp.einsum("nkd,nk->nd", back,
                         gates.reshape(N, K).astype(cfg.dtype),
                         precision=(lax.Precision.HIGHEST
                                    if back.dtype == jnp.float32 else None),
                         preferred_element_type=jnp.float32)
    if zero_experts:
        with jax.named_scope("moe_zero"):
            zero_gate = jnp.sum(
                jnp.where(experts.reshape(N, K) >= E - zero_experts,
                          gates.reshape(N, K).astype(jnp.float32), 0.0),
                axis=-1, keepdims=True)
            out = out + zero_gate * x.reshape(N, D).astype(jnp.float32)
    return out.reshape(B, T, D)


def _experts_on_mesh(x, gates, experts, wg, wu, wd, cfg: MoEConfig, mesh):
    """`_experts` inside a sharded program. The TPU compiler cannot
    partition a Mosaic kernel on its own, so each device runs the sort and
    the grouped matmuls on its own tokens (whole over `ep` and `tp`, as the
    mesh rules lay activations out) against its own shard of the experts:
    E/ep of them, F/tp of an expert's columns, the `fsdp` shards brought
    together at the boundary. The parts are summed over `ep` and `tp`."""
    from jax import shard_map

    def mesh_axes(logical):
        return tuple(a for part in logical_to_spec(logical)
                     for a in ((part,) if isinstance(part, str) else part))

    tokens = logical_to_spec("batch", "seq", None)
    up = logical_to_spec("expert", None, "mlp")
    down = logical_to_spec("expert", "mlp", None)
    over_experts = mesh_axes("expert")
    summed = over_experts + mesh_axes("mlp")

    def body(x, gates, experts, wg, wu, wd):
        first = None
        if wg.shape[0] < cfg.n_experts:
            first = lax.axis_index(over_experts) * wg.shape[0]
        out = _experts(x, gates, experts, wg, wu, wd, cfg, first)
        return lax.psum(out, summed) if summed else out

    return shard_map(body, mesh=mesh,
                     in_specs=(tokens, tokens, tokens, up, up, down),
                     out_specs=tokens, check_vma=False)(
        x, gates, experts, wg, wu, wd)


def moe_layer(x, p, cfg: MoEConfig):
    """Sparse SwiGLU MoE, x [B,T,D] -> (out [B,T,D], aux: the `AUX_KEYS`
    scalars and `routing`, what the router saw and gave). Dropless, on
    every mesh: float32 router, then `_experts`."""
    B, T, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    N = B * T
    logits, probs, gates, experts = _route(x.reshape(N, D), p["router"], cfg)
    gates, experts = gates.reshape(B, T, K), experts.reshape(B, T, K)
    weights = [lm.weight(p[k], cfg.dtype) for k in ("wg", "wu", "wd")]
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        out = _experts(x, gates, experts, *weights, cfg)
    else:
        out = _experts_on_mesh(x, gates, experts, *weights, cfg, mesh)
    out = out.astype(cfg.dtype)

    with jax.named_scope("moe_dispatch"):
        sizes = jnp.sum(jax.nn.one_hot(experts.reshape(N * K), E,
                                       dtype=jnp.int32),
                        axis=0).astype(jnp.float32)
    aux_loss, z_loss = _router_losses(logits, probs, sizes / (N * K), cfg)
    return out, {"aux_loss": aux_loss, "z_loss": z_loss,
                 "dropped_frac": (N * K - jnp.sum(sizes)) / (N * K),
                 "load_max_over_mean": jnp.max(sizes) * E / (N * K),
                 "routing": {"inputs": x,
                             "logits": logits.reshape(B, T, E),
                             "gates": gates, "experts": experts}}


AUX_KEYS = ("aux_loss", "z_loss", "dropped_frac", "load_max_over_mean")


def _block(carry, bp, cfg: MoEConfig):
    x, aux_acc = carry
    x = _llama.attention_residual(x, bp, cfg)
    with jax.named_scope("mlp"):
        moe_out, aux = moe_layer(
            _llama.rms_norm(x, bp["mlp_norm"], cfg.norm_eps), bp["moe"], cfg)
        x = x + moe_out
        x = constrain(x, "batch", "seq", "embed")
    return (x, {k: aux_acc[k] + aux[k] for k in AUX_KEYS}), aux["routing"]


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def hidden_states(params: Params, tokens: jax.Array, cfg: MoEConfig):
    """tokens [B,T] -> (final hidden [B,T,D] before the last norm, the
    layers' mean aux dict, every layer's `routing` stacked on a leading
    layer axis)."""
    x = _llama.embed(params, tokens, cfg)
    aux0 = {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}

    block_fn = partial(_block, cfg=cfg)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    with jax.named_scope("layers"):     # the scan's own slices and stacks
        (x, aux), routing = lax.scan(block_fn, (x, aux0), params["blocks"])
    return x, {k: v / cfg.n_layer for k, v in aux.items()}, routing


def forward(params: Params, tokens: jax.Array, cfg: MoEConfig,
            return_aux: bool = False):
    x, aux, _ = hidden_states(params, tokens, cfg)
    logits = _llama.unembed(params, x, cfg)
    return (logits, aux) if return_aux else logits


def routing(params: Params, tokens: jax.Array, cfg: MoEConfig) -> dict:
    """tokens [B,T] -> what every layer's router saw and gave: `inputs`
    [L,B,T,D] (the normed residual stream, in the compute dtype), `logits`
    [L,B,T,E] and `gates` [L,B,T,K] float32, `experts` [L,B,T,K] int32.
    What a reference's routing is compared with, and what shows that the
    router ran in float32 (recompute `logits` from `inputs`)."""
    return hidden_states(params, tokens, cfg)[2]


def loss_fn(params: Params, batch: dict, cfg: MoEConfig):
    """(cross-entropy + aux_loss_weight · load-balancing loss +
    z_loss_weight · router z-loss, aux): `aux` holds what a MoE job
    watches and `train/spmd.compile_train` adds to the step's metrics."""
    inputs, targets = lm.split_lm_batch(batch)
    x, aux, _ = hidden_states(params, inputs, cfg)
    ce = lm.chunked_cross_entropy(*_llama.final_hidden(params, x, cfg),
                                  targets)
    loss = (ce + cfg.aux_loss_weight * aux["aux_loss"]
            + cfg.z_loss_weight * aux["z_loss"])
    return loss, {"router_aux_loss": aux["aux_loss"],
                  "router_z_loss": aux["z_loss"],
                  "moe_dropped_frac": aux["dropped_frac"],
                  "moe_load_max_over_mean": aux["load_max_over_mean"]}


def _attn_params(cfg: MoEConfig) -> int:
    D = cfg.d_model
    kv_dim = cfg.n_kv_head * cfg.head_dim
    qk_norm = D + kv_dim if cfg.qk_norm else 0
    return D * D * 2 + D * kv_dim * 2 + qk_norm + 2 * D   # + the two norms


def num_params(cfg: MoEConfig) -> int:
    D, F, L, V, E = (cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size,
                     cfg.n_experts)
    per_block = _attn_params(cfg) + D * E + E * 3 * D * F
    total = V * D + L * per_block + D
    if not cfg.tie_embeddings:
        total += D * V
    return total


def active_params(cfg: MoEConfig) -> int:
    """Params touched per token (experts_per_token of n_experts)."""
    D, F, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    K = cfg.experts_per_token
    per_block = _attn_params(cfg) + D * cfg.n_experts + K * 3 * D * F
    total = cfg.vocab_size * D + L * per_block + D
    if not cfg.tie_embeddings:
        total += D * cfg.vocab_size
    return total
