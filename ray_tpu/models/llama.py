"""LLaMA model family in pure JAX, designed TPU-first.

Capability parity note: the reference serves LLaMA-family checkpoints through
vLLM (`python/ray/llm/_internal/serve/deployments/llm/vllm/`, SURVEY.md §2.5)
but ships no model math of its own. Here the framework owns the model: RMSNorm,
rotary embeddings, grouped-query attention, SwiGLU — all written the XLA way:

- stacked blocks (leading `n_layer` dim) + one `lax.scan` over them: one
  compiled block, O(1) compile time in depth;
- bfloat16 compute on the MXU, float32 params/softmax/reductions;
- logical-axis sharding annotations so the same code runs dp/fsdp/tp/sp
  sharded under any mesh from `ray_tpu.parallel.mesh.build_mesh`;
- GQA: `n_kv_head <= n_head` with K/V broadcast done via reshape (free under
  XLA) rather than materialized repeats;
- `jax.checkpoint` remat per block;
- every part of a step program sits in a `jax.named_scope` (`embed`, `ln`,
  `attn`, `mlp`, `unembed_loss`, `layers`, `weights_cast`: the names
  `models/gpt2.py` uses), so a device trace names its time.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm
from ray_tpu.parallel.mesh import constrain, logical_to_spec

Params = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 32
    n_head: int = 32
    n_kv_head: int = 32              # < n_head => grouped-query attention
    d_model: int = 4096
    d_ff: int = 11008                # SwiGLU hidden size
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    attn_impl: str = "auto"          # auto | dense | flash | ring | ulysses

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def q_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "LlamaConfig":
        presets = {
            "llama2-7b": dict(n_layer=32, n_head=32, n_kv_head=32,
                              d_model=4096, d_ff=11008, vocab_size=32000),
            "llama2-13b": dict(n_layer=40, n_head=40, n_kv_head=40,
                               d_model=5120, d_ff=13824, vocab_size=32000),
            "llama3-8b": dict(n_layer=32, n_head=32, n_kv_head=8,
                              d_model=4096, d_ff=14336, vocab_size=128256,
                              rope_theta=500000.0, max_seq_len=8192),
            "tinyllama-1.1b": dict(n_layer=22, n_head=32, n_kv_head=4,
                                   d_model=2048, d_ff=5632, vocab_size=32000),
            "llama-tiny": dict(n_layer=2, n_head=4, n_kv_head=2, d_model=128,
                               d_ff=352, vocab_size=512, max_seq_len=128),
        }
        return cls(**{**presets[name], **overrides})


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: LlamaConfig) -> Params:
    k_emb, k_head, k_blocks = jax.random.split(key, 3)
    pd = cfg.param_dtype
    D, Dh = cfg.d_model, cfg.head_dim
    kv_dim = cfg.n_kv_head * Dh
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)

    def norm(k, shape, s=std):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(pd)

    def init_block(k):
        ks = jax.random.split(k, 7)
        return {
            "attn_norm": {"scale": jnp.ones((D,), pd)},
            "attn": {
                "wq": norm(ks[0], (D, D)),
                "wk": norm(ks[1], (D, kv_dim)),
                "wv": norm(ks[2], (D, kv_dim)),
                "wo": norm(ks[3], (D, D), resid_std),
            },
            "mlp_norm": {"scale": jnp.ones((D,), pd)},
            "mlp": {
                "wg": norm(ks[4], (D, cfg.d_ff)),
                "wu": norm(ks[5], (D, cfg.d_ff)),
                "wd": norm(ks[6], (cfg.d_ff, D), resid_std),
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


def param_logical_axes(cfg: LlamaConfig) -> Params:
    block = {
        "attn_norm": {"scale": ("embed",)},
        "attn": {
            "wq": ("embed", "heads"),
            "wk": ("embed", "kv"),
            "wv": ("embed", "kv"),
            "wo": ("heads", "embed"),
        },
        "mlp_norm": {"scale": ("embed",)},
        "mlp": {
            "wg": ("embed", "mlp"),
            "wu": ("embed", "mlp"),
            "wd": ("mlp", "embed"),
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


def param_specs(cfg: LlamaConfig, rules=None) -> Params:
    return jax.tree.map(
        lambda axes: logical_to_spec(*axes, rules=rules),
        param_logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


# ---------------------------------------------------------------------------
# Building blocks (reused by ray_tpu.models.moe)
# ---------------------------------------------------------------------------

def rms_norm(x, p, eps: float):
    with jax.named_scope("ln"):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
        return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def rope_freqs(positions: jax.Array, head_dim: int, theta: float):
    """positions [...,T] int32 -> (cos, sin) each [...,T, head_dim/2] f32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [..., T, n_head, head_dim]; cos/sin broadcastable [..., T, 1, hd/2]."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def attention(x, p, cfg) -> jax.Array:
    """Causal GQA with RoPE. x [B,T,D]; p has wq/wk/wv/wo and, for a
    model with QK-norm (OLMoE), `q_norm`/`k_norm`: an RMSNorm over the
    whole projection, before it is split into heads and before RoPE."""
    B, T, D = x.shape
    H, KV, Dh = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    q = x @ lm.weight(p["wq"], cfg.dtype)
    k = x @ lm.weight(p["wk"], cfg.dtype)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(B, T, H, Dh)
    k = k.reshape(B, T, KV, Dh)
    v = (x @ lm.weight(p["wv"], cfg.dtype)).reshape(B, T, KV, Dh)

    cos, sin = rope_freqs(jnp.arange(T), Dh, cfg.rope_theta)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    # GQA broadcast: [B,T,KV,Dh] -> [B,T,H,Dh] view; XLA fuses the broadcast
    # into the attention einsum, no materialized repeat.
    if KV != H:
        k = jnp.broadcast_to(k[:, :, :, None], (B, T, KV, cfg.q_per_kv, Dh)
                             ).reshape(B, T, H, Dh)
        v = jnp.broadcast_to(v[:, :, :, None], (B, T, KV, cfg.q_per_kv, Dh)
                             ).reshape(B, T, H, Dh)

    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q = constrain(q, "batch", "heads", "seq", None)
    k = constrain(k, "batch", "heads", "seq", None)
    v = constrain(v, "batch", "heads", "seq", None)

    impl = lm.resolve_attn_impl(cfg.attn_impl, T)
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention_on_mesh

        out = flash_attention_on_mesh(q, k, v, True)
    elif impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        out = ring_attention(q, k, v, causal=True)
    elif impl == "ulysses":
        from ray_tpu.ops.ring_attention import ulysses_attention

        out = ulysses_attention(q, k, v, causal=True)
    else:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32)
        scores = scores / math.sqrt(Dh)
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
    return out @ lm.weight(p["wo"], cfg.dtype)


def swiglu(x, p, cfg) -> jax.Array:
    g = x @ lm.weight(p["wg"], cfg.dtype)
    u = x @ lm.weight(p["wu"], cfg.dtype)
    h = jax.nn.silu(g) * u
    h = constrain(h, "batch", "seq", "mlp")
    return h @ lm.weight(p["wd"], cfg.dtype)


def attention_residual(x, bp, cfg) -> jax.Array:
    """x + attention(norm(x)): the first half of a block, under the `attn`
    scope (each residual add belongs to the scope of what it adds)."""
    with jax.named_scope("attn"):
        x = x + attention(rms_norm(x, bp["attn_norm"], cfg.norm_eps),
                          bp["attn"], cfg)
        return constrain(x, "batch", "seq", "embed")


def _block(x, bp, cfg):
    x = attention_residual(x, bp, cfg)
    with jax.named_scope("mlp"):
        x = x + swiglu(rms_norm(x, bp["mlp_norm"], cfg.norm_eps),
                       bp["mlp"], cfg)
        x = constrain(x, "batch", "seq", "embed")
    return x


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def embed(params: Params, tokens: jax.Array, cfg) -> jax.Array:
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(cfg.dtype)
        return constrain(x, "batch", "seq", "embed")


def final_hidden(params: Params, x: jax.Array, cfg) -> tuple:
    """(the final norm of x, the unembedding matrix [D, V] in the compute
    dtype): what `unembed` multiplies and the fused loss takes apart."""
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        head = params["wte"].T if cfg.tie_embeddings else params["lm_head"]
        return x, lm.weight(head, cfg.dtype)


def unembed(params: Params, x: jax.Array, cfg) -> jax.Array:
    x, head = final_hidden(params, x, cfg)
    with jax.named_scope("unembed_loss"):
        return constrain(x @ head, "batch", "seq", "vocab")


def hidden_states(params: Params, tokens: jax.Array,
                  cfg: LlamaConfig) -> jax.Array:
    """tokens [B,T] int32 -> final hidden [B,T,D] (before the final norm)."""
    x = embed(params, tokens, cfg)

    block_fn = partial(_block, cfg=cfg)
    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    with jax.named_scope("layers"):     # the scan's own slices and stacks
        x, _ = lax.scan(lambda c, bp: (block_fn(c, bp), None), x,
                        params["blocks"])
    return x


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig) -> jax.Array:
    """tokens [B,T] int32 -> logits [B,T,vocab] (compute dtype)."""
    return unembed(params, hidden_states(params, tokens, cfg), cfg)


def loss_fn(params: Params, batch: dict, cfg: LlamaConfig) -> jax.Array:
    inputs, targets = lm.split_lm_batch(batch)
    x = hidden_states(params, inputs, cfg)
    return lm.chunked_cross_entropy(*final_hidden(params, x, cfg), targets)


def num_params(cfg: LlamaConfig) -> int:
    D, F, L, V = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size
    kv_dim = cfg.n_kv_head * cfg.head_dim
    per_block = D * D * 2 + D * kv_dim * 2 + 3 * D * F + 2 * D
    total = V * D + L * per_block + D
    if not cfg.tie_embeddings:
        total += D * V
    return total
