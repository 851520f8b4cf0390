"""MiMo-V2's layers for serving: five sliding-window softmax layers to each
global one, the two kinds with key-value heads of unequal number and every
key wider than its value, a learned sink a head in the sliding layers, and an
expert block of sigmoid-routed experts with no shared one.

What is served is `XiaomiMiMo/MiMo-V2.5`'s language model (`model_type:
mimo_v2`; preset `mimo-v2.5`): 48 layers, d = 4,096, RMSNorm eps 1e-5, no
bias, untied head. `hybrid_layer_pattern` is 0 (global) at layers 0, 5, 11,
17, .. and 1 (a window of 128) elsewhere; the first layer's MLP dense and the
other 47 sparse:

    u  = RMSNorm_a(x);  x  = x + Attn_l(u)                 # pre-norm
    h  = RMSNorm_m(x);  x' = x + MLP_l(h)
    final RMSNorm, head, logits float32

    Attn_l (64 query heads; a q and a k head 192 lanes, a v head 128):
      [q | k | v] = u W_qkv, one fused matrix a layer: [4096, 13568] in a
          global layer (4 key-value heads, 16 queries each), [4096, 14848]
          in a sliding one (8, 8 queries each)
      the first int(192 x 0.334) = 64 lanes of q and k rotated, the other
          128 pass; theta 1e7 in a global layer, 1e4 in a sliding one
      v = 0.707 v (`attention_value_scale`)
      s_t = q . k_t / sqrt(192); o = sum_t p_t v_t [64, 128]; W_o [8192, d]
      global layer: t <= pos, p = softmax(s)
      sliding layer: pos - 128 < t <= pos, and a learned b_h a head:
          p_t = exp(s_t - m) / (exp(b_h - m) + sum_t' exp(s_t' - m)),
          m = max(b_h, max_t s_t): the sink takes probability and weighs no
          value

    MLP_0 = SwiGLU 4096 -> 16384 -> 4096
    MLP_l, l >= 1 (256 routed SwiGLU experts 4096 -> 2048 -> 4096, 8 a
      token, no shared expert, no scaling factor):
      s = sigmoid(h W_r), float32; the 8 largest of s + bias chosen;
      g_k = s_{e_k} / (sum_j s_{e_j} + 1e-20)               (`moe._route`)
      out = sum_k g_k SwiGLU^(e_k)(h)

**Four cache leaves of four shapes.** A bf16 leaf `[.., T, 192]` is tiled to
256 lanes in HBM, a third more bytes held and read a key, so the keys hold
the positions on the lanes, 192 on the sublanes, and the values a position a
row: a global layer's `k` [global layers, slots, 4, 192, T] and `v` [global
layers, slots, 4, T, 128] (`CACHE_TOKEN_AXIS` 4 and 3), and a sliding
layer's rings `wk` [sliding layers, slots, 8, 192, W] and `wv` [sliding
layers, slots, 8, W, 128], position p at row (or lane) p mod W, the keys
stored rotated (`models/exaone.py` has the ring's account; the rings are the
family's `CACHE_STATE`, which the pool keeps as a snapshot beside the global
layers' rows by the block). Both products then lie as the MXU takes them:
q [G, R, 192] x k [G, 192, block] and p [G, R, block] x v [G, block, 128]
(`ops/gqa_attend.py`, which reads the layout off the leaves).

**Two stacks of attention weights.** The kinds' fused matrices differ in
shape, so `attn_g` [global layers, ...] and `attn_s` [sliding layers, ...]
(with `sink` [64] float32) stand apart; a layer's kind is static in the
program (which stack and which pair of leaves it indexes, its theta, its
heads, whether it has a sink), not a branch on the device.

**The chip's share** is `models/kimi.py`'s: `experts_held` E' and
`first_expert` say which of the E experts of every sparse layer this
replica holds; the router keeps its E outputs and its K a token; a pair
whose expert is absent adds nothing; `vocab_size` rows of the table and of
the head are this chip's slice; the held experts of all sparse layers are
one stack `[sparse layers x E', ...]` that no loop slices.

Both programs are one function (`models/exaone.py`'s, line for line where
the layers allow): `decode_step` is every slot's first lane through the
layers, all slots at once (the rows and the rings through
`ops/gqa_attend.py`, written by `ops/rows_write.py`), and `prefill_chunk`
that plus a slot's further lanes for the slots that prefill
(`lm.each_slot`: a global layer a block of positions at a time, a sliding
layer the ring as it stood and the chunk's own keys in a band, the ring
written after it is read; the MLPs take those lanes as rows of the first
lanes' call, `lm.all_lanes`).

The weights exist only in the dtype the replica holds them; float32 are the
norms' scales, the sinks, the router and its bias, and so are the residual
stream, everything projected, the rotation, the value's scale, the router
and its sigmoid, the gates and the logits. A product's operands are bf16,
the weight as it is held and the activation as the two bf16 pieces that add
up to it (`lm.dot`, `moe._experts` for float32 rows, `ops/gqa_attend.py` for
a float32 q); the rows and the rings are bf16.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm, moe as _moe
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs
from ray_tpu.ops.gqa_attend import gqa_attend, read_positions
from ray_tpu.ops.rows_write import rows_write

Params = Any

SLIDING, GLOBAL = "sliding_attention", "full_attention"
# `hybrid_layer_pattern`: 0 at layers 0, 5, 11, 17, .. 47, 1 elsewhere
_PUBLISHED_LAYERS = tuple(
    GLOBAL if l == 0 or l % 6 == 5 else SLIDING for l in range(48))


@dataclasses.dataclass(frozen=True)
class MimoConfig:
    vocab_size: int = 152576
    layer_types: tuple = _PUBLISHED_LAYERS
    sliding_window: int = 128
    n_dense_layer: int = 1           # moe_layer_freq: layer 0 alone is dense
    d_model: int = 4096
    n_head: int = 64
    n_kv_head: int = 4               # a global layer's
    swa_n_kv_head: int = 8           # a sliding layer's
    head_dim: int = 192              # a q's and a k's
    v_head_dim: int = 128
    rotary_dim: int = 64             # int(192 x partial_rotary_factor 0.334)
    rope_theta: float = 1e7          # a global layer's
    swa_rope_theta: float = 1e4
    value_scale: float = 0.707       # attention_value_scale
    d_ff: int = 16384                # intermediate_size: the dense SwiGLU
    d_ff_expert: int = 2048          # moe_intermediate_size
    n_experts: int = 256             # what the router scores
    experts_held: int = 256          # E': what this replica holds of them
    first_expert: int = 0
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0   # published null
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        assert self.layer_types and set(self.layer_types) <= {
            SLIDING, GLOBAL}, self.layer_types
        assert 0 <= self.n_dense_layer <= self.n_layer
        assert self.n_head % self.n_kv_head == 0
        assert self.n_head % self.swa_n_kv_head == 0
        assert self.rotary_dim % 2 == 0 and self.rotary_dim <= self.head_dim
        # a leaf's layout is read off its shape (`ops/rows_write.py`)
        assert self.head_dim != self.v_head_dim
        assert (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts)

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def kv_heads(self, kind: str) -> int:
        return self.swa_n_kv_head if kind == SLIDING else self.n_kv_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "MimoConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # XiaomiMiMo/MiMo-V2.5 config.json: the defaults (the three drafting
    # layers and the vision and audio towers are not served)
    "mimo-v2.5": dict(),
    "mimo-tiny": dict(
        vocab_size=512,
        layer_types=(GLOBAL, SLIDING, SLIDING, GLOBAL, SLIDING),
        sliding_window=16, d_model=64, n_head=8, n_kv_head=2,
        swa_n_kv_head=4, head_dim=24, v_head_dim=16, rotary_dim=8,
        d_ff=128, d_ff_expert=32, n_experts=8, experts_held=8,
        experts_per_token=3, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): a global layer's keys hold a
# value a token along their lanes, axis 4, and its values along axis 3; a
# sliding layer's rings hold a slot's state, with no token axis: the last
# `sliding_window` positions
CACHE_TOKEN_AXIS = {"k": 4, "v": 3}
CACHE_STATE = ("wk", "wv")

# the columns of the cache's `counts` leaf: `exaone.COUNTS`, column for
# column (the per-layer readers know them by name; the positions attended
# and read are a global layer's; the rings' live rows a step over the
# sliding layers, at most `sliding_window` a lane a layer)
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions",
          "expert_rows_all", "window_rows_read")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads, by `models/exaone.py`'s account (PERF.md, PR
# 59): every matrix N(0, 0.02), a routed expert's second matrix among them
# (a thirty-second of the pairs are held, and at a down projection's spread
# what they add would show in no logit); the other projections back into the
# stream, W_o and the dense MLP's second, 0.02 / sqrt(2 n_layer). MiMo has
# no norm a head: a normed u of size 1 a lane through W_qkv at 0.02 gives q
# and k lanes of 0.02 sqrt(4096) = 1.28 and a score a spread of 1.28^2 =
# 1.6 whatever the stream is, which W_o's spread keeps from collapsing as
# K-EXAONE's did. The token table 0.3 and the selection bias 0.02 by
# `models/kimi.py`'s argument. The sink a head N(SINK_MEAN, 1): a window's
# 128 scores of spread 1.6 sum to exp about 6.2 in the softmax's denominator,
# so a sink of 4 takes a tenth of a query's probability and one of 6 half:
# it shows in every logit, as a trained sink's does, and differs by head.
EMBED_STD, ROUTER_BIAS_STD, SINK_MEAN = 0.3, 0.02, 4.0


def _out_std(cfg: MimoConfig) -> float:
    return 0.02 / math.sqrt(2 * cfg.n_layer)


def _qkv_widths(cfg: MimoConfig, kind: str) -> tuple:
    """How many columns of a layer's fused W_qkv are q's, k's and v's."""
    G = cfg.kv_heads(kind)
    return cfg.n_head * cfg.head_dim, G * cfg.head_dim, G * cfg.v_head_dim


def _attn_params(key, cfg: MimoConfig, kind: str) -> Params:
    ks = jax.random.split(key, 3)
    pd, D = cfg.param_dtype, cfg.d_model
    out = {"norm": lm.ones(D),
           # `attention_projection_layout: fused_qkv`: [q | k | v]
           "wqkv": lm.normal(ks[0], (D, sum(_qkv_widths(cfg, kind))), 0.02,
                             pd),
           "wo": lm.normal(ks[1], (cfg.n_head * cfg.v_head_dim, D),
                           _out_std(cfg), pd)}
    if kind == SLIDING:              # add_swa_attention_sink_bias
        out["sink"] = SINK_MEAN + lm.normal(ks[2], (cfg.n_head,), 1.0,
                                            jnp.float32)
    return out


def _swiglu_params(key, cfg: MimoConfig, width: int) -> Params:
    k_in, k_out = jax.random.split(key)
    pd, D = cfg.param_dtype, cfg.d_model
    # gate and up side by side: one product
    return {"w_in": lm.normal(k_in, (D, 2 * width), 0.02, pd),
            "w_out": lm.normal(k_out, (width, D), _out_std(cfg), pd)}


def _expert_params(key, cfg: MimoConfig) -> Params:
    """The held experts' matrices: expert e's from `fold_in(key, e)` and
    nothing else, so that every share of a layer holds the same expert e."""
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff_expert

    def one(e):
        ks = jax.random.split(jax.random.fold_in(key, e), 3)
        return {"wg": lm.normal(ks[0], (D, F), 0.02, pd),
                "wu": lm.normal(ks[1], (D, F), 0.02, pd),
                "wd": lm.normal(ks[2], (F, D), 0.02, pd)}

    # a loop, not `vmap`: one expert's matrices are the program (`kimi`)
    return lax.map(one, cfg.first_expert + jnp.arange(cfg.experts_held))


def _attn_part(kind: str) -> str:
    return "attn_s" if kind == SLIDING else "attn_g"


def _init_layer(key: jax.Array, l, cfg: MimoConfig, kind: str,
                dense: bool) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 5)
    D, E = cfg.d_model, cfg.n_experts
    out = {_attn_part(kind): _attn_params(ks[0], cfg, kind)}
    if dense:
        out["dense"] = {"norm": lm.ones(D),
                        **_swiglu_params(ks[1], cfg, cfg.d_ff)}
        return out
    out["moe"] = {
        "norm": lm.ones(D),
        "router": lm.normal(ks[2], (D, E), 0.02, jnp.float32),
        "bias": lm.normal(ks[3], (E,), ROUTER_BIAS_STD, jnp.float32)}
    out["experts"] = _expert_params(ks[4], cfg)
    return out


def init_layer(key: jax.Array, l: int, cfg: MimoConfig) -> Params:
    """Layer l's weights (l from 0) from `fold_in(key, l)` and nothing else:
    `attn_g` or `attn_s` by its kind, and `dense`, or `moe` with `experts`,
    the held experts' [E', ...]; by the one compiled program a kind
    (`lm.layer_program`): a layer made alone is, to the bit, the layer in
    `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg, cfg.layer_types[l],
                            l < cfg.n_dense_layer)(key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: MimoConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def _entry(cfg: MimoConfig, l: int) -> int:
    """Which of its kind's layers layer l is: its entry in the kind's stack
    of weights and pair of cache leaves."""
    return cfg.layer_types[:l].count(cfg.layer_types[l])


def init_params(key: jax.Array, cfg: MimoConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `attn_g`
    [global layers, ...], `attn_s` [sliding layers, ...], `dense` [dense
    layers, ...], `moe` [sparse layers, ...] and `experts` [sparse layers x
    E', ...], the held experts of every sparse layer end to end; allocated
    once, a layer written at a time (donated), so the most that exists
    beside the tree is one layer (`kimi.init_params`)."""
    n_dense = cfg.n_dense_layer
    sizes = {"attn_g": cfg.layers_of(GLOBAL), "attn_s": cfg.layers_of(SLIDING),
             "dense": n_dense, "moe": cfg.n_layer - n_dense,
             "experts": (cfg.n_layer - n_dense) * cfg.experts_held}
    out = dict(init_ends(key, cfg))
    for l in range(cfg.n_layer):
        layer = init_layer(key, l, cfg)
        for part in layer:
            if part not in out:
                like = layer[part]
                if part == "experts":       # [E', ...] a layer, end to end
                    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape[1:], a.dtype), like)
                out[part] = lm.empty_stack(like, sizes[part])
            at = (_entry(cfg, l) if part.startswith("attn")
                  else l if l < n_dense else l - n_dense)
            out[part] = lm.put_layer(out[part], layer[part], jnp.int32(at))
        del layer
    return out


resident_params = lm.resident_params


def resident_specs(cfg: MimoConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the mimo family is served on one chip, which holds its share of "
        "the experts and of the vocabulary: its two stacks of attention "
        "weights, its rows and its rings have no partition specs and the "
        "shares no exchange yet (tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: MimoConfig) -> int:
    """What this replica holds: the held experts and the vocabulary's
    slice, not the published whole."""
    D = cfg.d_model
    attn = sum(
        cfg.layers_of(kind) * (
            D + D * sum(_qkv_widths(cfg, kind))
            + cfg.n_head * cfg.v_head_dim * D
            + (cfg.n_head if kind == SLIDING else 0))
        for kind in (GLOBAL, SLIDING))
    dense = D + 3 * D * cfg.d_ff
    moe = (D + D * cfg.n_experts + cfg.n_experts
           + cfg.experts_held * 3 * D * cfg.d_ff_expert)
    return (attn + cfg.n_dense_layer * dense
            + (cfg.n_layer - cfg.n_dense_layer) * moe
            + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: MimoConfig, batch: int, max_len: Optional[int] = None):
    """{"k" [global layers, B, 4, 192, T], "v" [global layers, B, 4, T,
    128]}, the keys' positions on the lanes and a value a row, and {"wk"
    [sliding layers, B, 8, 192, W], "wv" [sliding layers, B, 8, W, 128]},
    the rings, position p at p mod W, all in the compute dtype and zero; and
    `counts` uint32 [2, len(COUNTS)], the programs' own, row 0
    `decode_step`'s and row 1 `prefill_chunk`'s (they wrap: a reader takes
    differences modulo 2**32). `max_len` sizes the rows alone."""
    T, W = max_len or cfg.max_seq_len, cfg.sliding_window
    dk, dv = cfg.head_dim, cfg.v_head_dim
    rows = (cfg.layers_of(GLOBAL), batch, cfg.n_kv_head)
    ring = (cfg.layers_of(SLIDING), batch, cfg.swa_n_kv_head)
    return {"k": jnp.zeros(rows + (dk, T), cfg.dtype),
            "v": jnp.zeros(rows + (T, dv), cfg.dtype),
            "wk": jnp.zeros(ring + (dk, W), cfg.dtype),
            "wv": jnp.zeros(ring + (W, dv), cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def _rotate(x, cos, sin, n: int):
    """The first n lanes of x [..., heads, lanes] rotated, lane i with lane
    i + n/2; the other lanes pass."""
    return jnp.concatenate(
        [apply_rope(x[..., :n], cos, sin), x[..., n:]], axis=-1)


def _qkv(u, p, cfg: MimoConfig, pos, kind: str):
    """The normed input u [N,C,D] float32 -> q [N,C,G,R,192] float32 (its
    two pieces meet the cached rows), k [N,C,G,192] and v [N,C,G,128] in the
    compute dtype: one fused product, q and k rotated in their first lanes
    at the kind's theta and v scaled, all of it in float32; k and v are
    rounded once, as the cache holds them."""
    N, C, _ = u.shape
    G, H = cfg.kv_heads(kind), cfg.n_head
    dk, dv = cfg.head_dim, cfg.v_head_dim
    nq, nk, _ = _qkv_widths(cfg, kind)
    qkv = lm.dot(u, p["wqkv"], cfg.dtype)
    q = qkv[..., :nq].reshape(N, C, H, dk)
    k = qkv[..., nq:nq + nk].reshape(N, C, G, dk)
    v = qkv[..., nq + nk:].reshape(N, C, G, dv) * cfg.value_scale
    theta = cfg.swa_rope_theta if kind == SLIDING else cfg.rope_theta
    cos, sin = rope_freqs(pos, cfg.rotary_dim, theta)  # [N, C, rotary/2]
    q = _rotate(q, cos[:, :, None], sin[:, :, None], cfg.rotary_dim)
    k = _rotate(k, cos[:, :, None], sin[:, :, None], cfg.rotary_dim)
    return (q.reshape(N, C, G, H // G, dk), k.astype(cfg.dtype),
            v.astype(cfg.dtype))


def _attention(x, p, cfg: MimoConfig, cache, i, pos0, pos, ok, kind: str,
               slot=None):
    """Attention layer of `kind`, entry i of that kind's leaves: x [N,C,D]
    float32 += grouped-head attention of its lanes at positions pos [N,C].
    Row n is slot n at one lane (N = B, C = 1: `ops/gqa_attend.py`, a global
    layer to each slot's position, a sliding layer over its ring from its
    sink on), or the one row is `slot`'s own further lanes, the first at
    position pos0 [1]: a global layer against that slot's rows a block at a
    time, a sliding layer against the ring as the lane before them left it
    and the chunk's own keys, a band of W, the chunk's last rows written
    over the ring only then (`exaone._attention`, with two widths, two head
    counts and the sink)."""
    B, C, _ = x.shape
    G, dk = cfg.kv_heads(kind), cfg.head_dim
    R = cfg.n_head // G
    sliding = kind == SLIDING
    keys, values = ("wk", "wv") if sliding else ("k", "v")
    attend = "swa_attend" if sliding else "gqa_attend"
    scale = 1.0 / math.sqrt(dk)
    sink = p["sink"].reshape(G, R) if sliding else None
    with jax.named_scope("attn"):
        with jax.named_scope("gqa_project"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
            q, k, v = _qkv(u, p, cfg, pos, kind)
        if slot is None:
            with jax.named_scope("kv_update"):
                ck = rows_write(cache[keys], i, k[:, 0], pos0, ok[:, 0],
                                ring=sliding)
                cv = rows_write(cache[values], i, v[:, 0], pos0, ok[:, 0],
                                ring=sliding)
            with jax.named_scope(attend):
                # the leaves whole and the layer's index: the kernel's index
                # map picks a block where it lies, nothing slices a layer
                y = gqa_attend(q[:, 0], ck, cv, i, pos0, ok[:, 0], scale,
                               ring=sliding, sink=sink)[:, None]
        else:
            # [C,G,R,dk] -> [G, R C, dk]: a head's queries side by side
            qs = jnp.transpose(q[0], (1, 2, 0, 3)).reshape(G, R * C, dk)
            at = jnp.broadcast_to(
                pos0[0] + jnp.tile(jnp.arange(C), R), (G, R * C))
            if sliding:
                with jax.named_scope(attend):
                    y = lm.gqa_attend_ring(
                        qs, cache[keys], cache[values], i, slot, k[0], v[0],
                        at, pos0[0], scale, cfg.dtype,
                        sink=jnp.repeat(sink, C, axis=1))
                with jax.named_scope("kv_update"):
                    n = ok[0].sum()
                    ck = lm.ring_write_slot(cache[keys], i, slot, k[0],
                                            pos0[0], n)
                    cv = lm.ring_write_slot(cache[values], i, slot, v[0],
                                            pos0[0], n)
            else:
                with jax.named_scope("kv_update"):
                    ck = lm.gqa_write_slot(cache[keys], i, slot, k[0],
                                           pos0[0], ok[0])
                    cv = lm.gqa_write_slot(cache[values], i, slot, v[0],
                                           pos0[0], ok[0])
                with jax.named_scope(attend):
                    last = pos0[0] + jnp.maximum(ok[0].sum(), 1) - 1
                    y = lm.gqa_attend_blocks(qs, ck, cv, i, slot, at, last,
                                             scale, cfg.dtype)
            y = jnp.transpose(y.reshape(G, R, C, -1), (2, 0, 1, 3))[None]
        with jax.named_scope("gqa_project"):
            x = x + lm.dot(y.reshape(B, C, -1), p["wo"], cfg.dtype)
    return x, {**cache, keys: ck, values: cv}


def _swiglu(h, p, cfg: MimoConfig):
    a, b = jnp.split(lm.dot(h, p["w_in"], cfg.dtype), 2, axis=-1)
    return lm.dot(jax.nn.silu(a) * b, p["w_out"], cfg.dtype)


def _dense_mlp(x, p, cfg: MimoConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        with jax.named_scope("mlp_dense"):
            return x + _swiglu(h, p, cfg)


def _expert_mlp(x, p, experts_of_all_layers, j, cfg: MimoConfig, given,
                ok, packed: bool = False):
    """x [N,C,D] += the held experts' part of the routed sum (there is no
    shared expert), for sparse layer j; `given` [E] += the (lane, expert)
    pairs of the lanes that are `ok`, over all E (`kimi._expert_mlp`: a pair whose
    expert is held goes to entry j E' + e - first_expert of the stack of
    every layer's held experts, a pair whose expert is not past the stack's
    end, where `moe._experts` gives it no row of any matrix and zeroes
    it). `packed` (the rows are `lm.pack_lanes`'): a row that is not `ok` is
    no lane's and goes there too."""
    B, C, D = x.shape
    K, held = cfg.experts_per_token, cfg.experts_held
    stack = experts_of_all_layers["wg"].shape[0]
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        _, _, gates, experts = _moe._route(h.reshape(B * C, D), p["router"],
                                           cfg, p["bias"])
        with jax.named_scope("moe_router"):
            given = given.at[experts.reshape(-1)].add(
                jnp.repeat(ok.reshape(-1), K).astype(jnp.int32))
            local = experts - cfg.first_expert
            entry = jnp.where((local >= 0) & (local < held),
                              j * held + local, stack)
            if packed:
                entry = jnp.where(ok.reshape(-1, 1), entry, stack)
        routed = _moe._experts(
            h, gates.reshape(B, C, K), entry.reshape(B, C, K),
            *(experts_of_all_layers[w] for w in ("wg", "wu", "wd")),
            types.SimpleNamespace(n_experts=stack + 1, experts_per_token=K,
                                  dtype=jnp.float32),
            first_expert=jnp.int32(0))
        return x + routed, given


def _expert_counts(given, cfg: MimoConfig):
    """One sparse layer's step in `COUNTS`' order but the positions and the
    rings' rows: from the pairs `given` [E] each expert got over all the
    step's valid lanes."""
    with jax.named_scope("moe_router"):
        held = lax.dynamic_slice_in_dim(given, cfg.first_expert,
                                        cfg.experts_held)
        zero = jnp.zeros((), jnp.int32)
        return jnp.stack([jnp.sum(held), jnp.sum(held > 0), jnp.max(held),
                          jnp.ones((), jnp.int32), zero, zero,
                          jnp.sum(given), zero]).astype(jnp.uint32)


def _layer(kind: tuple, l, i, params: Params, cfg: MimoConfig, pos0, on,
           further, prefilling, rounds, first, rest, cache, counts):
    """Layer l, of `kind` (sliding or global, dense or sparse), entry i of
    its kind's leaves and of its kind's stack of attention weights. Attention takes every slot's first lane all slots at
    once, then the further lanes of the slots that have any, a slot at a
    time (`lm.each_slot`, which has why the weights are sliced inside the
    body here); the MLP, which knows nothing of slots, every valid lane of
    the step in one call (`lm.all_lanes`)."""
    attention, dense = kind
    attn_stack = params[_attn_part(attention)]
    mlp_stack = params["dense" if dense else "moe"]
    mlp_i = l if dense else l - cfg.n_dense_layer
    given = jnp.zeros((cfg.n_experts,), jnp.int32)

    def mlp(x, ok, g, given):
        p = lm.layer_weights(mlp_stack, mlp_i, turn=g)
        if dense:
            return _dense_mlp(x, p, cfg), given
        return _expert_mlp(x, p, params["experts"], mlp_i, cfg, given, ok,
                           packed=g is not None)

    first, cache = _attention(
        first, lm.layer_weights(attn_stack, i), cfg, cache, i, pos0,
        pos0[:, None], on[:, None], attention)
    if rest is None:
        first, given = mlp(first, on[:, None], None, given)
    else:
        M = rest.shape[1]
        # the loop writes the leaves where the first lanes read them
        # (`lm.each_slot`: nothing else ties the two here)
        first, cache = lax.optimization_barrier((first, cache))

        def slot(b, carry):
            rest, cache = carry
            xb, okb, at = lm.slot_lanes(b, rest, further, pos0 + 1)
            xb, cache = _attention(
                xb, lm.layer_weights(attn_stack, i, turn=b), cfg, cache,
                i, at, at[:, None] + jnp.arange(M), okb, attention, b)
            return lm.put_lanes(rest, xb, b), cache

        rest, cache = lm.each_slot(prefilling, slot, (rest, cache))
        first, rest, given = lm.all_lanes(mlp, first, on, rest, further,
                                          rounds, given)
    if not dense:
        counts = counts + _expert_counts(given, cfg)
    return first, rest, cache, counts


def _read_positions(cache, pos0, length, on, further):
    """The positions whose rows one global layer read for a step's valid
    lanes (`nemotron._read_positions`): every slot's first lane to its block
    through `gqa_attend` (all T in the plain form), a prefilling slot's
    further lanes the blocks to the slot's last lane."""
    T = cache["v"].shape[3]
    read = read_positions(pos0, on, T)
    if further is not None:
        turns, block = lm.gqa_blocks(pos0 + jnp.maximum(length, 1) - 1, T)
        read = read + jnp.sum(jnp.where(further.any(axis=1),
                                        turns * block, 0))
    return read.astype(jnp.uint32)


def _logits(params: Params, x, cfg: MimoConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: MimoConfig, program: int):
    """Both step programs (`exaone._forward`): a layer computes a lane
    only where the plan put a token, every slot's first lane all slots at
    once and the lanes after it through attention a slot at a time, C of
    them a slot with the last one padding, and through the MLP as rows of
    the first lanes' call.

    The layers are walked as runs of one kind (sliding or global, dense or
    sparse: `lm.layers_in_runs`, three bodies at the published pattern:
    global and dense, sliding and sparse, global and sparse). The loops carry
    the cache, one buffer a leaf from layer to layer, written in place where
    the caller donates it, and close over the experts' stack, which they
    never slice."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    counts = jnp.zeros((len(COUNTS),), jnp.uint32)
    leaves = {k: v for k, v in cache.items() if k != "counts"}
    kinds = [(attention, l < cfg.n_dense_layer)
             for l, attention in enumerate(cfg.layer_types)]
    # for each layer, which entry of its kind's leaves and weights it is
    entry = jnp.asarray([_entry(cfg, l) for l in range(cfg.n_layer)])

    def layer(kind, l, carry):
        return _layer(kind, l, entry[l], params, cfg, pos0, on, further,
                      prefilling, rounds, *carry)

    with jax.named_scope("layers"):
        first, rest, leaves, counts = lm.layers_in_runs(
            kinds, layer, (first, rest, leaves, counts))
    x = lm.join_lanes(first, rest, C)
    with jax.named_scope("moe_router"):
        at = pos0[:, None] + lane + 1
        attended = jnp.sum(jnp.where(ok, at, 0))
        window = cfg.layers_of(SLIDING) * jnp.sum(
            jnp.where(ok, jnp.minimum(at, cfg.sliding_window), 0))
        for name, n in (("attended_positions", attended),
                        ("window_rows_read", window)):
            counts = counts.at[COUNTS.index(name)].set(n.astype(jnp.uint32))
        if cfg.layers_of(GLOBAL):
            counts = counts.at[COUNTS.index("read_positions")].set(
                _read_positions(cache, pos0, length, on, further))
        counts = cache["counts"].at[program].add(counts)
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {**leaves, "counts": counts})


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: MimoConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0 and the rings at pos0 mod W on. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: MimoConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): attention over the cached rows
    and the rings and the held experts' kernel, one token a slot; the chunk
    program's first lane, and nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, 0)
