"""K-EXAONE's layers for serving: three sliding-window softmax layers to
each global one in one stack, the window layers' rows a ring a slot, and an
expert block of sigmoid-routed experts beside a shared one.

What is served is `LGAI-EXAONE/K-EXAONE-236B-A23B` (`model_type:
exaone_moe`; preset `kexaone-236b-a23b`): 48 layers, d = 6,144, RMSNorm eps
1e-5, no bias, untied head. `layer_types` is `LLLG` twelve times (a window
of 128, then none), the first layer's MLP dense and the other 47 sparse:

    u  = RMSNorm_a(x);  x  = x + Attn_l(u)                 # pre-norm
    h  = RMSNorm_m(x);  x' = x + MLP_l(h)
    final RMSNorm, head, logits float32

    Attn_l (64 query heads, 8 key-value heads, 8 queries a key-value head,
            a head 128 lanes: W_q [6144, 8192], W_k, W_v [6144, 1024]):
      q = RMSNorm_q(u W_q -> [64, 128]), k = RMSNorm_k(u W_k -> [8, 128])
          (a norm over the head's 128 lanes), v = u W_v
      sliding layer: q, k = RoPE(q, pos), RoPE(k, pos), theta 1e6; the token
          at pos attends t with pos - 128 < t <= pos
      global layer: no rotation at all; attends every t <= pos
      scores q . k_t / sqrt(128), softmax float32, weighted values, W_o

    MLP_0 = SwiGLU 6144 -> 18432 -> 6144
    MLP_l, l >= 1 (128 routed SwiGLU experts 6144 -> 2048 -> 6144, 8 a
      token, one shared expert of 2,048):
      s = sigmoid(h W_r), float32; the 8 largest of s + bias chosen;
      g_k = 2.5 s_{e_k} / (sum_j s_{e_j} + 1e-20)          (`moe._route`)
      out = sum_k g_k SwiGLU^(e_k)(h) + SwiGLU^shared(h)

**Two kinds of softmax layer whose caches differ in length.** A global
layer's keys and values are rows by head a token, `k`, `v` [global layers,
slots, 8, T, 128] (`CACHE_TOKEN_AXIS`: Solar's and Nemotron's leaves). A
sliding layer's rows older than its window are dead, so what it holds a
slot is a ring of W = 128 rows, `wk`, `wv` [sliding layers, slots, 8, W,
128], position p at row p mod W, the keys stored rotated so that a row's
place says nothing of its score. What a prefix leaves behind in such a layer
is the ring at its end: the ring is a slot's state (`CACHE_STATE`), and the
pool keeps it as a snapshot beside the global layers' rows by the block.
Row r of a ring whose newest position is p holds position p - ((p - r) mod
W), and is live iff that is not negative: a new request's slot holds zeros
or another request's rows until W tokens are in, and the mask by age keeps
them out (`ops/gqa_attend.py`, `lm.gqa_attend_band`).

Both kinds share one stack of weights `attn` [n_layer, ...]; a layer's kind
is static in the program (which pair of leaves it indexes, whether it
rotates), not a branch on the device. The norm a head and the rotation stay
here: `models/keye.py` rotates by three streams of positions and norms
before it splits its heads, so one function for both would be two.

**The chip's share** is `models/kimi.py`'s: `experts_held` E' and
`first_expert` say which of the E experts of every sparse layer this
replica holds; the router keeps its E outputs and its K a token; a pair
whose expert is absent adds nothing; the shared expert is whole;
`vocab_size` rows of the table and of the head are this chip's slice; the
held experts of all sparse layers are one stack `[sparse layers x E', ...]`
that no loop slices.

Both programs are one function: `decode_step` is every slot's first lane
through the layers, all slots at once (the rows and the rings through
`ops/gqa_attend.py`, written by `ops/rows_write.py`), and `prefill_chunk`
that plus a slot's further lanes for the slots that prefill
(`lm.each_slot`: a global layer a block of positions at a time, a sliding
layer the ring as it stood and the chunk's own keys in a band, the ring
written after it is read; the MLPs take those lanes as rows of the first
lanes' call, `lm.all_lanes`): two forms and no third.

The weights exist only in the dtype the replica holds them; float32 are the
norms' scales, the router and its bias, and so are the residual stream,
everything projected, the norms a head, the rotation, the router and its
sigmoid, the gates and the logits. A product's operands are bf16, the
weight as it is held and the activation as the two bf16 pieces that add up
to it (`lm.dot`, `moe._experts` for float32 rows, `ops/gqa_attend.py` for a
float32 q); the rows and the rings are bf16.
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
_PUBLISHED_LAYERS = (SLIDING, SLIDING, SLIDING, GLOBAL) * 12


@dataclasses.dataclass(frozen=True)
class ExaoneConfig:
    vocab_size: int = 153600
    layer_types: tuple = _PUBLISHED_LAYERS
    sliding_window: int = 128
    n_dense_layer: int = 1           # first_k_dense_replace
    d_model: int = 6144
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    d_ff: int = 18432                # intermediate_size: the dense SwiGLU
    d_ff_expert: int = 2048          # moe_intermediate_size
    n_shared_experts: int = 1
    n_experts: int = 128             # what the router scores
    experts_held: int = 128          # E': what this replica holds of them
    first_expert: int = 0
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 2.5
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        assert self.layer_types and set(self.layer_types) <= {
            SLIDING, GLOBAL}, self.layer_types
        assert 0 <= self.n_dense_layer <= self.n_layer
        assert self.n_head % self.n_kv_head == 0
        assert (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts)

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "ExaoneConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # LGAI-EXAONE/K-EXAONE-236B-A23B config.json: the defaults (the
    # multi-token-prediction module is not served)
    "kexaone-236b-a23b": dict(),
    "kexaone-tiny": dict(
        vocab_size=512, layer_types=(SLIDING, SLIDING, SLIDING, GLOBAL),
        sliding_window=16, d_model=64, n_head=4, n_kv_head=2, head_dim=16,
        d_ff=128, d_ff_expert=32, n_experts=8, experts_held=8,
        experts_per_token=3, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): a global layer's keys and
# values by head hold a value a token, along axis 3; a sliding layer's rings
# hold a slot's state, with no token axis: the last `sliding_window` rows
CACHE_TOKEN_AXIS = {"k": 3, "v": 3}
CACHE_STATE = ("wk", "wv")

# the columns of the cache's `counts` leaf: `kimi.COUNTS`, column for column
# (the per-layer readers know them by name; the positions attended and read
# are a global layer's), and the rings' live rows a step over the sliding
# layers, at most `sliding_window` a lane a layer
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions",
          "expert_rows_all", "window_rows_read")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02), a routed expert's
# second matrix among them (`models/longcat.py`'s experts of this very
# shape: a sixteenth of the pairs are held, and at a down projection's
# spread what they add would show in no logit); every other projection back
# into the stream, W_o, the dense MLP's and the shared expert's second, 0.02
# / sqrt(2 n_layer), a residual add a sublayer. W_o among them is what keeps
# attention soft: the norms a head are ones, so q and k have 128 lanes of
# size 1 and a score a spread of 1 whatever the stream is, and with W_o at
# 0.02 an attention sublayer added 0.5 to 2.5 a lane to a stream of 1.2,
# half of it and more the same for every position of a window; the next
# layer's normed q and k then shared that part, every query chose the same
# key, and by the first global layer attention had collapsed onto single
# rows (its output four fifths common to all positions, a greedy reply one
# token repeated, the argmax over 1,024 seeded tokens 104 distinct ids:
# PERF.md, PR 59). At 0.005 an attention sublayer adds 0.1 a lane beside the
# dense MLP's 1.1 and a sparse layer's 0.5, and a window's edge still shows
# in the logits. The token table 0.3 and the selection bias 0.02 by
# `models/kimi.py`'s argument (with the table at 0.02 the stream is a
# fraction of what the first layers add to it and any rounding becomes
# another expert for some token; the head is untied, so no token's own row
# stands out among its logits: granite's lesson on a tied table).
EMBED_STD, ROUTER_BIAS_STD = 0.3, 0.02


def _out_std(cfg: ExaoneConfig) -> float:
    return 0.02 / math.sqrt(2 * cfg.n_layer)


def _attn_params(key, cfg: ExaoneConfig) -> Params:
    ks = jax.random.split(key, 4)
    pd, D = cfg.param_dtype, cfg.d_model
    H, G, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    return {"norm": lm.ones(D),
            "wq": lm.normal(ks[0], (D, H * d), 0.02, pd),
            "wk": lm.normal(ks[1], (D, G * d), 0.02, pd),
            "wv": lm.normal(ks[2], (D, G * d), 0.02, pd),
            "q_norm": lm.ones(d), "k_norm": lm.ones(d),
            "wo": lm.normal(ks[3], (H * d, D), _out_std(cfg), pd)}


def _swiglu_params(key, cfg: ExaoneConfig, width: int) -> Params:
    k_in, k_out = jax.random.split(key)
    pd, D = cfg.param_dtype, cfg.d_model
    # gate and up side by side: one product
    return {"w_in": lm.normal(k_in, (D, 2 * width), 0.02, pd),
            "w_out": lm.normal(k_out, (width, D), _out_std(cfg), pd)}


def _expert_params(key, cfg: ExaoneConfig) -> Params:
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


def _init_layer(key: jax.Array, l, cfg: ExaoneConfig, dense: bool) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 6)
    D, E = cfg.d_model, cfg.n_experts
    out = {"attn": _attn_params(ks[0], cfg)}
    if dense:
        out["dense"] = {"norm": lm.ones(D),
                        **_swiglu_params(ks[1], cfg, cfg.d_ff)}
        return out
    out["moe"] = {
        "norm": lm.ones(D),
        "router": lm.normal(ks[2], (D, E), 0.02, jnp.float32),
        "bias": lm.normal(ks[3], (E,), ROUTER_BIAS_STD, jnp.float32),
        "shared": _swiglu_params(ks[4], cfg,
                                 cfg.n_shared_experts * cfg.d_ff_expert)}
    out["experts"] = _expert_params(ks[5], cfg)
    return out


def init_layer(key: jax.Array, l: int, cfg: ExaoneConfig) -> Params:
    """Layer l's weights (l from 0) from `fold_in(key, l)` and nothing else:
    `attn` (a sliding and a global layer's are alike), and `dense`, or `moe`
    with `experts`, the held experts' [E', ...]; by the one compiled program
    a kind (`lm.layer_program`): a layer made alone is, to the bit, the
    layer in `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg, l < cfg.n_dense_layer)(
        key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: ExaoneConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def init_params(key: jax.Array, cfg: ExaoneConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `attn`
    [n_layer, ...], `dense` [dense layers, ...], `moe` [sparse layers, ...]
    and `experts` [sparse layers x E', ...], the held experts of every
    sparse layer end to end; allocated once, a layer written at a time
    (donated), so the most that exists beside the tree is one layer
    (`kimi.init_params`)."""
    n_dense = cfg.n_dense_layer
    sizes = {"attn": cfg.n_layer, "dense": n_dense,
             "moe": cfg.n_layer - n_dense,
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
            at = l if part == "attn" or l < n_dense else l - n_dense
            out[part] = lm.put_layer(out[part], layer[part], jnp.int32(at))
        del layer
    return out


resident_params = lm.resident_params


def resident_specs(cfg: ExaoneConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the exaone family is served on one chip, which holds its share of "
        "the experts and of the vocabulary: its weights, its rows and its "
        "rings have no partition specs and the shares no exchange yet "
        "(tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: ExaoneConfig) -> int:
    """What this replica holds: the held experts and the vocabulary's
    slice, not the published whole."""
    D, d = cfg.d_model, cfg.head_dim
    attn = D + 2 * D * cfg.n_head * d + 2 * D * cfg.n_kv_head * d + 2 * d
    dense = D + 3 * D * cfg.d_ff
    moe = (D + D * cfg.n_experts + cfg.n_experts
           + 3 * D * cfg.n_shared_experts * cfg.d_ff_expert
           + cfg.experts_held * 3 * D * cfg.d_ff_expert)
    return (cfg.n_layer * attn + cfg.n_dense_layer * dense
            + (cfg.n_layer - cfg.n_dense_layer) * moe
            + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ExaoneConfig, batch: int, max_len: Optional[int] = None):
    """{"k", "v" [global layers, B, G, T, d]}, a position a row of the
    head's lanes, and {"wk", "wv" [sliding layers, B, G, W, d]}, the rings,
    position p at row p mod W, all in the compute dtype and zero; and
    `counts` uint32 [2, len(COUNTS)], the programs' own, row 0
    `decode_step`'s and row 1 `prefill_chunk`'s (they wrap: a reader takes
    differences modulo 2**32). `max_len` sizes the rows alone."""
    T = max_len or cfg.max_seq_len
    G, d = cfg.n_kv_head, cfg.head_dim
    rows = (cfg.layers_of(GLOBAL), batch, G, T, d)
    ring = (cfg.layers_of(SLIDING), batch, G, cfg.sliding_window, d)
    return {"k": jnp.zeros(rows, cfg.dtype), "v": jnp.zeros(rows, cfg.dtype),
            "wk": jnp.zeros(ring, cfg.dtype),
            "wv": jnp.zeros(ring, cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def _qkv(u, p, cfg: ExaoneConfig, pos):
    """The normed input u [N,C,D] float32 -> q [N,C,G,R,d] float32 (its two
    pieces meet the cached rows) and k, v [N,C,G,d] in the compute dtype:
    projected, q and k normed over a head's lanes and, with `pos` [N,C] (a
    sliding layer), rotated, all of it in float32; k is rounded once, as the
    cache holds it."""
    N, C, _ = u.shape
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    q = rms_norm(lm.dot(u, p["wq"], cfg.dtype).reshape(N, C, G * R, d),
                 p["q_norm"], cfg.norm_eps)
    k = rms_norm(lm.dot(u, p["wk"], cfg.dtype).reshape(N, C, G, d),
                 p["k_norm"], cfg.norm_eps)
    v = lm.dot(u, p["wv"], cfg.dtype).reshape(N, C, G, d)
    if pos is not None:
        cos, sin = rope_freqs(pos, d, cfg.rope_theta)      # [N, C, d/2]
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
    return (q.reshape(N, C, G, R, d), k.astype(cfg.dtype),
            v.astype(cfg.dtype))


def _attention(x, p, cfg: ExaoneConfig, cache, i, pos0, pos, ok, kind: str,
               slot=None):
    """Attention layer of `kind`, entry i of that kind's leaves: x [N,C,D]
    float32 += grouped-head attention of its lanes at positions pos [N,C].
    Row n is slot n at one lane (N = B, C = 1: `ops/gqa_attend.py`, a global
    layer to each slot's position, a sliding layer over its ring), or the
    one row is `slot`'s own further lanes, the first at position pos0 [1]: a
    global layer against that slot's rows a block at a time, a sliding layer
    against the ring as the lane before them left it and the chunk's own
    keys, a band of W, the chunk's last rows written over the ring only
    then."""
    B, C, _ = x.shape
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    sliding = kind == SLIDING
    keys, values = ("wk", "wv") if sliding else ("k", "v")
    attend = "swa_attend" if sliding else "gqa_attend"
    scale = 1.0 / math.sqrt(d)
    with jax.named_scope("attn"):
        with jax.named_scope("gqa_project"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
            q, k, v = _qkv(u, p, cfg, pos if sliding else None)
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
                               ring=sliding)[:, None]          # [B,1,G,R,d]
        else:
            # [C,G,R,d] -> [G, R C, d]: a head's queries side by side
            qs = jnp.transpose(q[0], (1, 2, 0, 3)).reshape(G, R * C, d)
            at = jnp.broadcast_to(
                pos0[0] + jnp.tile(jnp.arange(C), R), (G, R * C))
            if sliding:
                with jax.named_scope(attend):
                    y = lm.gqa_attend_ring(qs, cache[keys], cache[values], i,
                                           slot, k[0], v[0], at, pos0[0],
                                           scale, cfg.dtype)
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
            y = jnp.transpose(y.reshape(G, R, C, d), (2, 0, 1, 3))[None]
        with jax.named_scope("gqa_project"):
            x = x + lm.dot(y.reshape(B, C, -1), p["wo"], cfg.dtype)
    return x, {**cache, keys: ck, values: cv}


def _swiglu(h, p, cfg: ExaoneConfig):
    a, b = jnp.split(lm.dot(h, p["w_in"], cfg.dtype), 2, axis=-1)
    return lm.dot(jax.nn.silu(a) * b, p["w_out"], cfg.dtype)


def _dense_mlp(x, p, cfg: ExaoneConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["norm"], cfg.norm_eps)
        with jax.named_scope("mlp_dense"):
            return x + _swiglu(h, p, cfg)


def _expert_mlp(x, p, experts_of_all_layers, j, cfg: ExaoneConfig, given,
                ok, packed: bool = False):
    """x [N,C,D] += the held experts' part of the routed sum + the shared
    expert, for sparse layer j; `given` [E] += the (lane, expert) pairs of
    the lanes that are `ok`, over all E (`kimi._expert_mlp`: a pair whose
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
        with jax.named_scope("moe_shared"):
            shared = _swiglu(h, p["shared"], cfg)
        return x + routed + shared, given


def _expert_counts(given, cfg: ExaoneConfig):
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


def _layer(kind: tuple, l, i, params: Params, cfg: ExaoneConfig, pos0, on,
           further, prefilling, rounds, first, rest, cache, counts):
    """Layer l, of `kind` (sliding or global, dense or sparse), entry i of
    its kind's leaves. Attention takes every slot's first lane all slots at
    once, then the further lanes of the slots that have any, a slot at a
    time (`lm.each_slot`, which has why the weights are sliced inside the
    body here); the MLP, which knows nothing of slots, every valid lane of
    the step in one call (`lm.all_lanes`)."""
    attention, dense = kind
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
        first, lm.layer_weights(params["attn"], l), cfg, cache, i, pos0,
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
                xb, lm.layer_weights(params["attn"], l, turn=b), cfg, cache,
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
    T = cache["k"].shape[3]
    read = read_positions(pos0, on, T)
    if further is not None:
        turns, block = lm.gqa_blocks(pos0 + jnp.maximum(length, 1) - 1, T)
        read = read + jnp.sum(jnp.where(further.any(axis=1),
                                        turns * block, 0))
    return read.astype(jnp.uint32)


def _logits(params: Params, x, cfg: ExaoneConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: ExaoneConfig, program: int):
    """Both step programs (`kimi._forward`'s shape): a layer computes a lane
    only where the plan put a token, every slot's first lane all slots at
    once and the lanes after it through attention a slot at a time, C of
    them a slot with the last one padding, and through the MLP as rows of
    the first lanes' call.

    The layers are walked as runs of one kind (sliding or global, dense or
    sparse): one loop over the runs, whose body holds one loop a kind, and a
    kind's loop turns as many times as the run is long if the run is of that
    kind and not at all if it is not. No branch takes a layer's kind (a leaf
    that passes through a conditional untouched is copied on its way), the
    program holds a body a kind whatever the depth (three at the published
    pattern), and nothing of a layer stands outside the runs' loop. The
    loops carry the cache, one buffer a leaf from layer to layer, written in
    place where the caller donates it, and close over the experts' stack,
    which they never slice."""
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
    runs = []                             # [kind, first layer, layers]
    for l, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1])
    bodies = sorted(set(kinds))
    first_layer = jnp.asarray([first_l for _, first_l, _ in runs])
    turns = {kind: jnp.asarray([n if k == kind else 0 for k, _, n in runs])
             for kind in bodies}
    # for each layer, which entry of its kind's leaves it is
    entry = jnp.asarray([cfg.layer_types[:l].count(t)
                         for l, t in enumerate(cfg.layer_types)])

    def layer(kind, l, carry):
        return _layer(kind, l, entry[l], params, cfg, pos0, on, further,
                      prefilling, rounds, *carry)

    def run(r, carry):
        start = first_layer[r]
        for kind in bodies:
            carry = lax.fori_loop(start, start + turns[kind][r],
                                  functools.partial(layer, kind), carry)
        return carry

    with jax.named_scope("layers"):
        carry = lax.fori_loop(0, len(runs), run,
                              (first, rest, leaves, counts))
    first, rest, leaves, counts = carry
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
                  length: jax.Array, active: jax.Array, cfg: ExaoneConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0 and the rings at pos0 mod W on. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: ExaoneConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): attention over the cached rows
    and the rings and the held experts' kernel, one token a slot; the chunk
    program's first lane, and nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, 0)
