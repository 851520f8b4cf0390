"""Kimi Linear's layers for serving: Kimi Delta Attention (KDA) over a
recurrent state beside latent attention (MLA) without positions, then
routed experts of which this chip may hold a share; and Solar Open2's,
which are the same delta rule beside gated grouped-head attention.

What is served is `moonshotai/Kimi-Linear-48B-A3B-Instruct` (`model_type:
kimi_linear`; preset `kimi-linear-48b-a3b`): 27 layers counted from 1, MLA
at `full_attn_layers` (4, 8, .., 24, 27) and KDA elsewhere; layer 1's MLP
is dense, every other layer's the expert block. With d the hidden size, eps
1e-5, no bias but the output gate's:

    x += mixer(RMSNorm(x));  x += mlp(RMSNorm(x))
    final RMSNorm, untied head, logits float32

    KDA (32 heads of 128 lanes), u the normed input:
      q, k, v = silu(conv1d_causal_4(u W_q | W_k | W_v)), depthwise over
        the last 4 positions; q, k L2-normalised a head; q * 128^-1/2
      a = exp(-exp(A_log_h) softplus((u W_f1) W_f2 + dt_bias)) in (0,1)^128
        a head: a decay a key channel;  b = sigmoid(u W_b), a head
      S~ = Diag(a_t) S_{t-1};  S_t = S~ + b_t k_t (v_t - S~^T k_t)^T
      o_t = S_t^T q_t;   y = (RMSNorm_128(o_t) * sigmoid((u W_g1) W_g2 +
        c_g)) W_o

    MLA (`models/deepseek.py`'s docstring, `mla_use_nope`: no rotation):
      q = u W_q -> [32, 128 + 64];  [c, k_r] = u W_kva -> 512 + 64;
      c = RMSNorm(c); absorbed form over a cache of 576 values a token;
      the 64 lanes stay as a shared un-rotated key

    experts (`models/moe.py`): s = sigmoid(h W_g) over all E = 256, the 8
      largest of s + b chosen, gates s / (sum + 1e-20) * 2.446;
      x += sum_k g_k SwiGLU_1024^(e_k)(h) + SwiGLU_1024^shared(h)

`upstage/Solar-Open2-250B` (`model_type: solar_open2`; preset
`solar-open2-250b`; `models/solar.py` is the word `solar`) is served
here too, as a third kind of mixer and two switches, because everything
else of it is this module's: 48 layers counted from 0, softmax attention
at `gqa_layers` (0, 4, .., 44) and KDA elsewhere at 64 heads, every layer's
MLP the expert block (320 experts of 1,280, scaling 1.0), no dense layer.

    KDA as above with b = 2 sigmoid(u W_b) (`kda_neg_eigval`: a
      transition's eigenvalue along k reaches into (-1, 1))
    softmax layer (`gqa`), 64 query and 8 key-value heads of 128, no
      rotation, no q/k norm:  q = u W_q;  k, v = u W_k, u W_v, cached by
      the 8 heads;  o_h = softmax_{t<=pos}(q_h . k_{h//8,t} / sqrt(128))
      v_{h//8};  y = (o * sigmoid(u W_gate)) W_o

Its rows are keys and values by head, `k`, `v` [softmax layers, slots, 8,
T, 128] (`lm`'s grouped-head arithmetic, which granite's attention layers
run too: `gqa_qkv`, `gqa_attend`, `gqa_write_slot`; a decode step's
position through `ops/rows_write.py`). Nothing reads a slot's rows past
its position: every slot's first lane, the decode program whole, goes
through the kernel `ops/gqa_attend.py` (off the chip the plain form), a
chunk's further lanes a block of positions at a time (`gqa_attend_blocks`).
A float32 q and the probabilities meet the bf16 rows as two pieces.

**The chip's share.** `experts_held` E' and `first_expert` say which of the
E experts of every expert layer this replica holds: the router keeps its E
outputs and its 8 a token, `moe._experts` computes the held experts' part of
the sum for the (token, slot) pairs routed to them, and what the absent
experts would have added is left out: nothing stands in for the other
chips. `vocab_size` rows of the table and the head are this chip's slice.
The held experts of all expert layers are one stack `[layers x E', d, F]`
that no layers' loop slices: a layer hands `_experts` the whole stack with
its ids offset by the layer (and the pairs of absent experts sent past the
stack's end), so no program copies an expert matrix (PERF.md, PR 48).

The cache holds both kinds of leaf (`models/__init__.py`): `kda` [KDA
layers, slots, 32, 128, 128] and `conv` [KDA layers, slots, 3 x 12288] a
slot's state, float32 (`CACHE_STATE`: S in `ops/kda_update.py`'s layout and
the last three inputs of the convolutions of q, k and v side by side), and
`latent` / `k_rope` [MLA layers, slots, T, 512 | 64] or `k` / `v` a value
a token (`CACHE_TOKEN_AXIS` here and in `models/solar.py`; a configuration's
cache has the leaves of the kinds of layer it has), and `counts`, the
programs' own.

The mixers exist in two forms and no third (`models/granite.py`). The
recurrence, one token a slot through the kernel `kda_update`, is
`decode_step` whole and, in `prefill_chunk`, every slot's first lane. A
chunk's further lanes go, a slot at a time and only for the slots that
prefill (`models/lm.py`, "The lanes of a chunk", has the loop and the
contract), through the chunked form of the delta rule, M lanes after state
S_0, with g_i = sum_{m<=i} log a_m a channel:

    A_ij = b_i sum_c k_ic k_jc exp(g_ic - g_jc), j < i
    (I + A) U = b * (V - (K * exp(g)) S_0)
    o_i = S_0^T (q_i * exp(g_i)) + sum_{j<=i} (sum_c q_ic k_jc
          exp(g_ic - g_jc)) u_j
    S_M = Diag(exp(g_M)) S_0 + sum_j (k_j * exp(g_M - g_j)) u_j^T

in runs of `SUBCHUNK` lanes, the state handed from run to run. Differences
of g are taken before the exponential (a quotient of two cumulative
products overflows under strong decay); the unit-lower-triangular solve is
(I - N)^-1 = (I + N)(I + N^2)(I + N^4)(I + N^8), exact for the nilpotent
N = -A of 16 rows. A lane past a slot's length has a = 1, b = 0: it decays
nothing and writes nothing.

The weights exist only in the dtype the replica holds them; float32 are the
norms' scales, the convolution, `dt_bias`, `A_log`, W_b, the gate's bias,
the router and its bias, and so are the residual stream, everything
projected, the decay, the state, its update and read-out, the router and
the logits. A product's operands are bf16, the weight as it is held and the
activation as the two bf16 pieces that add up to it (`lm.dot`, and
`moe._experts` for float32 rows); the latent rows and attention's weights
go as one piece.
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
from ray_tpu.models.mla import cache_write, rows, write_first
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.gqa_attend import gqa_attend, read_positions as gqa_read
from ray_tpu.ops.kda_update import kda_update
from ray_tpu.ops.pieces import pieces
from ray_tpu.ops.mla_attend import attend_rows, mla_attend, read_positions
from ray_tpu.ops.rows_write import rows_write

Params = Any
_HIGHEST = lax.Precision.HIGHEST
SUBCHUNK = 16


@dataclasses.dataclass(frozen=True)
class KimiConfig:
    vocab_size: int = 163840
    n_layer: int = 27
    mla_layers: tuple = (4, 8, 12, 16, 20, 24, 27)   # counted from 1
    n_dense_layer: int = 1           # first_k_dense_replace
    d_model: int = 2304
    d_ff: int = 9216                 # the dense layer's SwiGLU
    d_ff_expert: int = 1024
    n_experts: int = 256             # what the router scores
    experts_held: int = 256          # E': what this replica holds of them
    first_expert: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    n_head: int = 32                 # MLA's, or the softmax layers' queries
    gqa_layers: tuple = ()           # counted from 0, as their source does
    n_kv_head: int = 8               # the softmax layers' key-value heads
    gqa_head_dim: int = 128
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4                # short_conv_kernel_size
    kda_rank: int = 128              # the two gates' low rank (assumed)
    kda_neg_eigval: bool = False     # b = 2 sigmoid (kda_allow_neg_eigval)
    norm_eps: float = 1e-5
    max_seq_len: int = 1048576
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        object.__setattr__(self, "mla_layers", tuple(self.mla_layers))
        object.__setattr__(self, "gqa_layers", tuple(self.gqa_layers))
        assert all(1 <= l <= self.n_layer for l in self.mla_layers)
        assert all(0 <= l < self.n_layer for l in self.gqa_layers)
        # one kind of attention a configuration: the cache holds its rows,
        # and `_read_positions` counts by which leaves there are
        assert not (self.mla_layers and self.gqa_layers)
        assert (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts)

    @property
    def layer_types(self) -> tuple:
        return tuple("mla" if l + 1 in self.mla_layers
                     else "gqa" if l in self.gqa_layers else "kda"
                     for l in range(self.n_layer))

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def n_expert_layer(self) -> int:
        return self.n_layer - self.n_dense_layer

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values a token leaves in the cache, an MLA layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def preset(cls, name: str, **overrides) -> "KimiConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # moonshotai/Kimi-Linear-48B-A3B-Instruct config.json: the defaults
    "kimi-linear-48b-a3b": dict(),
    # upstage/Solar-Open2-250B config.json; intermediate_size 10,240 is used
    # by no layer (first_k_dense_replace 0)
    "solar-open2-250b": dict(
        vocab_size=196608, n_layer=48, mla_layers=(),
        gqa_layers=tuple(range(0, 48, 4)), n_dense_layer=0, d_model=4096,
        d_ff=10240, d_ff_expert=1280, n_experts=320, experts_held=320,
        routed_scaling_factor=1.0, n_head=64, n_kv_head=8, gqa_head_dim=128,
        kda_heads=64, kda_neg_eigval=True),
    "solar-tiny": dict(
        vocab_size=512, n_layer=8, mla_layers=(), gqa_layers=(0, 4),
        n_dense_layer=0, d_model=64, d_ff=128, d_ff_expert=40, n_experts=16,
        experts_held=16, experts_per_token=3, routed_scaling_factor=1.0,
        n_head=4, n_kv_head=2, gqa_head_dim=16, kda_heads=2, kda_head_dim=16,
        kda_rank=8, kda_neg_eigval=True, max_seq_len=128),
    "kimi-tiny": dict(
        vocab_size=512, n_layer=5, mla_layers=(3, 5), n_dense_layer=1,
        d_model=64, d_ff=128, d_ff_expert=32, n_experts=8, experts_held=8,
        experts_per_token=3, n_head=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, kda_heads=2, kda_head_dim=16,
        kda_rank=8, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): the latent and the shared key
# hold a value a token, along axis 2; the delta-rule state and the
# convolutions' window hold a slot's state, with no token axis. (A
# configuration with softmax layers has keys and values by head instead:
# `models/solar.py` is this module under that word on the leaves.)
CACHE_TOKEN_AXIS = {"latent": 2, "k_rope": 2}
CACHE_STATE = ("kda", "conv")

# the columns of the cache's `counts` leaf, each a sum over a program's
# executions (`deepseek.COUNTS`, whose six the first six are): over the
# expert layers, the (lane, expert) rows the experts held here were given
# for valid lanes, the held experts that got at least one, the most that
# one of them got, and 1; once a step the positions the valid lanes attend
# to and the positions whose rows an MLA or softmax layer read for them
# (`mla_attend` to each lane's block, the grouped-head plain form all T, a
# chunk's further lanes to the slot's block); and, over the
# expert layers again, all the valid lanes' (lane, expert) pairs, held or
# not: 8 a lane
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions",
          "expert_rows_all")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02) and every down
# projection 0.02 / sqrt(2 n_layer), as a fresh Hugging Face model; the
# token table 0.3 and the selection bias 0.02 by `models/deepseek.py`'s
# argument (with the table at 0.02 the stream is a fifth of what the first
# layers add to it and any rounding becomes another expert for some token;
# the head is untied, so granite's lesson on a tied table does not apply).
# The KDA layer's own as the public reference layer initialises them:
# A = U(1, 16) a head, dt = exp(U(log 0.001, log 0.1)) a channel through the
# inverse of softplus into `dt_bias`, the depthwise convolution
# U(-1/2, 1/2) (a window of 4). With the projection's part added the decay's
# rate lies about 0.001 to 1.6 a token: a memory of one to a thousand
# tokens, so that a fault in carrying state across chunks, snapshots and
# slots cannot hide.
EMBED_STD, ROUTER_BIAS_STD = 0.3, 0.02
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def _swiglu_params(key, cfg: KimiConfig, width: int) -> Params:
    k_in, k_out = jax.random.split(key)
    pd, D = cfg.param_dtype, cfg.d_model
    return {"w_in": lm.normal(k_in, (D, 2 * width), 0.02, pd),
            "w_out": lm.normal(k_out, (width, D),
                             0.02 / math.sqrt(2 * cfg.n_layer), pd)}


def _kda_params(key, cfg: KimiConfig) -> Params:
    ks = jax.random.split(key, 10)
    pd, D, I = cfg.param_dtype, cfg.d_model, cfg.kda_inner
    H, R, K = cfg.kda_heads, cfg.kda_rank, cfg.kda_conv
    dt = jnp.exp(jax.random.uniform(ks[0], (I,), jnp.float32,
                                    math.log(DT_RANGE[0]),
                                    math.log(DT_RANGE[1])))
    edge = 1.0 / math.sqrt(K)
    return {
        # W_q, W_k, W_v side by side: one product, one convolution
        "w_qkv": lm.normal(ks[1], (D, 3 * I), 0.02, pd),
        # tap k of the window multiplies the input 3 - k positions back
        "conv_w": jax.random.uniform(ks[2], (K, 3 * I), jnp.float32,
                                     -edge, edge),
        # the three narrow projections of u side by side: the decay gate's
        # and the output gate's low ranks and b's H columns (padded to whole
        # lane tiles; as [D, H] float32 alone they cost a decode step 0.57
        # ms a layer, a tenth of it: PERF.md, PR 40): one product
        "w_fgb": jnp.concatenate([
            lm.normal(ks[3], (D, R), 0.02, pd),
            lm.normal(ks[7], (D, R), 0.02, pd),
            lm.normal(ks[6], (D, H), 0.02, pd),
            jnp.zeros((D, -H % 128), pd)], axis=1),
        "w_f2": lm.normal(ks[4], (R, I), 0.02, pd),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(ks[5], (H,), jnp.float32,
                                            *A_RANGE)),
        "w_g2": lm.normal(ks[8], (R, I), 0.02, pd),
        "g_bias": jnp.zeros((I,), jnp.float32),
        "o_norm": lm.ones(cfg.kda_head_dim),
        "w_o": lm.normal(ks[9], (I, D), 0.02, pd)}


def _mla_params(key, cfg: KimiConfig) -> Params:
    ks = jax.random.split(key, 4)
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.n_head
    return {"wq": lm.normal(ks[0], (D, H * cfg.qk_head_dim), 0.02, pd),
            "wkva": lm.normal(ks[1], (D, cfg.cache_width), 0.02, pd),
            "kv_norm": lm.ones(cfg.kv_lora_rank),
            # W_kvb by head, its two halves apart as the absorbed form
            # multiplies them: [H, n, r] into the query, [H, r, v] out of
            # the weighted latents
            "w_uk": lm.normal(ks[2], (H, cfg.qk_nope_head_dim,
                                    cfg.kv_lora_rank), 0.02, pd),
            "w_uv": lm.normal(jax.random.fold_in(ks[2], 1),
                            (H, cfg.kv_lora_rank, cfg.v_head_dim), 0.02, pd),
            "wo": lm.normal(ks[3], (H * cfg.v_head_dim, D), 0.02, pd)}


def _gqa_params(key, cfg: KimiConfig) -> Params:
    ks = jax.random.split(key, 5)
    pd, D = cfg.param_dtype, cfg.d_model
    H, G, d = cfg.n_head, cfg.n_kv_head, cfg.gqa_head_dim
    return {"wq": lm.normal(ks[0], (D, H * d), 0.02, pd),
            "wk": lm.normal(ks[1], (D, G * d), 0.02, pd),
            "wv": lm.normal(ks[2], (D, G * d), 0.02, pd),
            # the output gate, a value an output lane (assumed element-wise)
            "w_gate": lm.normal(ks[3], (D, H * d), 0.02, pd),
            "wo": lm.normal(ks[4], (H * d, D), 0.02, pd)}


_MIXER_PARAMS = {"kda": _kda_params, "mla": _mla_params, "gqa": _gqa_params}


def _expert_params(key, cfg: KimiConfig) -> Params:
    """The held experts' matrices: expert e's from `fold_in(key, e)` and
    nothing else, so that every share of a layer holds the same expert
    e."""
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff_expert
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)

    def one(e):
        ks = jax.random.split(jax.random.fold_in(key, e), 3)
        return {"wg": lm.normal(ks[0], (D, F), 0.02, pd),
                "wu": lm.normal(ks[1], (D, F), 0.02, pd),
                "wd": lm.normal(ks[2], (F, D), resid_std, pd)}

    # a loop, not `vmap`: one expert's three matrices are the program, which
    # compiles in a fifth of the time of all 64 side by side (8 s of a cold
    # replica's start a kind of layer)
    return lax.map(one, cfg.first_expert + jnp.arange(cfg.experts_held))


def _init_layer(key: jax.Array, l, cfg: KimiConfig, kind: str,
                dense: bool) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 6)
    D, E = cfg.d_model, cfg.n_experts
    out = {kind: {"norm": lm.ones(D), **_MIXER_PARAMS[kind](ks[0], cfg)}}
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


def init_layer(key: jax.Array, l: int, cfg: KimiConfig) -> Params:
    """Layer l's weights (l from 0) from `fold_in(key, l)` and nothing
    else: its mixer under `kda` or `mla`, its MLP under `dense` or under
    `moe` (router, bias, shared expert) and `experts` (the held experts'
    [E', ...]), by the one compiled program a kind (`lm.layer_program`): a
    layer made alone is, to the bit, the layer in `init_params`' tree. The
    mixer lies under its kind's name, `kda`, `mla` or `gqa`."""
    return lm.layer_program(_init_layer, cfg, cfg.layer_types[l],
                            l < cfg.n_dense_layer)(key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: KimiConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def _stack_index(cfg: KimiConfig) -> list:
    """For each layer, where its parts lie: {part: index in that part's
    stack}."""
    seen: dict = {}
    out = []
    for l, kind in enumerate(cfg.layer_types):
        parts = [kind] + (["dense"] if l < cfg.n_dense_layer
                          else ["moe", "experts"])
        at = {}
        for part in parts:
            at[part] = seen.get(part, 0)
            seen[part] = at[part] + 1
        out.append(at)
    return out


def init_params(key: jax.Array, cfg: KimiConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `kda`,
    `mla`, `dense` and `moe`, one stack a part on a leading axis in the
    order the layers have, and `experts` [expert layers x E', ...], the
    held experts of every expert layer end to end. A layer's program makes
    all of its parts at once, so the layers' loop is here and not
    `lm.stack_layers`; the stacks are `lm`'s: allocated once, a layer
    written at a time (donated), so the most that exists beside the tree
    is one layer."""
    index = _stack_index(cfg)
    out = dict(init_ends(key, cfg))
    for l, at in enumerate(index):
        layer = init_layer(key, l, cfg)
        for part, i in at.items():
            if part not in out:
                like, n = layer[part], sum(part in at for at in index)
                if part == "experts":       # [E', ...] a layer, end to end
                    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape[1:], a.dtype), like)
                    n *= cfg.experts_held
                out[part] = lm.empty_stack(like, n)
            out[part] = lm.put_layer(out[part], layer[part], jnp.int32(i))
        del layer
    return out


resident_params = lm.resident_params


def resident_specs(cfg: KimiConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the kimi family is served on one chip, which holds its share of "
        "the experts and of the vocabulary: its weights, its rows and its "
        "state have no partition specs and the shares no exchange yet "
        "(tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: KimiConfig) -> int:
    """What this replica holds: the held experts and the vocabulary's
    slice, not the published whole."""
    D, I, R, H = cfg.d_model, cfg.kda_inner, cfg.kda_rank, cfg.kda_heads
    kda = (D * 3 * I + cfg.kda_conv * 3 * I + D * (2 * R + H + -H % 128)
           + 2 * R * I + 2 * I + H + cfg.kda_head_dim + I * D + D)
    r, Hm = cfg.kv_lora_rank, cfg.n_head
    mla = (D * Hm * cfg.qk_head_dim + D * cfg.cache_width + r
           + r * Hm * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + Hm * cfg.v_head_dim * D + D)
    gqa = (2 * D * Hm * cfg.gqa_head_dim
           + 2 * D * cfg.n_kv_head * cfg.gqa_head_dim
           + Hm * cfg.gqa_head_dim * D + D)
    F = cfg.d_ff_expert
    moe = (D + D * cfg.n_experts + cfg.n_experts
           + (cfg.experts_held + cfg.n_shared_experts) * 3 * D * F)
    dense = D + 3 * D * cfg.d_ff
    return (cfg.layers_of("kda") * kda + cfg.layers_of("mla") * mla
            + cfg.layers_of("gqa") * gqa
            + cfg.n_dense_layer * dense + cfg.n_expert_layer * moe
            + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: KimiConfig, batch: int, max_len: Optional[int] = None):
    """{"kda" [KDA layers, B, H, 128, 128], "conv" [KDA layers, B, 3 x 3 H
    128]} float32 (the three inputs of the convolutions of q, k and v side
    by side on the lanes, as granite's window), zero, which is what a
    sequence starts from; {"latent" [MLA layers, B, T, r], "k_rope" [MLA
    layers, B, T, p]} in the compute dtype, `deepseek.init_cache`'s two
    leaves; and `counts` uint32 [2, len(COUNTS)], the programs' own, row 0
    `decode_step`'s and row 1 `prefill_chunk`'s (they wrap: a reader takes
    differences modulo 2**32). `max_len` sizes the rows alone."""
    T = max_len or cfg.max_seq_len
    Lk, Lm, Lg = (cfg.layers_of(kind) for kind in ("kda", "mla", "gqa"))
    P = cfg.kda_head_dim
    out = {"kda": jnp.zeros((Lk, batch, cfg.kda_heads, P, P), jnp.float32),
           "conv": jnp.zeros(
               (Lk, batch, (cfg.kda_conv - 1) * 3 * cfg.kda_inner),
               jnp.float32)}
    if Lm:
        out.update(
            latent=jnp.zeros((Lm, batch, T, cfg.kv_lora_rank), cfg.dtype),
            k_rope=jnp.zeros((Lm, batch, T, cfg.qk_rope_head_dim), cfg.dtype))
    if Lg:
        # by the key-value heads, a position a row of the head's lanes
        by_head = (Lg, batch, cfg.n_kv_head, T, cfg.gqa_head_dim)
        out.update(k=jnp.zeros(by_head, cfg.dtype),
                   v=jnp.zeros(by_head, cfg.dtype))
    return {**out, "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def _swiglu(h, p, cfg: KimiConfig):
    ab = lm.dot(h, p["w_in"], cfg.dtype)
    a, b = jnp.split(ab, 2, axis=-1)
    return lm.dot(jax.nn.silu(a) * b, p["w_out"], cfg.dtype)


def _kda_in(u, p, cfg: KimiConfig):
    """The norm's output u [B,M,D] float32 -> q, k and v side by side before
    the convolution [B,M,3I], the log of the decay [B,M,I] (<= 0), b
    [B,M,H] and the output gate [B,M,I], all float32."""
    with jax.named_scope("kda_project"):
        R, H = cfg.kda_rank, cfg.kda_heads
        qkv = lm.dot(u, p["w_qkv"], cfg.dtype)
        narrow = lm.dot(u, p["w_fgb"], cfg.dtype)
        f = lm.dot(narrow[..., :R], p["w_f2"], cfg.dtype)
        log_a = -lm.over_lanes(jnp.exp(p["a_log"]), cfg.kda_head_dim) \
            * jax.nn.softplus(f + p["dt_bias"])
        b = jax.nn.sigmoid(narrow[..., 2 * R:2 * R + H])
        if cfg.kda_neg_eigval:
            b = 2.0 * b
        gate = jax.nn.sigmoid(
            lm.dot(narrow[..., R:2 * R], p["w_g2"], cfg.dtype) + p["g_bias"])
        return qkv, log_a, b, gate


def _conv(qkv, p, window, ok):
    with jax.named_scope("kda_project"):
        return lm.short_conv(qkv, p["conv_w"], window, ok)


def _heads(qkv, cfg: KimiConfig):
    """The convolution's output [..., 3I] -> q, k, v [..., H, P]: q and k
    of length 1 a head, q times P^-1/2."""
    H, P = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (t.reshape(*t.shape[:-1], H, P)
               for t in jnp.split(qkv, 3, axis=-1))

    def unit(t):
        return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * P ** -0.5, unit(k), v


def _kda_out(x, o, gate, p, cfg: KimiConfig):
    """o [B,M,H,P]: the norm a head, the gate, W_o, the residual."""
    B, M = o.shape[:2]
    with jax.named_scope("kda_project"):
        y = rms_norm(o, p["o_norm"], cfg.norm_eps).reshape(B, M, -1) * gate
        return x + lm.dot(y, p["w_o"], cfg.dtype)


def _kda_first(x, p, cfg: KimiConfig, cache, i, on):
    """KDA layer `i` of the stack over every slot's first lane, x [B,1,D]
    float32, by the recurrence: -> (x, cache). `on` [B]: the slots whose
    lane is valid; the others keep their state and window bit for bit."""
    H, P = cfg.kda_heads, cfg.kda_head_dim
    B = x.shape[0]
    with jax.named_scope("attn"):
        qkv, log_a, b, gate = _kda_in(rms_norm(x, p["norm"], cfg.norm_eps),
                                      p, cfg)
        with jax.named_scope("kda_project"):
            window = lax.dynamic_index_in_dim(cache["conv"], i, 0,
                                              keepdims=False)
        qkv, window = _conv(qkv, p, window, on[:, None])
        with jax.named_scope("kda_project"):
            conv = lax.dynamic_update_index_in_dim(cache["conv"], window, i,
                                                   0)
            q, k, v = _heads(qkv[:, 0], cfg)                     # [B, H, P]
        with jax.named_scope("kda_update"):
            a = jnp.exp(log_a[:, 0]).reshape(B, H, P)
            kda, o = kda_update(cache["kda"], i, a, k, q, v, b[:, 0], on)
        x = _kda_out(x, o[:, None], gate, p, cfg)
    return x, {**cache, "kda": kda, "conv": conv}


def _delta_chunk(q, k, v, log_a, b, s):
    """The chunked form for m lanes of one slot: q, k, log_a [m,H,N], v
    [m,H,P], b [m,H], the state s [H,N,P] before them -> (o [m,H,P], the
    state after them). A lane that is not valid comes with log_a = 0 and
    b = 0."""
    m = q.shape[0]
    g = jnp.cumsum(log_a, axis=0)                                  # [m,H,N]
    lane = jnp.arange(m)
    upto = (lane[None, :] <= lane[:, None])[:, :, None, None]     # [i,j,1,1]
    # exp(g_i - g_j) for j <= i: the difference first, never a quotient
    within = jnp.where(upto, jnp.exp(jnp.where(
        upto, g[:, None] - g[None, :], 0.0)), 0.0)                 # [i,j,H,N]
    kk = jnp.einsum("ihc,jhc,ijhc->hij", k, k, within, precision=_HIGHEST)
    qk = jnp.einsum("ihc,jhc,ijhc->hij", q, k, within, precision=_HIGHEST)
    strict = (lane[None, :] < lane[:, None])[None]
    n = -jnp.where(strict, kk * b.T[:, :, None], 0.0)        # N = -A [H,m,m]
    # (I + A)^-1 = (I + N)(I + N^2)(I + N^4)..: N^m = 0
    inverse = jnp.eye(m, dtype=n.dtype) + n
    power = n
    for _ in range(max(0, math.ceil(math.log2(m)) - 1)):
        power = jnp.einsum("hij,hjk->hik", power, power, precision=_HIGHEST)
        inverse = inverse + jnp.einsum("hij,hjk->hik", inverse, power,
                                       precision=_HIGHEST)
    grown = jnp.exp(g)
    held = jnp.einsum("ihc,hcp->ihp", k * grown, s, precision=_HIGHEST)
    u = jnp.einsum("hij,jhp->ihp", inverse, b[..., None] * (v - held),
                   precision=_HIGHEST)
    o = (jnp.einsum("ihc,hcp->ihp", q * grown, s, precision=_HIGHEST)
         + jnp.einsum("hij,jhp->ihp", qk, u, precision=_HIGHEST))
    to_end = jnp.exp(g[-1][None] - g)                              # [m,H,N]
    s_new = grown[-1][..., None] * s + jnp.einsum(
        "jhc,jhp->hcp", k * to_end, u, precision=_HIGHEST)
    return o, s_new


def _kda_further(x, p, cfg: KimiConfig, cache, i, slot, ok):
    """The same layer over one slot's further lanes, x [1,M,D], by the
    chunked form from the state its first lane left, in runs of `SUBCHUNK`
    lanes and only as many runs as hold a valid lane: -> (x, cache)."""
    H, P = cfg.kda_heads, cfg.kda_head_dim
    M = x.shape[1]
    W = cache["conv"].shape[-1]
    m = min(SUBCHUNK, M)
    runs = -(-M // m)
    with jax.named_scope("attn"):
        qkv, log_a, b, gate = _kda_in(rms_norm(x, p["norm"], cfg.norm_eps),
                                      p, cfg)
        with jax.named_scope("kda_project"):
            window = lax.dynamic_slice(cache["conv"], (i, slot, 0),
                                       (1, 1, W))[0]
        qkv, window = _conv(qkv, p, window, ok)
        with jax.named_scope("kda_project"):
            conv = lax.dynamic_update_slice(cache["conv"], window[None],
                                            (i, slot, 0))
            q, k, v = _heads(qkv[0], cfg)                        # [M, H, P]
        with jax.named_scope("kda_chunk"):
            valid = ok[0]
            log_a = jnp.where(valid[:, None], log_a[0], 0.0).reshape(M, H, P)
            b = jnp.where(valid[:, None], b[0], 0.0)

            def by_run(t):
                t = jnp.pad(t, ((0, runs * m - M),) + ((0, 0),) * (t.ndim - 1))
                return t.reshape(runs, m, *t.shape[1:])

            lanes = tuple(by_run(t) for t in (q, k, v, log_a, b))
            s = lax.dynamic_slice(cache["kda"], (i, slot, 0, 0, 0),
                                  (1, 1, H, P, P))[0, 0]

            def run(r, carry):
                s, o = carry
                o_r, s = _delta_chunk(*(lax.dynamic_index_in_dim(
                    t, r, 0, keepdims=False) for t in lanes), s)
                return s, lax.dynamic_update_index_in_dim(o, o_r, r, 0)

            s, o = lax.fori_loop(
                0, (valid.sum() + m - 1) // m, run,
                (s, jnp.zeros((runs, m, H, P), jnp.float32)))
            kda = lax.dynamic_update_slice(cache["kda"], s[None, None],
                                           (i, slot, 0, 0, 0))
        x = _kda_out(x, o.reshape(1, runs * m, H, P)[:, :M], gate, p, cfg)
    return x, {**cache, "kda": kda, "conv": conv}


def _mla(x, p, cfg: KimiConfig, cache, i, pos0, pos, ok, slot=None):
    """MLA layer `i` of the stack: x [N,C,D] float32 += absorbed attention
    of its C lanes (positions `pos` [N,C], written where `ok`) against the
    carried rows, `mla.attention`'s whole form without the rotation, by
    this family's own layout of the weights: row n is slot n (N = B), or the
    one row is `slot`'s own lanes against that slot's rows alone."""
    B, C, _ = x.shape
    H, r = cfg.n_head, cfg.kv_lora_rank
    n, v = cfg.qk_nope_head_dim, cfg.v_head_dim
    lat, kr = cache["latent"], cache["k_rope"]
    with jax.named_scope("attn"):
        u = rms_norm(x, p["norm"], cfg.norm_eps)
        with jax.named_scope("mla_project"):
            q = lm.dot(u, p["wq"], cfg.dtype).reshape(B, C, H, cfg.qk_head_dim)
            ckr = lm.dot(u, p["wkva"], cfg.dtype)                 # [N,C,r+p]
            c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
            # a product batched by the head comes out head first (the CPU
            # backend has no other float32 product of two bf16 operands)
            q_abs = jnp.moveaxis(jnp.sum(jnp.einsum(
                "abchn,hnr->habcr", pieces(q[..., :n], cfg.dtype),
                p["w_uk"].astype(cfg.dtype),
                preferred_element_type=jnp.float32), axis=1), 0, 2).astype(
                    cfg.dtype)                                    # [N,C,H,r]
            q_r = q[..., n:].astype(cfg.dtype)
        with jax.named_scope("kv_update"):
            write = write_first if slot is None and C == 1 else cache_write
            lat = write(lat, i, c.astype(cfg.dtype), pos0, ok, slot)
            kr = write(kr, i, ckr[..., r:].astype(cfg.dtype), pos0, ok, slot)
        with jax.named_scope("mla_attend"):
            scale = 1.0 / math.sqrt(cfg.qk_head_dim)
            if slot is None and C == 1:
                mixed = mla_attend(q_abs[:, 0], q_r[:, 0], lat, kr, i,
                                   pos[:, 0], ok[:, 0], scale)[:, :, None]
            else:
                mixed = attend_rows(q_abs, q_r, rows(lat, i, slot),
                                    rows(kr, i, slot), pos, scale)
            # [N,H,C,r] float32, the heads before the lanes
        with jax.named_scope("mla_project"):
            o = jnp.moveaxis(jnp.sum(jnp.einsum(
                "abhcr,hrv->habcv", pieces(mixed, cfg.dtype),
                p["w_uv"].astype(cfg.dtype),
                preferred_element_type=jnp.float32), axis=1), 0, 2)
            x = x + lm.dot(o.reshape(B, C, H * v), p["wo"], cfg.dtype)
    return x, {**cache, "latent": lat, "k_rope": kr}


def _gqa(x, p, cfg: KimiConfig, cache, i, pos0, ok, slot=None):
    """Softmax layer `i` of the stack: x [N,C,D] float32 += gated
    grouped-head attention of its lanes against the carried rows of `k` and
    `v`. Row n is slot n at one lane (N = B, C = 1: `ops/gqa_attend.py`, to
    each slot's position), or the one row is `slot`'s own further lanes, the
    first at position pos0 [1], against that slot's rows a block at a time."""
    B, C, _ = x.shape
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.gqa_head_dim
    scale = 1.0 / math.sqrt(d)
    with jax.named_scope("attn"):
        with jax.named_scope("gqa_project"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
            # q stays float32: its two pieces meet the cached rows
            q, k, v = lm.gqa_qkv(u, p, G, R, d, cfg.dtype, jnp.float32)
            gate = jax.nn.sigmoid(lm.dot(u, p["w_gate"], cfg.dtype))
        if slot is None:
            with jax.named_scope("kv_update"):
                ck = rows_write(cache["k"], i, k[:, 0], pos0, ok[:, 0])
                cv = rows_write(cache["v"], i, v[:, 0], pos0, ok[:, 0])
            with jax.named_scope("gqa_attend"):
                # the leaves whole and the layer's index: the kernel's index
                # map picks a block where it lies, nothing slices a layer
                y = gqa_attend(q[:, 0], ck, cv, i, pos0, ok[:, 0],
                               scale)[:, None]                 # [B,1,G,R,d]
        else:
            with jax.named_scope("kv_update"):
                ck = lm.gqa_write_slot(cache["k"], i, slot, k[0], pos0[0],
                                       ok[0])
                cv = lm.gqa_write_slot(cache["v"], i, slot, v[0], pos0[0],
                                       ok[0])
            with jax.named_scope("gqa_attend"):
                # [C,G,R,d] -> [G, R C, d]: a head's queries side by side
                qs = jnp.transpose(q[0], (1, 2, 0, 3)).reshape(G, R * C, d)
                at = jnp.broadcast_to(
                    pos0[0] + jnp.tile(jnp.arange(C), R), (G, R * C))
                last = pos0[0] + jnp.maximum(ok[0].sum(), 1) - 1
                y = lm.gqa_attend_blocks(qs, ck, cv, i, slot, at, last,
                                         scale, cfg.dtype)
                y = jnp.transpose(y.reshape(G, R, C, d), (2, 0, 1, 3))[None]
        with jax.named_scope("gqa_project"):
            x = x + lm.dot(y.reshape(B, C, -1) * gate, p["wo"], cfg.dtype)
    return x, {**cache, "k": ck, "v": cv}


def _dense_mlp(x, p, cfg: KimiConfig):
    with jax.named_scope("mlp"):
        return x + _swiglu(rms_norm(x, p["norm"], cfg.norm_eps), p, cfg)


def _expert_mlp(x, p, experts_of_all_layers, j, cfg: KimiConfig, given, ok,
                packed: bool = False):
    """x [N,C,D] += the held experts' part of the routed sum + the shared
    expert, for expert layer j; `given` [E] += the (lane, expert) pairs of
    the lanes that are `ok`, over all E. `packed` (the rows are
    `lm.pack_lanes`'): a row that is not `ok` is no lane's and goes to no
    expert.

    The router scores all E experts and chooses K of them. A pair whose
    expert is held, e in first_expert..+E', goes to entry j E' + e -
    first_expert of the stack of every layer's held experts; a pair whose
    expert is not goes past the stack's end, where `moe._experts`
    (`first_expert` 0 of a stack shorter than the ids) gives it no row of
    any matrix and zeroes it. The stack is handed over whole: the groups of
    the other layers are empty, and nothing is sliced out of it."""
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


def _expert_counts(given, cfg: KimiConfig):
    """One expert layer's step in `COUNTS`' order but the positions: from
    the pairs `given` [E] each expert got over all the step's valid
    lanes."""
    with jax.named_scope("moe_router"):
        held = lax.dynamic_slice_in_dim(given, cfg.first_expert,
                                        cfg.experts_held)
        return jnp.stack([jnp.sum(held), jnp.sum(held > 0), jnp.max(held),
                          jnp.ones((), jnp.int32), jnp.zeros((), jnp.int32),
                          jnp.zeros((), jnp.int32),
                          jnp.sum(given)]).astype(jnp.uint32)


def _further_lanes(rest, mixer: str, mixer_stack, i, cfg: KimiConfig, cache,
                   pos, ok, prefilling):
    """One layer's mixer over the lanes after the first, rest [B,M,D] with
    ok [B,M], the first of them at position pos [B], for the slots
    `prefilling` a slot at a time (`lm.each_slot`, which has why the weights
    are sliced inside the body here)."""
    M = rest.shape[1]

    def slot(b, carry):
        rest, cache = carry
        p = lm.layer_weights(mixer_stack, i)
        xb, okb, at = lm.slot_lanes(b, rest, ok, pos)
        if mixer == "mla":
            xb, cache = _mla(xb, p, cfg, cache, i, at,
                             at[:, None] + jnp.arange(M), okb, slot=b)
        elif mixer == "gqa":
            xb, cache = _gqa(xb, p, cfg, cache, i, at, okb, slot=b)
        else:
            xb, cache = _kda_further(xb, p, cfg, cache, i, b, okb)
        return lm.put_lanes(rest, xb, b), cache

    return lm.each_slot(prefilling, slot, (rest, cache))


def _layer(mixer: str, i, mlp_i, j, params: Params, cfg: KimiConfig, pos0,
           on, further, prefilling, rounds, first, rest, cache, counts):
    """One layer: the mixer `mixer` with entry i of its stack, then the
    dense MLP with entry mlp_i of its stack (j None) or expert layer j's
    block. The mixer takes every slot's first lane all slots at once, then
    the further lanes of the slots that have any, a slot at a time; the
    MLP, which knows nothing of slots, every valid lane of the step in one
    call (`lm.all_lanes`)."""
    dense = j is None
    mlp_stack = params["dense" if dense else "moe"]
    given = jnp.zeros((cfg.n_experts,), jnp.int32)
    p = lm.layer_weights(params[mixer], i)
    if mixer == "mla":
        first, cache = _mla(first, p, cfg, cache, i, pos0, pos0[:, None],
                            on[:, None])
    elif mixer == "gqa":
        first, cache = _gqa(first, p, cfg, cache, i, pos0, on[:, None])
    else:
        first, cache = _kda_first(first, p, cfg, cache, i, on)

    def mlp(x, ok, g, given):
        mp = lm.layer_weights(mlp_stack, mlp_i, turn=g)
        if dense:
            return _dense_mlp(x, mp, cfg), given
        return _expert_mlp(x, mp, params["experts"], j, cfg, given, ok,
                           packed=g is not None)

    if rest is None:
        first, given = mlp(first, on[:, None], None, given)
    else:
        # the loop writes the leaves where the first lanes read them: its
        # lanes wait for theirs (`lm.each_slot`; the experts' counts no
        # longer tie the two, and a leaf through the barrier is re-laid)
        first, rest = lax.optimization_barrier((first, rest))
        rest, cache = _further_lanes(rest, mixer, params[mixer], i, cfg,
                                     cache, pos0 + 1, further, prefilling)
        first, rest, given = lm.all_lanes(mlp, first, on, rest, further,
                                          rounds, given)
    if not dense:
        counts = counts + _expert_counts(given, cfg)
    return first, rest, cache, counts


def _read_positions(cache, pos0, length, on, further):
    """The positions whose rows one attention layer read for a step's valid
    lanes: every slot's first lane to its block through `mla_attend` or
    `gqa_attend` (all T in their plain forms); a prefilling slot's further
    lanes all T of latent rows, or the grouped-head blocks to the slot's
    last lane."""
    if "latent" in cache:
        T = cache["latent"].shape[2]
        read = read_positions(pos0, on, T)
        if further is not None:
            read = read + (further.any(axis=1).sum() * T).astype(jnp.uint32)
        return read
    T = cache["k"].shape[3]
    read = gqa_read(pos0, on, T)
    if further is not None:
        turns, block = lm.gqa_blocks(pos0 + jnp.maximum(length, 1) - 1, T)
        read = read + jnp.sum(jnp.where(further.any(axis=1),
                                        turns * block, 0))
    return read.astype(jnp.uint32)


def _logits(params: Params, x, cfg: KimiConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: KimiConfig, program: int):
    """Both step programs. A layer computes a lane only where the plan put
    a token (`models/lm.py`, "The lanes of a chunk"): every slot's first
    lane all slots at once, which is the whole decode program; the lanes
    after it through a layer's mixer a slot at a time, only the slots that
    have them, C of them a slot with the last one padding
    (`lm.split_lanes`), and through its MLP as rows of the first lanes' call.

    The dense layers stand before the loops. The loops carry the cache, one
    buffer a leaf from layer to layer, written in place where the caller
    donates it, and close over the experts' stack, which they never
    slice."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    counts = jnp.zeros((len(COUNTS),), jnp.uint32)
    leaves = {k: v for k, v in cache.items() if k != "counts"}
    index = _stack_index(cfg)
    n_dense = cfg.n_dense_layer
    # The layers as runs of one kind (a mixer and dense or experts): one
    # loop over the runs, whose body holds one loop a kind, and a kind's
    # loop turns as many times as the run is long if the run is of that
    # kind and not at all if it is not. No branch takes a layer's kind: a
    # leaf that passes through a conditional untouched is copied on its way
    # (1.9 GB of state a step for each MLA layer, found by
    # rehearse/compile_kimi_for_v5e.py), and a loop that turns no time hands
    # its carry on where it lies. Nothing of a layer stands outside the
    # runs' loop either: a trace holds one event a step for it and none of
    # the gaps between its operations.
    kinds = [(mixer, l < n_dense) for l, mixer in enumerate(cfg.layer_types)]
    runs = []                             # (kind, first layer, layers)
    for l, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1])
    bodies = sorted(set(kinds))
    first_layer = jnp.asarray([first_l for _, first_l, _ in runs])
    turns = {kind: jnp.asarray([n if k == kind else 0 for k, _, n in runs])
             for kind in bodies}
    mixer_at = jnp.asarray([at[mixer] for at, mixer in zip(
        index, cfg.layer_types)])

    def layer(kind, l, carry):
        mixer, dense = kind
        return _layer(mixer, mixer_at[l], l if dense else l - n_dense,
                      None if dense else l - n_dense, params, cfg, pos0, on,
                      further, prefilling, rounds, *carry)

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
        attended = jnp.sum(jnp.where(ok, pos0[:, None] + lane + 1, 0))
        counts = counts.at[COUNTS.index("attended_positions")].set(
            attended.astype(jnp.uint32))
        counts = counts.at[COUNTS.index("read_positions")].set(
            _read_positions(cache, pos0, length, on, further))
        counts = cache["counts"].at[program].add(counts)
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {**leaves, "counts": counts})


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: KimiConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0; the state does not read it. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: KimiConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): the recurrence through the
    delta-rule kernel and attention over the cached rows, one token a slot;
    the chunk program's first lane, and nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, 0)
