"""Keye-VL-2.0's language model for serving: grouped-head attention over
the rows a learned indexer chooses, then routed experts.

What is served is `Kwai-Keye/Keye-VL-2.0-30B-A3B` (`model_type: KeyeVL2`;
preset `keye-vl-2.0-30b-a3b`), the language model alone: the vision tower
has no key in the published config and is not built; what an image would
give the program is three unequal position streams, which it takes. 48
layers, every one the same. With d the hidden size 2,048, eps 1e-6, no
bias anywhere, u = RMSNorm(x):

    q = u W_q -> [32, 128], k, v = u W_k, u W_v -> [4, 128]; RMSNorm over a
      head's 128 on q and k; rotary on q and k (rotate-half, theta 1e7):
      frequency pair i of 64 takes its angle from position stream
      section(i) of `mrope_section` [16, 24, 24]
    indexer (`sa_config`): qI = u W_qI -> [16, 64], kI = LayerNorm(u W_kI)
      -> [64] (one key for all 16 heads), w = u W_w -> [16] times 16^-1/2
      64^-1/2; rotary on qI and kI's 32 pairs at stream 0;
      I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]), s <= t, float32
    S_t = the 2,048 positions of largest I[t, .], all of them while
      t < 2,048, ties to the lower index: the set, exactly
    o[t, h] = softmax_{s in S_t}(q[t, h] . k[s, h // 8] / sqrt(128))
      v[s, h // 8];  x += o W_o
    h = RMSNorm(x); p = softmax(h W_r) over 128, float32; the 8 largest,
      renormalised; x += sum_e p_e W_d^e (silu(W_g^e h) * W_u^e h)
    final RMSNorm, untied head, logits float32

What the config has no key for is assumed, each with its reason in the
benchmark's configuration file (`assumed`): the norm on q and k (the keys
are Qwen3-MoE's), that the indexer reads the normed hidden state, its key's
LayerNorm (scale and bias, eps 1e-6), rotary on all 64 of its dimensions,
w's constant scale, no always-kept first or local tokens.

The cache names three leaves a token (`CACHE_TOKEN_AXIS`): `k` and `v`
[layers, slots, T, 4 x 128] and the indexer's key `ik` [layers, slots, T,
64], token-major: a token's values side by side, a key-value head 128
lanes of them, so that a block of positions is one stretch of the leaf, a
head's keys are lanes of it as they lie, and a chosen row is whole
wherever it is read by index (positions on the lanes, granite's layout,
would make a gather of rows a gather of columns); and `counts`, the
programs' own. `serve/kv_cache.py` pools all three by the block.

Both programs take every slot's first lane all slots at once
(`_attend_first`: the indexer over the slot's `ik` rows, `ops/dsa.py`'s
choice, and `ops/dsa_attend.py`: on the chip a kernel that reads the
slot's rows of k and v once, to its position, with the set as a mask, and
elsewhere a gather of the chosen rows and attention over them: the whole
decode program) and a chunk's further lanes a slot at a time and only for
the slots that prefill (`_attend_further`: the same set as a mask over the
slot's rows, plain; `models/lm.py`, "The lanes of a chunk"; the experts
take those lanes as rows of the first lanes' call, `lm.all_lanes`).
The experts of all layers are one stack `[layers x 128, d, 768]` that no
loop slices (`models/kimi.py`'s form): a layer hands `moe._experts` the
whole stack with its ids offset by the layer.

Float32 are the norms' scales, the LayerNorm, the router, the residual
stream, everything projected, the indexer's scores and the choice,
attention's scores and the logits. A product's operands are bf16, the
weight as it is held and the activation as the two bf16 pieces that add up
to it (`lm.dot`; the indexer's query against the `ik` rows too); q, the
rows and attention's weights go as one piece.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import lm, moe as _moe
from ray_tpu.models.gpt2 import _layer_norm
from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.models.mla import cache_write, rows, write_first
from ray_tpu.ops import dsa
from ray_tpu.ops.dsa_attend import (dsa_attend, read_positions as dsa_read,
                                    rows_chosen)

Params = Any
SUBLANES = 32


@dataclasses.dataclass(frozen=True)
class KeyeConfig:
    vocab_size: int = 151936
    n_layer: int = 48
    d_model: int = 2048
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_section: tuple = (16, 24, 24)
    index_heads: int = 16            # sa_config.indexer_num_heads
    index_head_dim: int = 64         # sa_config.indexer_head_dim
    index_topk: int = 2048           # sa_config.topk
    d_ff_expert: int = 768           # moe_intermediate_size
    n_experts: int = 128
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-6
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        object.__setattr__(self, "mrope_section", tuple(self.mrope_section))
        assert sum(self.mrope_section) * 2 == self.head_dim, \
            self.mrope_section
        assert self.n_head % self.n_kv_head == 0

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @property
    def kv_width(self) -> int:
        """Values a token's key (or value) leaves in the cache, a layer."""
        return self.n_kv_head * self.head_dim

    @property
    def index_width(self) -> int:
        """The columns of W_qI, W_kI and W_w side by side, padded to whole
        lane tiles."""
        w = self.index_heads * (self.index_head_dim + 1) + self.index_head_dim
        return w + -w % 128

    @classmethod
    def preset(cls, name: str, **overrides) -> "KeyeConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # Kwai-Keye/Keye-VL-2.0-30B-A3B config.json: the defaults
    "keye-vl-2.0-30b-a3b": dict(),
    "keye-tiny": dict(
        vocab_size=512, n_layer=3, d_model=64, n_head=4, n_kv_head=2,
        head_dim=16, mrope_section=(2, 3, 3), index_heads=2,
        index_head_dim=8, index_topk=16, d_ff_expert=32, n_experts=8,
        experts_per_token=3, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): keys, values and the
# indexer's key hold a value a token, along axis 2
CACHE_TOKEN_AXIS = {"k": 2, "v": 2, "ik": 2}

# the columns of the cache's `counts` leaf, each a sum over a program's
# executions (`deepseek.COUNTS`' first four: over the layers, the (lane,
# expert) rows of the valid lanes, the experts that got at least one, the
# most that one of them got, and 1); once a step, over the valid lanes,
# the positions the indexer scored (pos + 1 a lane), the rows the choice
# left attention (min(pos + 1, topk) a lane), and the positions whose rows
# of k and v one layer's attention fetched for them (`_read_positions`: on
# the chip more than were chosen, a first lane's kernel reads to the
# slot's position)
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "positions_indexed", "rows_selected",
          "read_positions")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02) and every down
# projection (W_o, W_d) 0.02 / sqrt(2 n_layer), as a fresh Hugging Face
# model; the token table 0.3 by `models/deepseek.py`'s argument (with the
# table at 0.02 the stream is a fifth of what the first layers add to it
# and any rounding becomes another expert for some token; the head is
# untied). The norms' scales are 1 but q's, `Q_NORM_SCALE`: q and k come out
# of their norms with 128 values of size 1, so q . k / sqrt(128) spreads
# by 1 over a slot's rows, the softmax over 2,048 of them is nearly a mean
# of 750 independent values, and attention adds a twentieth of what the
# stream holds: a wrong set would move the logits by less than the experts'
# bf16 rounding. At 2 the scores spread by 2, some 100 rows carry a head's
# weight, as a trained model's few do, and attention adds a fifth of the
# stream. (At 4 one row carries it, and whether a rounding at the set's
# boundary swaps that row decides a reading: a coin toss a token.) The
# indexer's own (W_qI, W_kI, W_w at 0.02, the LayerNorm 1 and 0) spread a
# query's scores by 0.58, six hundred times what the bf16 rounding of a
# cached key moves one; over 8-13 thousand rows that still puts two rows a
# query a layer on the boundary's other side
# (`benchmarks/chip/rehearse/keye_boundary.py`, the benchmark's `limits`).
EMBED_STD = 0.3
Q_NORM_SCALE = 2.0


def _init_layer(key: jax.Array, l, cfg: KeyeConfig) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 8)
    pd, D, d = cfg.param_dtype, cfg.d_model, cfg.head_dim
    H, G = cfg.n_head, cfg.n_kv_head
    J, e, E, F = (cfg.index_heads, cfg.index_head_dim, cfg.n_experts,
                  cfg.d_ff_expert)
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)

    def expert(i):
        k3 = jax.random.split(jax.random.fold_in(ks[7], i), 3)
        return {"wg": lm.normal(k3[0], (D, F), 0.02, pd),
                "wu": lm.normal(k3[1], (D, F), 0.02, pd),
                "wd": lm.normal(k3[2], (F, D), resid_std, pd)}

    layer = {
        "attn_norm": lm.ones(D),
        # W_q, W_k and W_v side by side: one product
        "w_qkv": lm.normal(ks[0], (D, (H + 2 * G) * d), 0.02, pd),
        "q_norm": {"scale": jnp.full((d,), Q_NORM_SCALE, jnp.float32)},
        "k_norm": lm.ones(d),
        "wo": lm.normal(ks[1], (H * d, D), resid_std, pd),
        # the indexer's three projections side by side, W_qI [D, J e], W_kI
        # [D, e] and W_w [D, J], padded to whole lane tiles: one product (a
        # matrix of 16 columns alone is laid out again on every step:
        # `models/kimi.py`, `w_fgb`)
        "w_index": jnp.concatenate([
            lm.normal(ks[2], (D, J * e), 0.02, pd),
            lm.normal(ks[3], (D, e), 0.02, pd),
            lm.normal(ks[4], (D, J), 0.02, pd),
            jnp.zeros((D, cfg.index_width - J * (e + 1) - e), pd)], axis=1),
        "ki_norm": {"scale": jnp.ones((e,), jnp.float32),
                    "bias": jnp.zeros((e,), jnp.float32)},
        "mlp_norm": lm.ones(D),
        "router": lm.normal(ks[5], (D, E), 0.02, jnp.float32)}
    # a loop, not `vmap`: one expert's three matrices are the program
    # (`models/kimi.py`)
    return {"layer": layer, "experts": lax.map(expert, jnp.arange(E))}


def init_layer(key: jax.Array, l: int, cfg: KeyeConfig) -> Params:
    """Layer l's weights from `fold_in(key, l)` and nothing else: `layer`
    (attention, the indexer, the norms, the router) and `experts` ([128,
    ...] a matrix), by the one compiled program (`lm.layer_program`): a
    layer made alone is, to the bit, the layer in `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg)(key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: KeyeConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def init_params(key: jax.Array, cfg: KeyeConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `layers`,
    one stack on a leading axis, and `experts` [layers x 128, ...], every
    layer's experts end to end; allocated once, a layer written at a time
    (`lm.put_layer`, donated), so the most that exists beside the tree is
    one layer."""
    out = dict(init_ends(key, cfg))
    for l in range(cfg.n_layer):
        made = init_layer(key, l, cfg)
        if l == 0:
            out["layers"] = lm.empty_stack(made["layer"], cfg.n_layer)
            out["experts"] = lm.empty_stack(
                jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                            a.dtype),
                             made["experts"]), cfg.n_layer * cfg.n_experts)
        out["layers"] = lm.put_layer(out["layers"], made["layer"],
                                     jnp.int32(l))
        out["experts"] = lm.put_layer(out["experts"], made["experts"],
                                      jnp.int32(l))
        del made
    return out


resident_params = lm.resident_params


def resident_specs(cfg: KeyeConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the keye family is served on one chip: its weights and its three "
        "kinds of row have no partition specs yet (tensor_parallel_size > "
        "1 is GPT-2's)")


def num_params(cfg: KeyeConfig) -> int:
    D, d, e, J = cfg.d_model, cfg.head_dim, cfg.index_head_dim, \
        cfg.index_heads
    attention = 2 * D * cfg.n_head * d + 2 * D * cfg.kv_width + 2 * d
    indexer = D * cfg.index_width + 2 * e
    experts = cfg.n_experts * 3 * D * cfg.d_ff_expert
    layer = attention + indexer + D * cfg.n_experts + experts + 2 * D
    return cfg.n_layer * layer + 2 * cfg.vocab_size * D + D


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: KeyeConfig, batch: int, max_len: Optional[int] = None):
    """{"k", "v" [L, B, T, 4 x 128], "ik" [L, B, T, 64]} in the compute
    dtype, a token's values side by side, and `counts` uint32 [2,
    len(COUNTS)], the programs' own, row 0 `decode_step`'s and row 1
    `prefill_chunk`'s (they wrap: a reader takes differences modulo
    2**32)."""
    T = max_len or cfg.max_seq_len
    L = cfg.n_layer
    return {"k": jnp.zeros((L, batch, T, cfg.kv_width), cfg.dtype),
            "v": jnp.zeros((L, batch, T, cfg.kv_width), cfg.dtype),
            "ik": jnp.zeros((L, batch, T, cfg.index_head_dim), cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def rope_angles(positions, cfg: KeyeConfig):
    """positions [3, ...] (the three streams of a token's position) ->
    ((cos, sin) [..., 64] for q and k, pair i's angle from stream
    section(i); (cos, sin) [..., 32] for the indexer, from stream 0)."""
    d, e = cfg.head_dim, cfg.index_head_dim
    stream = np.repeat(np.arange(3), cfg.mrope_section)
    at = jnp.moveaxis(positions.astype(jnp.float32)[stream], 0, -1)
    ang = at * (1.0 / cfg.rope_theta
                ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang_i = positions[0].astype(jnp.float32)[..., None] * (
        1.0 / cfg.rope_theta ** (jnp.arange(0, e, 2, dtype=jnp.float32) / e))
    return (jnp.cos(ang), jnp.sin(ang)), (jnp.cos(ang_i), jnp.sin(ang_i))


def _project(x, p, cfg: KeyeConfig, angles):
    """x [N,M,D] float32 -> q [N,M,G,R,d] and k, v [N,M,G d] in the compute
    dtype, qI [N,M,J,e], kI [N,M,e] (compute dtype) and w [N,M,J]."""
    N, M, _ = x.shape
    H, G, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    J, e = cfg.index_heads, cfg.index_head_dim
    (cos, sin), (cos_i, sin_i) = angles
    u = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("gqa_project"):
        qkv = lm.dot(u, p["w_qkv"], cfg.dtype)
        q = rms_norm(qkv[..., :H * d].reshape(N, M, H, d), p["q_norm"],
                     cfg.norm_eps)
        k = rms_norm(qkv[..., H * d:(H + G) * d].reshape(N, M, G, d),
                     p["k_norm"], cfg.norm_eps)
        q = apply_rope(q, cos[:, :, None], sin[:, :, None])
        k = apply_rope(k, cos[:, :, None], sin[:, :, None])
        v = qkv[..., (H + G) * d:]
        q = q.astype(cfg.dtype).reshape(N, M, G, H // G, d)
        k = k.astype(cfg.dtype).reshape(N, M, G * d)
        v = v.astype(cfg.dtype)
    with jax.named_scope("dsa_index"):
        iq = lm.dot(u, p["w_index"], cfg.dtype)
        qi = apply_rope(iq[..., :J * e].reshape(N, M, J, e),
                        cos_i[:, :, None], sin_i[:, :, None])
        ki = _layer_norm(iq[..., J * e:(J + 1) * e], p["ki_norm"],
                         cfg.norm_eps)
        ki = apply_rope(ki[:, :, None], cos_i[:, :, None],
                        sin_i[:, :, None])[:, :, 0].astype(cfg.dtype)
        w = iq[..., (J + 1) * e:(J + 1) * e + J] * (J * e) ** -0.5
    return q, k, v, qi, ki, w


def _attn_out(x, y, p, cfg: KeyeConfig):
    N, M, _ = x.shape
    with jax.named_scope("gqa_project"):
        return x + lm.dot(y.reshape(N, M, -1), p["wo"], cfg.dtype)


def _attend_first(x, p, cfg: KeyeConfig, cache, l, pos, angles, on):
    """Layer l's attention over every slot's first lane, x [B,1,D] float32
    at row pos [B]: the rows written, the indexer over the slot's `ik`
    rows, the choice, and attention over the chosen rows of k and v, each
    in the form the platform reads them (`ops/dsa_attend.py`: the leaves
    whole and the set as a mask through the kernel, or the set's rows
    gathered by index): -> (x, cache)."""
    with jax.named_scope("attn"):
        q, k, v, qi, ki, w = _project(x, p, cfg, angles)
        with jax.named_scope("kv_update"):
            # one scatter a leaf for all slots (`models/kimi.py`)
            ck = write_first(cache["k"], l, k, pos, on[:, None])
            cv = write_first(cache["v"], l, v, pos, on[:, None])
            cik = write_first(cache["ik"], l, ki, pos, on[:, None])
        with jax.named_scope("dsa_index"):
            scores = dsa.index_scores(qi, w, rows(cik, l), pos[:, None])
        with jax.named_scope("dsa_select"):
            chosen = rows_chosen(scores[:, 0], cfg.index_topk)
        with jax.named_scope("dsa_attend"):
            y = dsa_attend(q[:, 0], ck, cv, l, pos, on, chosen,
                           1.0 / math.sqrt(cfg.head_dim))
        x = _attn_out(x, y[:, None], p, cfg)
    return x, {**cache, "k": ck, "v": cv, "ik": cik}


def _lanes_a_run(M: int) -> int:
    """The further lanes `_attend_further` takes at a time, of M."""
    return SUBLANES if M % SUBLANES == 0 else M


def _attend_further(x, p, cfg: KeyeConfig, cache, l, slot, at, angles, ok):
    """The same over one slot's further lanes, x [1,M,D], the first of them
    at row `at` [1]: the set as a mask over the slot's rows, `SUBLANES`
    lanes at a time (their scores are [4, 8 x 32, T] floats, and the
    indexer's [2, 32 x 16, T]) and only as many runs as hold a valid
    lane."""
    M = x.shape[1]
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    T = cache["k"].shape[2]
    m = _lanes_a_run(M)
    with jax.named_scope("attn"):
        q, k, v, qi, ki, w = _project(x, p, cfg, angles)
        with jax.named_scope("kv_update"):
            ck = cache_write(cache["k"], l, k, at, ok, slot)
            cv = cache_write(cache["v"], l, v, at, ok, slot)
            cik = cache_write(cache["ik"], l, ki, at, ok, slot)

        def run(r, y):
            def lanes(a):
                return lax.dynamic_slice_in_dim(a, r * m, m, axis=1)

            with jax.named_scope("dsa_index"):
                scores = dsa.index_scores(
                    lanes(qi), lanes(w), rows(cik, l, slot),
                    at[:, None] + r * m + jnp.arange(m))
            with jax.named_scope("dsa_select"):
                keep = dsa.select_mask(scores[0], cfg.index_topk)  # [m, T]
            with jax.named_scope("dsa_attend"):
                # [m,G,R,d] -> [G, R m, d]: a head's queries side by side
                qs = jnp.transpose(lanes(q)[0], (1, 2, 0, 3)).reshape(
                    G, R * m, d)
                out = dsa.attend_masked(
                    qs, rows(ck, l, slot)[0].reshape(T, G, d),
                    rows(cv, l, slot)[0].reshape(T, G, d),
                    jnp.tile(keep, (R, 1))[None], 1.0 / math.sqrt(d))
                out = jnp.transpose(out.reshape(G, R, m, d), (2, 0, 1, 3))
                return lax.dynamic_update_slice_in_dim(y, out[None], r * m,
                                                       axis=1)

        y = lax.fori_loop(0, (ok.sum() + m - 1) // m, run,
                          jnp.zeros((1, M, G, R, d), jnp.float32))
        x = _attn_out(x, y, p, cfg)
    return x, {**cache, "k": ck, "v": cv, "ik": cik}


def _expert_mlp(x, p, experts_of_all_layers, l, cfg: KeyeConfig, given, ok,
                packed: bool = False):
    """x [N,C,D] += the routed sum of layer l's experts; `given` [E] += the
    (lane, expert) pairs of the lanes that are `ok`. The stack of every
    layer's experts is handed over whole with the ids offset by the layer
    (`models/kimi.py`'s form): the groups of the other layers are empty,
    and nothing is sliced out of it. `packed` (the rows are
    `lm.pack_lanes`'): a row that is not `ok` is no lane's and goes past
    the stack's end, to no expert."""
    B, C, D = x.shape
    K, E = cfg.experts_per_token, cfg.n_experts
    stack = experts_of_all_layers["wg"].shape[0]
    with jax.named_scope("mlp"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        _, _, gates, experts = _moe._route(h.reshape(B * C, D), p["router"],
                                           cfg)
        with jax.named_scope("moe_router"):
            given = given.at[experts.reshape(-1)].add(
                jnp.repeat(ok.reshape(-1), K).astype(jnp.int32))
        entry = l * E + experts
        if packed:
            entry = jnp.where(ok.reshape(-1, 1), entry, stack)
        routed = _moe._experts(
            h, gates.reshape(B, C, K), entry.reshape(B, C, K),
            *(experts_of_all_layers[w] for w in ("wg", "wu", "wd")),
            types.SimpleNamespace(n_experts=stack + 1, experts_per_token=K,
                                  dtype=jnp.float32),
            first_expert=jnp.int32(0))
        return x + routed, given


def _further_lanes(rest, params: Params, l, cfg: KeyeConfig, cache, pos,
                   positions, ok, prefilling):
    """Layer l's attention over the lanes after the first, rest [B,M,D] with
    ok [B,M], the first of them at row pos [B] and at `positions` [3,B,M],
    for the slots `prefilling` a slot at a time (`lm.each_slot`, which has
    why the weights are sliced inside the body here)."""
    M = rest.shape[1]

    def slot(b, carry):
        rest, cache = carry
        p = lm.layer_weights(params["layers"], l, turn=b)
        xb, okb, at = lm.slot_lanes(b, rest, ok, pos)
        angles = rope_angles(lax.dynamic_slice(
            positions, (0, b, 0), (3, 1, M)), cfg)
        xb, cache = _attend_further(xb, p, cfg, cache, l, b, at, angles, okb)
        return lm.put_lanes(rest, xb, b), cache

    return lm.each_slot(prefilling, slot, (rest, cache))


def _read_positions(T: int, pos0, on, further, cfg: KeyeConfig):
    """The positions whose rows of k and v one layer's attention fetched
    for a step's valid lanes: every slot's first lane what `dsa_attend`
    reads for it (its position rounded up to a block through the kernel,
    its chosen rows plain), a prefilling slot's further lanes all T a run."""
    read = dsa_read(pos0, on, T, cfg.index_topk)
    if further is not None:
        m = _lanes_a_run(further.shape[1])
        read = read + (jnp.sum((further.sum(axis=1) + m - 1) // m)
                       * T).astype(jnp.uint32)
    return read


def _expert_counts(given):
    """`COUNTS`' first four, one expert layer's."""
    with jax.named_scope("moe_router"):
        return jnp.stack([jnp.sum(given), jnp.sum(given > 0), jnp.max(given),
                          jnp.ones((), jnp.int32)]).astype(jnp.uint32)


def _logits(params: Params, x, cfg: KeyeConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, positions, length, active,
             cfg: KeyeConfig, program: int):
    """Both step programs: tokens [B,C], the row pos0 [B] of each slot's
    first lane, `positions` [3,B,C] every lane's three position streams.
    The layers are one loop that carries the cache, one buffer a leaf,
    written in place where the caller donates it, and closes over the
    experts' stack, which it never slices."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    angles = rope_angles(positions[:, :, :1], cfg)
    if rest is not None:
        # the padding lane stands one past the chunk's last
        after = jnp.concatenate(
            [positions[:, :, 1:], positions[:, :, -1:] + 1], axis=2)
    leaves = {k: v for k, v in cache.items() if k != "counts"}

    def layer(l, carry):
        first, rest, leaves, counts = carry
        given = jnp.zeros((cfg.n_experts,), jnp.int32)
        p = lm.layer_weights(params["layers"], l)
        first, leaves = _attend_first(first, p, cfg, leaves, l, pos0, angles,
                                      on)
        if rest is None:
            first, given = _expert_mlp(first, p, params["experts"], l, cfg,
                                       given, on[:, None])
        else:
            # the loop writes the leaves where the first lanes read them: its
            # lanes wait for theirs (`lm.each_slot`; the experts' counts no
            # longer tie the two, and a leaf through the barrier is re-laid)
            first, rest = lax.optimization_barrier((first, rest))
            rest, leaves = _further_lanes(rest, params, l, cfg, leaves,
                                          pos0 + 1, after, further,
                                          prefilling)
            # the experts know nothing of slots: every valid lane of the
            # step is a row of one call
            first, rest, given = lm.all_lanes(
                lambda x, ok, g, given: _expert_mlp(
                    x, lm.layer_weights(params["layers"], l, turn=g),
                    params["experts"], l, cfg, given, ok, packed=True),
                first, on, rest, further, rounds, given)
        return first, rest, leaves, counts + _expert_counts(given)

    with jax.named_scope("layers"):
        first, rest, leaves, counts = lax.fori_loop(
            0, cfg.n_layer, layer,
            (first, rest, leaves, jnp.zeros((4,), jnp.uint32)))
    x = lm.join_lanes(first, rest, C)
    with jax.named_scope("moe_router"):
        seen = jnp.where(ok, pos0[:, None] + lane + 1, 0)
        step = jnp.stack([
            jnp.sum(seen), jnp.sum(jnp.minimum(seen, cfg.index_topk)),
            _read_positions(cache["k"].shape[2], pos0, on, further, cfg)
        ]).astype(jnp.uint32)
        counts = cache["counts"].at[program].add(
            jnp.concatenate([counts, step]))
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {**leaves, "counts": counts})


def _text_positions(pos0, lanes: int):
    """A text token's three position streams are its row, three times:
    plain RoPE."""
    at = pos0[:, None] + jnp.arange(lanes)
    return jnp.broadcast_to(at, (3,) + at.shape)


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: KeyeConfig,
                  positions: Optional[jax.Array] = None):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0. `positions` [3, B, C], every lane's three position streams where
    they are not its row's (an image's patches; the engine sends text, and
    passes none). Donate `cache`."""
    if positions is None:
        positions = _text_positions(pos0, tokens.shape[1])
    return _forward(params, cache, tokens, pos0, positions, length, active,
                    cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: KeyeConfig,
                positions: Optional[jax.Array] = None):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): one token a slot, the chunk
    program's first lane and nothing else of it. `positions` [3, B] as
    `prefill_chunk`'s."""
    positions = (_text_positions(pos, 1) if positions is None
                 else positions[:, :, None])
    return _forward(params, cache, tokens[:, None], pos, positions,
                    active.astype(jnp.int32), active, cfg, 0)
