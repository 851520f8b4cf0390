"""LongCat-Flash's layers for serving: a layer is a double layer, two
latent-attention (MLA) sublayers and two dense FFNs on one stream, and one
expert block whose result joins the stream a sublayer after it was asked
for (shortcut-connected MoE); the router scores zero-compute experts beside
the routed ones.

What is served is `meituan-longcat/LongCat-Flash-Chat` (`attention_method:
MLA`; preset `longcat-flash-chat`): 28 layers, d = 6,144, RMSNorm eps 1e-5,
no bias anywhere, untied head. With x the stream:

    a  = x + MLA_0(RMSNorm_a0(x))
    h0 = RMSNorm_m0(a)
    r  = ExpertBlock(h0)             # reads h0; nothing reads r until the end
    b  = a + SwiGLU_0(h0)            # dense, 6144 -> 12288 -> 6144
    c  = b + MLA_1(RMSNorm_a1(b))
    x' = c + SwiGLU_1(RMSNorm_m1(c)) + r       # the shortcut lands here
    final RMSNorm, head, logits float32

    MLA_i (`models/mla.py`, which `models/deepseek.py` shares: 64 heads of
      128 un-rotated + 64 rotated lanes, v = 128; a query latent of 1,536
      and a key-value latent of 512, each normed and times sqrt(d / rank):
      2 and 3.4641), RoPE theta 1e7, in its whole form: everything projected
      float32, the rows bf16

    ExpertBlock (E = 512 routed SwiGLU experts 6144 -> 2048 -> 6144, Z = 256
      zero-compute experts of type identity, K = 12 a token, no shared
      expert, `routed_scaling_factor` 6):
      s = softmax(h0 W_r) over all 768, float32; the 12 largest of s + bias
        chosen; gates g_k = 6 s_{e_k}, not renormalised       (`moe._route`)
      r = sum_{k: e_k < 512} g_k SwiGLU^(e_k)(h0)
          + (sum_{k: e_k >= 512} g_k) h0                     (`moe._experts`)

Nothing in the order of r, SwiGLU_0, MLA_1 and SwiGLU_1 is forced but the
data's: `_double_layer` asks for r where h0 exists and adds it at the
layer's end, and the compiler places the expert block anywhere between.

**The chip's share** is `models/kimi.py`'s: `experts_held` E' and
`first_expert` say which of the 512 routed experts of every layer this
replica holds; the router keeps its 768 outputs and its 12 a token; a pair
whose expert is absent adds nothing, a pair whose expert is zero-compute is
every chip's alike (the zero term whole); `vocab_size` rows of the table and
of the head are this chip's slice; the held experts of all layers are one
stack `[layers x E', ...]` that no loop slices.

The cache holds rows alone (`models/__init__.py`): `latent` and `k_rope` [2
n_layer, slots, T, 512 | 64], sublayer i of layer l at entry 2 l + i
(`CACHE_TOKEN_AXIS`), and `counts`, the programs' own (`COUNTS`: Kimi's
seven and `zero_rows`).

Both programs are one function: `decode_step` is every slot's first lane
through the layers, all slots at once, and `prefill_chunk` that plus the
further lanes of the slots that prefill, through attention a slot at a time
(`lm.each_slot`) and through the router, the experts and the dense FFNs as
rows of the first lanes' call (`lm.pack_lanes`): two forms and no third; r
is carried across the second sublayer in both.

The weights exist only in the dtype the replica holds them; float32 are the
norms' scales, the router and its bias, and so are the residual stream, r,
everything projected, the router and its softmax, the gates and the logits.
A product's operands are bf16, the weight as it is held and the activation
as the two bf16 pieces that add up to it (`lm.dot`, `moe._experts` for
float32 rows); the rows and the queries that meet them are bf16.
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

from ray_tpu.models import lm, mla, moe as _moe
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.mla_attend import read_positions

Params = Any


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    n_layer: int = 28                # double layers
    d_model: int = 6144
    d_ff: int = 12288                # ffn_hidden_size: each dense SwiGLU
    d_ff_expert: int = 2048          # expert_ffn_hidden_size
    n_experts: int = 512             # n_routed_experts: the ones with matrices
    zero_experts: int = 256          # zero_expert_num: router outputs beside
    zero_expert_type: str = "identity"
    experts_held: int = 512          # E': what this replica holds of the 512
    first_expert: int = 0
    experts_per_token: int = 12      # moe_topk
    norm_topk_prob: bool = False
    router_scoring: str = "softmax"
    routed_scaling_factor: float = 6.0
    n_head: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        assert (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts)
        if self.zero_expert_type not in _moe.ZERO_EXPERT_TYPES:
            raise ValueError(
                f"zero_expert_type {self.zero_expert_type!r}: the expert "
                f"layer knows {_moe.ZERO_EXPERT_TYPES}")

    @property
    def router_outputs(self) -> int:
        return self.n_experts + self.zero_experts

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values a token leaves in the cache, a sublayer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def preset(cls, name: str, **overrides) -> "LongcatConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # meituan-longcat/LongCat-Flash-Chat config.json: the defaults
    "longcat-flash-chat": dict(),
    "longcat-tiny": dict(
        vocab_size=512, n_layer=2, d_model=64, d_ff=128, d_ff_expert=32,
        n_experts=8, zero_experts=4, experts_held=8, experts_per_token=3,
        n_head=4, q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): the latent and the shared
# rotary key hold a value a token, along axis 2; no recurrent state
CACHE_TOKEN_AXIS = {"latent": 2, "k_rope": 2}

# the columns of the cache's `counts` leaf: `kimi.COUNTS`, column for column
# (the per-layer readers know them by name), the pairs 12 a lane, and of
# those the pairs that chose a zero-compute expert: a pair is held
# (`expert_rows`), zero-compute (`zero_rows`) or absent (the rest of
# `expert_rows_all`)
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions",
          "expert_rows_all", "zero_rows")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02), W_o and an expert's
# second matrix among them, but: the dense FFNs' second 0.02 / sqrt(4
# n_layer), two of them a layer (a dense FFN of 12,288 lanes then adds about
# two thirds of the stream's size, the zero term a half, and a held expert's
# pair, one pair in fifty, a tenth: at the dense FFNs' spread it would add a
# fortieth, and a fault in the experts' kernel would show in no logit); and
# the two up-projections out of the latents, W_qb and W_kvb, 0.02 over their
# latent's factor (`mla.latent_scales`: 0.01 and 0.00577), so that q, the
# keys and the values have the spread an unscaled latent gives them. With
# both at 0.02 the factors multiply the seeded scores' spread by 6.9 (2.5
# where Kanana's is 0.6): a softmax that attends to a handful of positions
# turns the bf16 rounding of the queries and of the probabilities, which the
# rows' dtype states, into 1.1% of the logits' spread, as much as a bf16
# residual stream costs, and no check could tell the two apart (PERF.md,
# PR 55: 0.0179 against 0.0164). The token table
# 0.3 and the selection bias by `models/kimi.py`'s argument (with the table
# at 0.02 the stream is a fraction of what the first layers add to it and
# any rounding becomes another expert for some token; the head is untied, so
# no token's own row stands out among its logits and greedy replies do not
# repeat one token: granite's lesson on a tied table). The bias is on a
# softmax over 768, whose scores lie about 1/768: N(0, 0.0002), small
# against them and not zero, so that selection by s + b and weighting by s
# can be told apart.
EMBED_STD, ROUTER_BIAS_STD = 0.3, 0.0002


def _out_std(cfg: LongcatConfig) -> float:
    return 0.02 / math.sqrt(4 * cfg.n_layer)


def _attn_params(key, cfg: LongcatConfig) -> Params:
    ks = jax.random.split(key, 5)
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.n_head
    s_q, s_kv = (s or 1.0 for s in mla.latent_scales(cfg))
    return {"norm": lm.ones(D),
            "wqa": lm.normal(ks[0], (D, cfg.q_lora_rank), 0.02, pd),
            "q_norm": lm.ones(cfg.q_lora_rank),
            "wqb": lm.normal(ks[1], (cfg.q_lora_rank, H, cfg.qk_head_dim),
                             0.02 / s_q, pd),
            "wkva": lm.normal(ks[2], (D, cfg.cache_width), 0.02, pd),
            "kv_norm": lm.ones(cfg.kv_lora_rank),
            "wkvb": lm.normal(ks[3], (cfg.kv_lora_rank, H,
                                      cfg.qk_nope_head_dim + cfg.v_head_dim),
                              0.02 / s_kv, pd),
            "wo": lm.normal(ks[4], (H * cfg.v_head_dim, D), 0.02, pd)}


def _dense_params(key, cfg: LongcatConfig) -> Params:
    k_in, k_out = jax.random.split(key)
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff
    # gate and up side by side: one product
    return {"norm": lm.ones(D),
            "w_in": lm.normal(k_in, (D, 2 * F), 0.02, pd),
            "w_out": lm.normal(k_out, (F, D), _out_std(cfg), pd)}


def _expert_params(key, cfg: LongcatConfig) -> Params:
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


# a layer's parts and how many entries of its stack a layer is
def _entries(cfg: LongcatConfig) -> dict:
    return {"attn": 2, "dense": 2, "moe": 1, "experts": cfg.experts_held}


def _init_layer(key: jax.Array, l, cfg: LongcatConfig) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 7)
    D, R = cfg.d_model, cfg.router_outputs

    def two(make, k0, k1):
        return jax.tree.map(lambda a, b: jnp.stack([a, b]), make(k0, cfg),
                            make(k1, cfg))

    return {"attn": two(_attn_params, ks[0], ks[1]),
            "dense": two(_dense_params, ks[2], ks[3]),
            "moe": {"router": lm.normal(ks[4], (1, D, R), 0.02, jnp.float32),
                    "bias": lm.normal(ks[5], (1, R), ROUTER_BIAS_STD,
                                      jnp.float32)},
            "experts": _expert_params(ks[6], cfg)}


def init_layer(key: jax.Array, l: int, cfg: LongcatConfig) -> Params:
    """Layer l's weights from `fold_in(key, l)` and nothing else: `attn` and
    `dense` [2, ...] (the two sublayers'), `moe` [1, ...] (router and bias)
    and `experts` [E', ...] (the held experts'), each leaf's leading axis the
    entries the layer is of that part's stack, by the one compiled program
    (`lm.layer_program`): a layer made alone is, to the bit, the layer in
    `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg)(key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: LongcatConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def init_params(key: jax.Array, cfg: LongcatConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `attn` and
    `dense` [2 n_layer, ...] (sublayer i of layer l at entry 2 l + i), `moe`
    [n_layer, ...] and `experts` [n_layer x E', ...], the held experts of
    every layer end to end; allocated once, a layer written at a time
    (donated), so the most that exists beside the tree is one layer
    (`kimi.init_params`)."""
    out = dict(init_ends(key, cfg))
    for l in range(cfg.n_layer):
        layer = init_layer(key, l, cfg)
        for part, n in _entries(cfg).items():
            if part not in out:
                like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                    a.shape[1:], a.dtype), layer[part])
                out[part] = lm.empty_stack(like, n * cfg.n_layer)
            out[part] = lm.put_layer(out[part], layer[part], jnp.int32(l))
        del layer
    return out


resident_params = lm.resident_params


def resident_specs(cfg: LongcatConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the longcat family is served on one chip, which holds its share of "
        "the experts and of the vocabulary: its weights and its rows have no "
        "partition specs and the shares no exchange yet "
        "(tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: LongcatConfig) -> int:
    """What this replica holds: the held experts and the vocabulary's
    slice, not the published whole."""
    D, H, rq, r = cfg.d_model, cfg.n_head, cfg.q_lora_rank, cfg.kv_lora_rank
    attn = (D + D * rq + rq + rq * H * cfg.qk_head_dim + D * cfg.cache_width
            + r + r * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * D)
    dense = D + 3 * D * cfg.d_ff
    moe = (D * cfg.router_outputs + cfg.router_outputs
           + cfg.experts_held * 3 * D * cfg.d_ff_expert)
    return (cfg.n_layer * (2 * attn + 2 * dense + moe)
            + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: LongcatConfig, batch: int,
               max_len: Optional[int] = None):
    """{"latent" [2 n_layer, B, T, r], "k_rope" [2 n_layer, B, T, p]} in the
    compute dtype, `deepseek.init_cache`'s two leaves with an entry a
    sublayer; and `counts` uint32 [2, len(COUNTS)], the programs' own, row 0
    `decode_step`'s and row 1 `prefill_chunk`'s (they wrap: a reader takes
    differences modulo 2**32)."""
    T = max_len or cfg.max_seq_len
    L = 2 * cfg.n_layer
    return {"latent": jnp.zeros((L, batch, T, cfg.kv_lora_rank), cfg.dtype),
            "k_rope": jnp.zeros((L, batch, T, cfg.qk_rope_head_dim),
                                cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _dense_ffn(h, p, cfg: LongcatConfig):
    """SwiGLU of the normed h [N,C,D] float32."""
    with jax.named_scope("mlp_dense"):
        a, b = jnp.split(lm.dot(h, p["w_in"], cfg.dtype), 2, axis=-1)
        return lm.dot(jax.nn.silu(a) * b, p["w_out"], cfg.dtype)


def _expert_block(h, p, experts_of_all_layers, l, cfg: LongcatConfig, given,
                  ok, packed: bool = False):
    """The normed h [N,C,D] float32 -> r, the held experts' part of the
    routed sum + the zero-compute experts' term, for layer l; `given` [768]
    += the (lane, output) pairs of the lanes that are `ok`, over all the
    router's outputs.

    The router scores all 768 outputs and chooses K. A pair whose expert is
    held, e in first_expert..+E', goes to entry l E' + e - first_expert of
    the stack of every layer's held experts; a pair whose expert is routed
    and absent goes to the id past the stack's end and a pair whose expert
    is zero-compute (e >= 512) to the one after it, `moe._experts`' one
    zero-compute id: neither is given a row of any matrix, both are zeroed on
    the way out, and the zero pairs' gates times h are added there
    (`moe_zero`). `packed` (the rows are `lm.pack_lanes`'): a row that is
    not `ok` is no lane's and goes to no expert."""
    B, C, D = h.shape
    K, held = cfg.experts_per_token, cfg.experts_held
    stack = experts_of_all_layers["wg"].shape[0]
    _, _, gates, experts = _moe._route(h.reshape(B * C, D), p["router"], cfg,
                                       p["bias"])
    with jax.named_scope("moe_router"):
        given = given.at[experts.reshape(-1)].add(
            jnp.repeat(ok.reshape(-1), K).astype(jnp.int32))
        local = experts - cfg.first_expert
        entry = jnp.where(
            experts >= cfg.n_experts, stack + 1,
            jnp.where((local >= 0) & (local < held), l * held + local, stack))
        if packed:
            entry = jnp.where(ok.reshape(-1, 1), entry, stack)
    r = _moe._experts(
        h, gates.reshape(B, C, K), entry.reshape(B, C, K),
        *(experts_of_all_layers[w] for w in ("wg", "wu", "wd")),
        types.SimpleNamespace(n_experts=stack + 2, experts_per_token=K,
                              dtype=jnp.float32),
        first_expert=jnp.int32(0), zero_experts=1 if cfg.zero_experts else 0,
        zero_type=cfg.zero_expert_type)
    return r, given


def _expert_counts(given, cfg: LongcatConfig):
    """One layer's step in `COUNTS`' order but the positions: from the pairs
    `given` [768] each of the router's outputs got over all the step's valid
    lanes."""
    with jax.named_scope("moe_router"):
        held = lax.dynamic_slice_in_dim(given, cfg.first_expert,
                                        cfg.experts_held)
        zero = jnp.zeros((), jnp.int32)
        return jnp.stack([jnp.sum(held), jnp.sum(held > 0), jnp.max(held),
                          jnp.ones((), jnp.int32), zero, zero,
                          jnp.sum(given),
                          jnp.sum(given[cfg.n_experts:])]).astype(jnp.uint32)


def _layer(l, params: Params, cfg: LongcatConfig, pos0, on, further,
           prefilling, rounds, first, rest, lat, kr, counts):
    """One double layer. Its two attention sublayers mix a sequence: every
    slot's first lane all slots at once, then the further lanes of the slots
    that have any, a slot at a time (`lm.each_slot`, which has why the
    weights are sliced inside the body here). What stands behind each is
    token-wise and takes every valid lane of the step in one call
    (`lm.pack_lanes`): behind the first the router, the routed experts (r,
    asked for where h0 exists) and the first dense FFN, behind the second
    the second dense FFN, where r is added, still in the rows it was made
    in. The decode program is the first lanes' four steps alone.

    A step whose lanes are more than a call's rows goes in rounds
    (`lm.lane_rounds`), and a round is the layer from the first dense FFN
    on for its own slots, since r lives between the two calls: the first
    lanes' second sublayer belongs to round 0 and writes and counts in no
    other. At these widths a row costs MXU time whether it is a lane's or
    not (the dense FFNs' two-piece products, the pairs' rows by expert), so
    each call is cut to the quarter of the rows behind the first lanes that
    the round needs (`lm.row_buckets`): a branch a bucket, one taken."""
    scales = mla.latent_scales(cfg)
    given = jnp.zeros((cfg.router_outputs,), jnp.int32)

    def part(name, i, turn=None):
        return lm.layer_weights(params[name], i, turn=turn)

    def attend(x, lat, kr, i, pos0, pos, ok, slot=None, turn=None):
        p = part("attn", i, slot if turn is None else turn)
        return mla.attention(x, p["norm"], p, cfg, lat, kr, i, pos0, pos, ok,
                             slot, scales=scales, whole=True)

    def after_first(a, ok, given, turn=None, packed=False):
        """a -> (b = a + the first dense FFN, r, given)."""
        dense0 = part("dense", 2 * l, turn)
        with jax.named_scope("mlp"):
            h0 = rms_norm(a, dense0["norm"], cfg.norm_eps)
            r, given = _expert_block(h0, part("moe", l, turn),
                                     params["experts"], l, cfg, given, ok,
                                     packed)
            return a + _dense_ffn(h0, dense0, cfg), r, given

    def after_second(c, r, turn=None):
        dense1 = part("dense", 2 * l + 1, turn)
        with jax.named_scope("mlp"):
            h1 = rms_norm(c, dense1["norm"], cfg.norm_eps)
            return c + _dense_ffn(h1, dense1, cfg) + r

    first, lat, kr = attend(first, lat, kr, 2 * l, pos0, pos0[:, None],
                            on[:, None])
    if rest is None:
        first, r, given = after_first(first, on[:, None], given)
        first, lat, kr = attend(first, lat, kr, 2 * l + 1, pos0,
                                pos0[:, None], on[:, None])
        first = after_second(first, r)
        return first, rest, lat, kr, counts + _expert_counts(given, cfg)

    M = rest.shape[1]

    def by_slot(i, g, rest, lat, kr):
        def slot(b, carry):
            rest, lat, kr = carry
            xb, okb, at = lm.slot_lanes(b, rest, further, pos0 + 1)
            xb, lat, kr = attend(xb, lat, kr, i, at,
                                 at[:, None] + jnp.arange(M), okb, slot=b)
            return lm.put_lanes(rest, xb, b), lat, kr

        return lm.each_slot(lm.round_slots(rounds, g), slot, (rest, lat, kr))

    buckets = lm.row_buckets(*rest.shape[:2])

    def cut(stage, g):
        """`stage` on the leading rows of its operands, a branch a bucket,
        what it returns of rows padded back to the call's N."""
        def to(rows):
            def branch(*operands):
                out = stage(*(x[:, :rows] if x.ndim > 1 else x
                              for x in operands))
                return tuple(jnp.pad(y, ((0, 0), (0, buckets[-1] - rows))
                                     + ((0, 0),) * (y.ndim - 2))
                             if y.ndim > 1 else y for y in out)
            return branch

        return lambda *operands: lax.switch(
            lm.round_bucket(rounds, g, buckets),
            [to(rows) for rows in buckets], *operands)

    def one(g, carry):
        first, rest, lat, kr, given = carry
        rest, lat, kr = by_slot(2 * l, g, rest, lat, kr)
        a, ok = lm.pack_lanes(first, on, rest, rounds, g)
        b, r, given = cut(
            lambda a, ok, given: after_first(a, ok, given, turn=g,
                                             packed=True), g)(a, ok, given)
        first, rest = lm.unpack_lanes(first, rest, further, b, rounds, g)
        c, lat, kr = attend(first, lat, kr, 2 * l + 1, pos0, pos0[:, None],
                            (on & (g == 0))[:, None], turn=g)
        # the loop writes the leaves where the first lanes read them: its
        # lanes wait for theirs (`lm.each_slot`: nothing else ties the two)
        first, rest = lax.optimization_barrier(
            (jnp.where(g == 0, c, first), rest))
        rest, lat, kr = by_slot(2 * l + 1, g, rest, lat, kr)
        c, _ = lm.pack_lanes(first, on, rest, rounds, g)
        x, = cut(lambda c, r: (after_second(c, r, turn=g),), g)(c, r)
        first, rest = lm.unpack_lanes(first, rest, further, x, rounds, g)
        return first, rest, lat, kr, given

    first, rest, lat, kr, given = lax.fori_loop(
        0, rounds["count"], one, (first, rest, lat, kr, given))
    return first, rest, lat, kr, counts + _expert_counts(given, cfg)


def _logits(params: Params, x, cfg: LongcatConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: LongcatConfig, program: int):
    """Both step programs (`kimi._forward`'s shape): a layer computes a lane
    only where the plan put a token, every slot's first lane all slots at
    once and the lanes after it a slot at a time, C of them a slot with the
    last one padding for the grouped matmul's tiles. One loop over the
    layers carries the two leaves, one buffer each from layer to layer,
    written in place where the caller donates them, and closes over the
    experts' stack, which it never slices; nothing of a layer stands outside
    it."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    counts = jnp.zeros((len(COUNTS),), jnp.uint32)

    def layer(l, carry):
        return _layer(l, params, cfg, pos0, on, further, prefilling, rounds,
                      *carry)

    with jax.named_scope("layers"):
        first, rest, lat, kr, counts = lax.fori_loop(
            0, cfg.n_layer, layer,
            (first, rest, cache["latent"], cache["k_rope"], counts))
    x = lm.join_lanes(first, rest, C)
    with jax.named_scope("moe_router"):
        attended = jnp.sum(jnp.where(ok, pos0[:, None] + lane + 1, 0))
        T = lat.shape[2]
        read = read_positions(pos0, on, T)
        if prefilling is not None:
            read = read + (prefilling[1] * T).astype(jnp.uint32)
        counts = counts.at[COUNTS.index("attended_positions")].set(
            attended.astype(jnp.uint32))
        counts = counts.at[COUNTS.index("read_positions")].set(read)
        counts = cache["counts"].at[program].add(counts)
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {"latent": lat, "k_rope": kr, "counts": counts})


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: LongcatConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: LongcatConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): the chunk program's first lane,
    and nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, 0)
