"""The DeepSeek-V3 layer for serving: latent attention (MLA) over a latent
cache, one leading dense layer, then expert layers with shared experts.

What is served is `kakaocorp/kanana-2-30b-a3b-instruct-2601`
(`model_type: deepseek_v3`; preset `kanana-2-30b-a3b`). As published, with
d the hidden size, H heads, `qk_nope_head_dim` n, `qk_rope_head_dim` p,
`v_head_dim` v, `kv_lora_rank` r, `q_lora_rank` null, no biases:

    h = RMSNorm(x)
    q = h W_q -> [H, n + p], split q_nope [H, n], q_rope [H, p]
    [c, k_r] = h W_kva -> r + p;  c = RMSNorm_kv(c)
    RoPE(position) on q_rope (each head) and on k_r (one key for all heads)

  plain form (the definition; `benchmarks/chip/families/kanana.py`):
    [k_nope, val] = c W_kvb -> [H, n + v];  k = [k_nope ; k_r]
    scores q . k / sqrt(n + p), causal softmax in float32
    o = softmax . val -> [H, v];  x += concat(o) W_o

  absorbed form (what runs here, decode and chunk alike), with W_kvb split
  by head into W_uk [r, H, n] and W_uv [r, H, v]:
    q' = q_nope W_uk^T -> [H, r]
    scores (q' . c_t + q_rope . k_r,t) / sqrt(n + p) against the cache
    o = (softmax . c) W_uv
  The cache holds c after its norm and k_r after RoPE: r + p values a token
  a layer and nothing by head. Both forms are one function
  (tests/test_deepseek_serving.py holds them to each other). The absorbed
  layer is `models/mla.py`'s `attention`, which LongCat-Flash shares: it
  takes the input norm, the layer's weights (`wq`, or `wqa`, `q_norm`, `wqb`
  for a query latent), two optional factors on the normed latents and the
  precision a family states; this family gives it `wq` alone, no factor, and
  its rounded form (the norm's output in the compute dtype, one piece).

    layer 0 .. first_k_dense_replace - 1:  x += SwiGLU_dense(RMSNorm(x))
    the others, with h = RMSNorm(x):
      s = sigmoid(h W_g) in float32 over the E experts; the K largest of
      s + b are chosen (`e_score_correction_bias`), their gates are s
      without b, divided by (their sum + 1e-20) (`norm_topk_prob`) and
      times `routed_scaling_factor`
      x += sum_k g_k SwiGLU^(e_k)(h) + SwiGLU_shared(h)
    no capacity, nothing dropped; the `n_shared_experts` shared experts are
    one MLP of n_shared x the experts' width. `n_group: 1, topk_group: 1`
    make the published group limit a no-op, which is not built.
    Final RMSNorm, untied head, logits float32.

Departures and choices (the configuration file lists them under `assumed`):
RoPE pairs lane i with lane i + p/2 (`llama.apply_rope`; `rope_interleave`
is a convention of the checkpoint's layout, and a score is the same under
any pairing that q and k share); `b` is drawn from the seed.

The weights exist only in the dtype the replica holds them
(`cfg.param_dtype`), a layer at a time: `init_layer(key, l, cfg)` makes
layer l from `fold_in(key, l)` and nothing else, so the engine, a
reference and a test make the same layer alone. The router, its bias and
the norms' scales are float32 (they are used in float32), and so is the
residual stream inside the step programs (`_layers`). Router and experts
are `models/moe.py`'s `_route` and `_experts`, the one sorted grouped-matmul
path, over every expert layer's experts as one stack (`_expert_stack`).

The two step programs are one function (`_chunk`): `decode_step` is every
slot's first lane through the layers, all slots at once, and
`prefill_chunk` is that plus the lanes after the first of the slots whose
chunk has any, a slot at a time (`models/lm.py`, "The lanes of a chunk",
has the loop and the contract; `_further_lanes` is a slot's layer): a lane
is computed only where the plan put a token, so a chunk step costs a decode
step and a term a slot that prefills, not B x C lanes whoever prefills
(PERF.md, PR 39; `benchmarks/kanana_chunk_lanes.py` has the table).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm, mla, moe as _moe
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.mla_attend import read_positions

Params = Any


@dataclasses.dataclass(frozen=True)
class DeepseekConfig:
    vocab_size: int = 128256
    n_layer: int = 48                # dense and expert layers together
    n_dense_layer: int = 1           # first_k_dense_replace
    n_head: int = 32
    d_model: int = 2048
    d_ff: int = 6144                 # the dense layers' SwiGLU
    d_ff_expert: int = 768           # one routed expert's
    n_experts: int = 128
    experts_per_token: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 2.448
    kv_lora_rank: int = 512          # q_lora_rank null: `mla.attention`'s `wq`
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Values a token leaves in the cache, a layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def preset(cls, name: str, **overrides) -> "DeepseekConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json: the defaults
    "kanana-2-30b-a3b": dict(),
    "deepseek-tiny": dict(
        vocab_size=512, n_layer=3, n_dense_layer=1, n_head=4, d_model=64,
        d_ff=128, d_ff_expert=32, n_experts=8, experts_per_token=3,
        n_shared_experts=2, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, max_seq_len=128),
}

# the cache's leaves that hold a value a token, and the axis that counts
# the tokens: what a prefix pool keeps a block of (`serve/kv_cache.py`)
CACHE_TOKEN_AXIS = {"latent": 2, "k_rope": 2}


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads, N(0, std). Every matrix 0.02 and every down
# projection 0.02 / sqrt(2 n_layer), as a fresh Hugging Face model, but: the
# token table 0.3 and W_o 0.02, so that a layer's attention and its experts
# each add about a third of the residual stream's size (with the table at
# 0.02 the stream is a fifth of what the first layers add to it, any
# rounding becomes another expert for some token within three layers, and a
# bf16 program and a float8 one land equally far from a float32 reference:
# PERF.md, PR 29); and the selection bias b 0.02, small against the scores
# it corrects and not zero, so that selection by s + b and weighting by s
# can be told apart.
EMBED_STD, ATTN_OUT_STD, ROUTER_BIAS_STD = 0.3, 0.02, 0.02


def _attn_params(key, cfg: DeepseekConfig) -> Params:
    ks = jax.random.split(key, 4)
    pd, D, H = cfg.param_dtype, cfg.d_model, cfg.n_head
    return {
        "wq": lm.normal(ks[0], (D, H, cfg.qk_head_dim), 0.02, pd),
        "wkva": lm.normal(ks[1], (D, cfg.cache_width), 0.02, pd),
        "kv_norm": lm.ones(cfg.kv_lora_rank),
        "wkvb": lm.normal(ks[2], (cfg.kv_lora_rank, H,
                                cfg.qk_nope_head_dim + cfg.v_head_dim),
                        0.02, pd),
        "wo": lm.normal(ks[3], (H * cfg.v_head_dim, D), ATTN_OUT_STD, pd),
    }


def _swiglu_params(key, cfg: DeepseekConfig, width: int,
                   resid_std: float) -> Params:
    ks = jax.random.split(key, 3)
    pd, D = cfg.param_dtype, cfg.d_model
    return {"wg": lm.normal(ks[0], (D, width), 0.02, pd),
            "wu": lm.normal(ks[1], (D, width), 0.02, pd),
            "wd": lm.normal(ks[2], (width, D), resid_std, pd)}


def _init_layer(key: jax.Array, l, cfg: DeepseekConfig,
                dense: bool) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 6)
    pd, D, E, F = cfg.param_dtype, cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    layer = {"attn_norm": lm.ones(D), "attn": _attn_params(ks[0], cfg),
             "mlp_norm": lm.ones(D)}
    if dense:
        layer["mlp"] = _swiglu_params(ks[1], cfg, cfg.d_ff, resid_std)
        return layer
    layer["moe"] = {
        "router": lm.normal(ks[1], (D, E), 0.02, jnp.float32),
        "bias": lm.normal(ks[2], (E,), ROUTER_BIAS_STD, jnp.float32),
        "wg": lm.normal(ks[3], (E, D, F), 0.02, pd),
        "wu": lm.normal(ks[4], (E, D, F), 0.02, pd),
        "wd": lm.normal(jax.random.fold_in(ks[4], 1), (E, F, D), resid_std,
                        pd),
    }
    layer["shared"] = _swiglu_params(
        ks[5], cfg, cfg.n_shared_experts * F, resid_std)
    return layer


def init_layer(key: jax.Array, l: int, cfg: DeepseekConfig) -> Params:
    """Layer l's weights from `fold_in(key, l)` and nothing else: a dense
    layer for l < cfg.n_dense_layer, else an expert layer, by the one
    compiled program a kind (`lm.layer_program`): a layer made alone is, to
    the bit, the layer in `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg, l < cfg.n_dense_layer)(
        key, jnp.int32(l))


def init_ends(key: jax.Array, cfg: DeepseekConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def init_params(key: jax.Array, cfg: DeepseekConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `dense`
    [n_dense_layer, ...] and `blocks` [n_layer - n_dense_layer, ...], a
    layer at a time into a stack (`lm.stack_layers`: the most that exists
    beside the tree is one layer)."""
    k = cfg.n_dense_layer
    return {**jax.jit(init_ends, static_argnums=(1,))(key, cfg),
            "dense": lm.stack_layers(
                lambda i: init_layer(key, i, cfg), k),
            "blocks": lm.stack_layers(
                lambda i: init_layer(key, k + i, cfg), cfg.n_layer - k)}


resident_params = lm.resident_params


def resident_specs(cfg: DeepseekConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the deepseek family is served on one chip: its weights have no "
        "partition specs yet (tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: DeepseekConfig) -> int:
    D, H, r = cfg.d_model, cfg.n_head, cfg.kv_lora_rank
    attn = (D * H * cfg.qk_head_dim + D * cfg.cache_width + r
            + r * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * D + 2 * D)
    F = cfg.d_ff_expert
    expert = attn + D * cfg.n_experts + cfg.n_experts \
        + (cfg.n_experts + cfg.n_shared_experts) * 3 * D * F
    dense = attn + 3 * D * cfg.d_ff
    return (cfg.n_dense_layer * dense
            + (cfg.n_layer - cfg.n_dense_layer) * expert
            + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

# the columns of the cache's `counts` leaf, each a sum over a program's
# executions: over the expert layers, the (lane, expert) rows the experts
# were given for valid lanes, the experts that got at least one, the most
# that one expert got, and 1; and once a step the positions the valid
# lanes attend to (position + 1 each) and the positions whose rows a
# layer's attention read for them (`ops/mla_attend.read_positions` for the
# first lanes, all T for each slot that has further lanes)
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions")


def init_cache(cfg: DeepseekConfig, batch: int,
               max_len: Optional[int] = None):
    """{"latent" [n_layer, B, T, r], "k_rope" [n_layer, B, T, p]} in the
    compute dtype: c after its norm and the shared rotary key after RoPE,
    two leaves because they are two operands (the scores contract both, the
    weighted sum only the latent) and a [.., T, r + p] leaf would be sliced
    inside every layer; and `counts` uint32 [2, 6], not a token's: what
    the step programs count themselves, row 0 `decode_step`'s, row 1
    `prefill_chunk`'s; `COUNTS` names the columns
    (`serve/llm.py` reads them for `stats()`; they wrap, so a reader takes
    differences modulo 2**32)."""
    T = max_len or cfg.max_seq_len
    L = cfg.n_layer
    return {"latent": jnp.zeros((L, batch, T, cfg.kv_lora_rank), cfg.dtype),
            "k_rope": jnp.zeros((L, batch, T, cfg.qk_rope_head_dim),
                                cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _swiglu(h, p, cfg: DeepseekConfig):
    g = h @ lm.weight(p["wg"], cfg.dtype)
    u = h @ lm.weight(p["wu"], cfg.dtype)
    return (jax.nn.silu(g) * u) @ lm.weight(p["wd"], cfg.dtype)


def _attention(x, bp, cfg: DeepseekConfig, lat, kr, l, pos0, pos, ok,
               slot=None, rope: bool = True):
    """x [N,C,D] float32 += the layer's absorbed attention: `mla.attention`
    in its rounded form, with no query latent (`q_lora_rank` null) and no
    factor on the latents, by the weights `bp` holds (`attn_norm`,
    `attn`)."""
    return mla.attention(x, bp["attn_norm"], bp["attn"], cfg, lat, kr, l,
                         pos0, pos, ok, slot, rope)


# an expert layer's routed experts in `moe`: what the layers' loop leaves out
ROUTED = ("wg", "wu", "wd")


def _expert_stack(blocks: Params, cfg: DeepseekConfig) -> tuple:
    """The routed experts of every expert layer as the kernels read them:
    `blocks.moe`'s wg, wu [n, E, D, F] and wd [n, E, F, D] seen as [n E, ..],
    a merge of the leading axes that moves nothing. No loop slices it
    (`models/kimi.py`'s form): a layer's [E, D, F] taken out of the stack
    is a 0.4 GB copy for the kernel, three a layer a step."""
    return tuple(lm.weight(blocks["moe"][w], cfg.dtype).reshape(
        (-1,) + blocks["moe"][w].shape[2:]) for w in ROUTED)


def _expert_mlp(x, bp, stack, i, cfg: DeepseekConfig, given, ok,
                packed: bool = False):
    """x [N,C,D] += routed experts + shared experts of expert layer i
    (layer n_dense_layer + i), whose routed experts are entries i E ..
    (i + 1) E of `stack` (`_expert_stack`): the stack goes to the kernels
    whole with the ids offset by the layer, and the other layers' groups
    are empty. `given` [E] += the (lane, expert) rows each expert was given
    for the lanes that are `ok`, by the layer's own ids. `packed` (the rows
    are `lm.pack_lanes`'): a row that is not `ok` is no lane's and goes past
    the stack's end, where `moe._experts` (`first_expert` 0 of a stack
    shorter than the ids) gives it no row of any matrix and zeroes it."""
    B, C, D = x.shape
    K, E = cfg.experts_per_token, cfg.n_experts
    with jax.named_scope("mlp"):
        h32 = rms_norm(x, bp["mlp_norm"], cfg.norm_eps)
        h = h32.astype(cfg.dtype)
        m = bp["moe"]
        # the router reads the norm's float32 output, not its rounding
        _, _, gates, experts = _moe._route(h32.reshape(B * C, D),
                                           m["router"], cfg, m["bias"])
        with jax.named_scope("moe_router"):
            given = given.at[experts.reshape(-1)].add(
                jnp.repeat(ok.reshape(-1), K).astype(jnp.int32))
        entry, held = i * E + experts, stack[0].shape[0]
        if packed:
            entry = jnp.where(ok.reshape(-1, 1), entry, held)
        routed = _moe._experts(
            h, gates.reshape(B, C, K), entry.reshape(B, C, K), *stack,
            dataclasses.replace(cfg, n_experts=held + packed),
            first_expert=jnp.int32(0) if packed else None)
        with jax.named_scope("moe_shared"):
            shared = _swiglu(h, bp["shared"], cfg)
        x = x + routed.astype(x.dtype) + shared.astype(x.dtype)
    return x, given


def _expert_counts(given):
    """The first four of `COUNTS` of one expert layer's step, from the rows
    `given` [E] each expert got over all of the step's valid lanes."""
    with jax.named_scope("moe_router"):
        return jnp.stack([jnp.sum(given), jnp.sum(given > 0), jnp.max(given),
                          jnp.ones((), jnp.int32)]).astype(jnp.uint32)


def _dense_mlp(x, bp, cfg: DeepseekConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, bp["mlp_norm"], cfg.norm_eps).astype(cfg.dtype)
        return x + _swiglu(h, bp["mlp"], cfg).astype(x.dtype)


def _mlp(x, bp, stack, i, cfg: DeepseekConfig, given, ok,
         packed: bool = False):
    """The layer's second half, by what its weights are: (x, given)."""
    if "moe" in bp:
        return _expert_mlp(x, bp, stack, i, cfg, given, ok, packed)
    return _dense_mlp(x, bp, cfg), given


def _further_lanes(rest, bp, cfg: DeepseekConfig, lat, kr, l, pos, ok,
                   prefilling):
    """One layer's attention over the lanes after the first, rest [B,M,D]
    with ok [B,M], the first of them at position pos [B], for the slots
    `prefilling` a slot at a time (`lm.each_slot`): a slot's scores
    [1,H,M,T] against its own rows. The weights are the ones the first
    lanes read: `bp` as the layers' scan holds it (`lm.each_slot` has the
    rule)."""
    M = rest.shape[1]

    def slot(b, carry):
        rest, lat, kr = carry
        xb, okb, at = lm.slot_lanes(b, rest, ok, pos)
        xb, lat, kr = _attention(xb, bp, cfg, lat, kr, l, at,
                                 at[:, None] + jnp.arange(M), okb, slot=b)
        return lm.put_lanes(rest, xb, b), lat, kr

    return lm.each_slot(prefilling, slot, (rest, lat, kr))


def _layers(x, params: Params, cache, cfg: DeepseekConfig, pos0, pos, ok,
            program: int):
    """x [B,C,D] float32 through every layer, the caches carried: the residual
    stream stays float32 from the table to the last norm (a bf16 stream
    rounds every layer's sum to 8 bits, which at these widths is most of
    what separates the program from the reference: PERF.md, PR 29), what a
    product reads of it is the norm's output in the compute dtype, and the
    router reads that output before it is rounded.

    A layer computes a lane only where the plan put a token (`models/lm.py`,
    "The lanes of a chunk"): every slot's first lane all slots at once, the
    lanes after it through attention a slot at a time (`_further_lanes`, C
    of them a slot, the last one padding: `lm.split_lanes`) and through the
    layer's second half, which knows nothing of slots, as rows of the first
    lanes' call (`lm.all_lanes`). A step costs the decode program's time
    plus a slot's attention a slot that prefills, where all B x C lanes
    through every layer cost the worst case whoever prefilled (305 ms at
    32 x 128 for one slot's question).

    The dense layers stand before the loop, the expert layers are one scan
    over their stacked weights but the routed experts' three matrices,
    which the scan would slice and the compiler then copy for the kernels,
    0.4 GB each, two thirds of a decode step (PERF.md, PR 48): the loop
    closes over those whole (`_expert_stack`). `program` is the row of the
    cache's `counts` that this program's counts go to. Returns (x,
    cache)."""
    C = x.shape[1]
    lat, kr = cache["latent"], cache["k_rope"]
    counts = jnp.zeros((4,), jnp.uint32)
    n_dense = cfg.n_dense_layer
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    blocks = params["blocks"]
    stack = _expert_stack(blocks, cfg)
    rest_of = {**blocks, "moe": {k: w for k, w in blocks["moe"].items()
                                 if k not in ROUTED}}

    def layer(l, bp, first, rest, lat, kr, counts):
        given = jnp.zeros((cfg.n_experts,), jnp.int32)
        first, lat, kr = _attention(first, bp, cfg, lat, kr, l, pos0,
                                    pos[:, :1], on[:, None])
        if rest is None:
            first, given = _mlp(first, bp, stack, l - n_dense, cfg, given,
                                on[:, None])
        else:
            # the loop writes the leaves where the first lanes read them: its
            # lanes wait for theirs (`lm.each_slot`; the experts' counts no
            # longer tie the two, and a leaf through the barrier is re-laid)
            first, rest = lax.optimization_barrier((first, rest))
            rest, lat, kr = _further_lanes(rest, bp, cfg, lat, kr, l,
                                           pos0 + 1, further, prefilling)
            first, rest, given = lm.all_lanes(
                lambda x, ok, g, given: _mlp(x, bp, stack, l - n_dense, cfg,
                                             given, ok, packed=True),
                first, on, rest, further, rounds, given)
        if "moe" in bp:
            counts = counts + _expert_counts(given)
        return first, rest, lat, kr, counts

    carry = (first, rest, lat, kr, counts)
    for l in range(n_dense):
        carry = layer(l, jax.tree.map(lambda a, l=l: a[l], params["dense"]),
                      *carry)

    # as `gpt2._cached_layers`: the caches are carries, one buffer from
    # layer to layer, written in place where the caller donates them
    with jax.named_scope("layers"):
        carry, _ = lax.scan(
            lambda carry, layer_: (layer(*layer_, *carry), None), carry,
            (jnp.arange(n_dense, cfg.n_layer), rest_of))
    first, rest, lat, kr, counts = carry
    x = lm.join_lanes(first, rest, C)
    with jax.named_scope("moe_router"):
        attended = jnp.sum(jnp.where(ok, pos + 1, 0)).astype(jnp.uint32)
        T = lat.shape[2]
        read = read_positions(pos0, on, T)
        if prefilling is not None:
            read = read + (prefilling[1] * T).astype(jnp.uint32)
        counts = cache["counts"].at[program].add(
            jnp.concatenate([counts, jnp.stack([attended, read])]))
    return x, {"latent": lat, "k_rope": kr, "counts": counts}


def _logits(params: Params, x, cfg: DeepseekConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(x, lm.weight(params["lm_head"], cfg.dtype),
                       preferred_element_type=jnp.float32)


def _chunk(params: Params, cache, tokens, pos0, length, active,
           cfg: DeepseekConfig, program: int):
    """`prefill_chunk`, its counts to row `program` of the cache's."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    pos = pos0[:, None] + lane[None, :]                               # [B, C]
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    x, cache = _layers(x, params, cache, cfg, pos0, pos, ok, program)
    return _logits(params, lm.last_valid_lane(x, length), cfg), cache


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: DeepseekConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). Donate `cache`."""
    return _chunk(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: DeepseekConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache). The chunk program at one lane
    a slot."""
    return _chunk(params, cache, tokens[:, None], pos,
                  active.astype(jnp.int32), active, cfg, 0)
