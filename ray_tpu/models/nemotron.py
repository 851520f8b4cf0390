"""Nemotron-H's layers for serving: a layer is one sublayer, a Mamba-2
mixer, an attention mixer or an expert block alone, and the expert block is
a LatentMoE, routed experts of two matrices inside a narrow latent.

What is served is `nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`
(`model_type: nemotron_h`; preset `nemotron-3-super-120b-a12b`): 88 layers by
`hybrid_override_pattern`, 40 `M` (Mamba-2), 40 `E` (expert block) and 8 `*`
(attention), none of them a mixer and an MLP. With d the hidden size 4,096,
eps 1e-5, no bias but the convolution's:

    x += mixer_l(RMSNorm_l(x)),  mixer_l by the pattern's letter
    final RMSNorm, untied head, logits float32

    M (`models/mamba2.py`: 128 heads of 64 lanes, 8 groups of B and C of 16
      heads each, N = 128; the gated norm by group, 1,024 lanes at a time)

    * (32 query and 2 key-value heads of 128, no positions, no q/k norm, no
      gate):  q = u W_q;  k, v = u W_k, u W_v, cached by the 2 heads;
      o_h = softmax_{t<=pos}(q_h . k_{h//16,t} / sqrt(128)) v_{h//16};  W_o

    E (E = 512 experts, K = 22 a token, one shared expert; relu2(a) =
      relu(a)^2, no gate matrix):
      s = sigmoid(u W_r) over all 512 (float32); the 22 largest of s + bias
        chosen; gates = s_chosen / (sum + 1e-20) * 5.0    (`moe._route`)
      c = u W_down                                        [4096 -> 1024]
      r = sum_k gate_k relu2(c W_up^(e_k)) W_dn^(e_k)     [1024, 2688] twice
      out = r W_back + relu2(u W_su) W_sd      [1024 -> 4096]; [4096, 5376]

The router and the shared expert read the 4,096-wide normed input; only the
routed experts live in the latent: the rows that are sorted, gathered and
multiplied are 1,024 wide (`moe._experts` with no gate matrix; on the chip
`ops/expert_mlp.py`'s kernel in that form), and the two projections stand
round the dispatch under the scope `moe_latent`.

**The chip's share** is `models/kimi.py`'s, word for word: `experts_held`
E' and `first_expert` say which of the E experts of every expert layer this
replica holds; the router keeps its E outputs and its K a token; a pair
whose expert is absent adds nothing (W_back is linear and has no bias, so
the shares of a layer add up after it); `vocab_size` rows of the table and
of the head are this chip's slice; the held experts of all expert layers
are one stack `[expert layers x E', ...]` that no loop slices.

The cache holds both kinds of leaf (`models/__init__.py`): `ssm` [Mamba
layers, slots, N, 8192] and `conv` [Mamba layers, slots, 3 x 10240] a slot's
state, float32 (`CACHE_STATE`), `k` and `v` [attention layers, slots, 2, T,
128] a value a token (`CACHE_TOKEN_AXIS`; `lm`'s grouped-head arithmetic,
a decode step's lane through `ops/gqa_attend.py` and `ops/rows_write.py`),
and `counts`, the programs' own (`COUNTS`, Kimi's seven).

Every mixer exists in two forms and no third (`models/granite.py`): every
slot's first lane all slots at once, which is `decode_step` whole (the
recurrence through `ops/ssm_update.py`, attention through the kernel to each
slot's position), and a chunk's further lanes a slot at a time and only for
the slots that prefill (`lm.each_slot`: the SSD form, attention a block of
positions at a time). An expert layer mixes no sequence: a chunk step's
valid lanes, first and further, are the rows of one call (`lm.all_lanes`).

The weights exist only in the dtype the replica holds them; float32 are the
norms' scales, the convolution, `dt_bias`, `A_log`, `D`, W_in's dt columns,
the router and its bias, and so are the residual stream, everything
projected, dt, the decay, the state, the router, the latent rows and the
logits. A product's operands are bf16, the weight as it is held and the
activation as the two bf16 pieces that add up to it (`lm.dot`, `moe._experts`
for float32 rows, `lm.gqa_attend` for a float32 q); the rows of k and v are
bf16.
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

from ray_tpu.models import lm, mamba2, moe as _moe
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.gqa_attend import gqa_attend, read_positions
from ray_tpu.ops.rows_write import rows_write

Params = Any

_PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                      "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# a letter of the pattern -> the stack its layer's weights lie in
KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronConfig:
    vocab_size: int = 131072
    pattern: str = _PUBLISHED_PATTERN    # hybrid_override_pattern
    d_model: int = 4096
    ssm_heads: int = 128             # mamba_num_heads
    ssm_head_dim: int = 64           # mamba_head_dim
    ssm_state: int = 128             # ssm_state_size
    ssm_groups: int = 8              # n_groups
    ssm_conv: int = 4                # conv_kernel
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    n_experts: int = 512             # what the router scores
    experts_held: int = 512          # E': what this replica holds of them
    first_expert: int = 0
    experts_per_token: int = 22
    d_ff_expert: int = 2688          # moe_intermediate_size
    d_latent: int = 1024             # moe_latent_size
    d_ff_shared: int = 5376          # moe_shared_expert_intermediate_size
    norm_topk_prob: bool = True
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        assert self.pattern and set(self.pattern) <= set(KINDS), self.pattern
        assert self.ssm_heads % self.ssm_groups == 0
        assert self.n_head % self.n_kv_head == 0
        assert (0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.n_experts)

    @property
    def n_layer(self) -> int:
        return len(self.pattern)

    @property
    def layer_types(self) -> tuple:
        return tuple(KINDS[letter] for letter in self.pattern)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "NemotronConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json: the defaults
    # (intermediate_size 2,688 is read by no layer: the pattern has no `-`)
    "nemotron-3-super-120b-a12b": dict(),
    "nemotron-tiny": dict(
        vocab_size=512, pattern="MEM*EME", d_model=64, ssm_heads=4,
        ssm_head_dim=32, ssm_state=16, ssm_groups=2, n_head=4, n_kv_head=2,
        head_dim=16, n_experts=16, experts_held=16, experts_per_token=3,
        d_ff_expert=40, d_latent=32, d_ff_shared=48, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): keys and values by head hold
# a value a token, along axis 3; the SSM state and the convolution's window
# hold a slot's state, with no token axis
CACHE_TOKEN_AXIS = {"k": 3, "v": 3}
CACHE_STATE = ("ssm", "conv")

# the columns of the cache's `counts` leaf: `kimi.COUNTS`, column for column
# (the per-layer readers know them by name), the pairs 22 a lane
COUNTS = ("expert_rows", "experts_touched", "busiest_expert_rows",
          "expert_layer_steps", "attended_positions", "read_positions",
          "expert_rows_all")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02) and every projection
# back into the stream (W_out, W_o, W_back, the shared expert's second)
# 0.02 / sqrt(n_layer): the published `rescale_prenorm_residual`, a residual
# add a layer. The three matrices inside the latent, W_down and an expert's
# two, N(0, 1 / sqrt(rows)): 0.016, 0.031 and 0.019 at the published widths,
# which is 0.02 there, and at any width a latent row and an expert's hidden
# lanes of size 1, so that relu^2, which squares whatever scale it is given,
# leaves the routed sum a part of the layer that a fault in it shows (at
# 0.02 the tiny preset's routed experts added a millionth of the stream).
# The Mamba-2 layer's own are `mamba2.init`'s. The token table 0.3 and the
# selection bias 0.02 by `models/kimi.py`'s argument (with the table at 0.02
# the stream is a fraction of what the first layers add to it and any
# rounding becomes another expert for some token; the head is untied, so no
# token's own row stands out among its logits and greedy replies do not
# repeat one token: granite's lesson on a tied table).
EMBED_STD, ROUTER_BIAS_STD = 0.3, 0.02


def _out_std(cfg: NemotronConfig) -> float:
    return 0.02 / math.sqrt(cfg.n_layer)


def _attention_params(key, cfg: NemotronConfig) -> Params:
    ks = jax.random.split(key, 4)
    pd, D = cfg.param_dtype, cfg.d_model
    H, G, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    return {"wq": lm.normal(ks[0], (D, H * d), 0.02, pd),
            "wk": lm.normal(ks[1], (D, G * d), 0.02, pd),
            "wv": lm.normal(ks[2], (D, G * d), 0.02, pd),
            "wo": lm.normal(ks[3], (H * d, D), _out_std(cfg), pd)}


def _expert_params(key, cfg: NemotronConfig) -> Params:
    """The held experts' two matrices: expert e's from `fold_in(key, e)` and
    nothing else, so that every share of a layer holds the same expert e."""
    pd, C, F = cfg.param_dtype, cfg.d_latent, cfg.d_ff_expert

    def one(e):
        k_up, k_down = jax.random.split(jax.random.fold_in(key, e))
        return {"wu": lm.normal(k_up, (C, F), C ** -0.5, pd),
                "wd": lm.normal(k_down, (F, C), F ** -0.5, pd)}

    # a loop, not `vmap`: one expert's matrices are the program (`kimi`)
    return lax.map(one, cfg.first_expert + jnp.arange(cfg.experts_held))


def _init_layer(key: jax.Array, l, cfg: NemotronConfig, kind: str) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 8)
    pd, D, E = cfg.param_dtype, cfg.d_model, cfg.n_experts
    C, S = cfg.d_latent, cfg.d_ff_shared
    norm = {"norm": lm.ones(D)}
    if kind == "mamba":
        return {kind: {**norm,
                       "ssm": mamba2.init(ks[:7], cfg, _out_std(cfg))}}
    if kind == "attention":
        return {kind: {**norm, **_attention_params(ks[0], cfg)}}
    return {
        "moe": {**norm,
                "router": lm.normal(ks[0], (D, E), 0.02, jnp.float32),
                "bias": lm.normal(ks[1], (E,), ROUTER_BIAS_STD, jnp.float32),
                # fc1_latent_proj and fc2_latent_proj: into the latent the
                # routed experts live in, and back
                "w_down": lm.normal(ks[2], (D, C), D ** -0.5, pd),
                "w_back": lm.normal(ks[3], (C, D), _out_std(cfg), pd),
                "shared": {"w_in": lm.normal(ks[4], (D, S), 0.02, pd),
                           "w_out": lm.normal(ks[5], (S, D), _out_std(cfg),
                                              pd)}},
        "experts": _expert_params(ks[6], cfg)}


def init_layer(key: jax.Array, l: int, cfg: NemotronConfig) -> Params:
    """Layer l's weights (l from 0) from `fold_in(key, l)` and nothing else,
    under its kind's name (`mamba`, `attention`, or `moe` with `experts`,
    the held experts' [E', ...]), by the one compiled program a kind
    (`lm.layer_program`): a layer made alone is, to the bit, the layer in
    `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg, cfg.layer_types[l])(
        key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: NemotronConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def _stack_index(cfg: NemotronConfig) -> list:
    """For each layer, which entry of its kind's stack it is."""
    seen: dict = {}
    out = []
    for kind in cfg.layer_types:
        out.append(seen.get(kind, 0))
        seen[kind] = out[-1] + 1
    return out


def init_params(key: jax.Array, cfg: NemotronConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `mamba`,
    `attention` and `moe`, one stack a kind on a leading axis in the order
    the layers have, and `experts` [expert layers x E', ...], the held
    experts of every expert layer end to end; allocated once, a layer
    written at a time (donated), so the most that exists beside the tree is
    one layer (`kimi.init_params`)."""
    out = dict(init_ends(key, cfg))
    for l, (kind, i) in enumerate(zip(cfg.layer_types, _stack_index(cfg))):
        layer = init_layer(key, l, cfg)
        for part in layer:
            if part not in out:
                like, n = layer[part], cfg.layers_of(kind)
                if part == "experts":       # [E', ...] a layer, end to end
                    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                        a.shape[1:], a.dtype), like)
                    n *= cfg.experts_held
                out[part] = lm.empty_stack(like, n)
            out[part] = lm.put_layer(out[part], layer[part], jnp.int32(i))
        del layer
    return out


resident_params = lm.resident_params


def resident_specs(cfg: NemotronConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the nemotron family is served on one chip, which holds its share "
        "of the experts and of the vocabulary: its weights, its rows and "
        "its state have no partition specs and the shares no exchange yet "
        "(tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: NemotronConfig) -> int:
    """What this replica holds: the held experts and the vocabulary's
    slice, not the published whole."""
    D, C, F = cfg.d_model, cfg.d_latent, cfg.d_ff_expert
    attention = (2 * D * cfg.n_head * cfg.head_dim
                 + 2 * D * cfg.n_kv_head * cfg.head_dim + D)
    moe = (D + D * cfg.n_experts + cfg.n_experts + 2 * D * C
           + 2 * D * cfg.d_ff_shared + cfg.experts_held * 2 * C * F)
    return (cfg.layers_of("mamba") * (mamba2.num_params(cfg) + D)
            + cfg.layers_of("attention") * attention
            + cfg.layers_of("moe") * moe + 2 * cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: NemotronConfig, batch: int,
               max_len: Optional[int] = None):
    """`mamba2.init_cache`'s two leaves, float32, zero; {"k", "v"
    [attention layers, B, 2, T, 128]} in the compute dtype, by the
    key-value heads, a position a row of the head's lanes; and `counts`
    uint32 [2, len(COUNTS)], the programs' own, row 0 `decode_step`'s and
    row 1 `prefill_chunk`'s (they wrap: a reader takes differences modulo
    2**32). `max_len` sizes the rows alone."""
    T = max_len or cfg.max_seq_len
    by_head = (cfg.layers_of("attention"), batch, cfg.n_kv_head, T,
               cfg.head_dim)
    return {**mamba2.init_cache(cfg, cfg.layers_of("mamba"), batch),
            "k": jnp.zeros(by_head, cfg.dtype),
            "v": jnp.zeros(by_head, cfg.dtype),
            "counts": jnp.zeros((2, len(COUNTS)), jnp.uint32)}


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------

def _mamba(x, p, cfg: NemotronConfig, cache, i, on, slot=None):
    """Mamba-2 layer `i` of its stack: x += the mixer of its normed lanes,
    every slot's first lane by the recurrence (x [B,1,D], `on` [B]) or
    `slot`'s further lanes by the SSD form (x [1,M,D], `on` [1,M])."""
    with jax.named_scope("attn"):
        u = rms_norm(x, p["norm"], cfg.norm_eps)
        if slot is None:
            o, cache = mamba2.first(u, p["ssm"], cfg, cache, i, on)
        else:
            o, cache = mamba2.further(u, p["ssm"], cfg, cache, i, slot, on)
    return x + o, cache


def _attention(x, p, cfg: NemotronConfig, cache, i, pos0, ok, slot=None):
    """Attention layer `i` of its stack: x [N,C,D] float32 += grouped-head
    attention of its lanes against the carried rows of `k` and `v`. Row n is
    slot n at one lane (N = B, C = 1: `ops/gqa_attend.py`, to each slot's
    position), or the one row is `slot`'s own further lanes, the first at
    position pos0 [1], against that slot's rows a block at a time."""
    B, C, _ = x.shape
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    scale = 1.0 / math.sqrt(d)
    with jax.named_scope("attn"):
        with jax.named_scope("gqa_project"):
            u = rms_norm(x, p["norm"], cfg.norm_eps)
            # q stays float32: its two pieces meet the cached rows
            q, k, v = lm.gqa_qkv(u, p, G, R, d, cfg.dtype, jnp.float32)
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
            x = x + lm.dot(y.reshape(B, C, -1), p["wo"], cfg.dtype)
    return x, {**cache, "k": ck, "v": cv}


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def _expert_block(x, p, experts_of_all_layers, j, cfg: NemotronConfig, given,
                  ok, packed: bool = False):
    """x [N,C,D] += the held experts' part of the routed sum, through the
    latent, + the shared expert, for expert layer j; `given` [E] += the
    (lane, expert) pairs of the lanes that are `ok`, over all E. `packed`
    (the rows are `lm.pack_lanes`'): a row that is not `ok` is no lane's
    and goes to no expert.

    The router scores all E experts over the normed input and chooses K. A
    pair whose expert is held goes to entry j E' + e - first_expert of the
    stack of every layer's held experts, a pair whose expert is not past the
    stack's end, where `moe._experts` gives it no row of any matrix and
    zeroes it (`kimi._expert_mlp`). What is dispatched is the latent row
    c = u W_down, 1,024 wide, and what comes back goes through W_back."""
    B, C, D = x.shape
    K, held = cfg.experts_per_token, cfg.experts_held
    stack = experts_of_all_layers["wu"].shape[0]
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
        with jax.named_scope("moe_latent"):
            c = lm.dot(h, p["w_down"], cfg.dtype)
        routed = _moe._experts(
            c, gates.reshape(B, C, K), entry.reshape(B, C, K), None,
            experts_of_all_layers["wu"], experts_of_all_layers["wd"],
            types.SimpleNamespace(n_experts=stack + 1, experts_per_token=K,
                                  dtype=jnp.float32),
            first_expert=jnp.int32(0))
        with jax.named_scope("moe_latent"):
            routed = lm.dot(routed, p["w_back"], cfg.dtype)
        with jax.named_scope("moe_shared"):
            shared = lm.dot(_relu2(lm.dot(h, p["shared"]["w_in"], cfg.dtype)),
                            p["shared"]["w_out"], cfg.dtype)
        return x + routed + shared, given


def _expert_counts(given, cfg: NemotronConfig):
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


def _layer(kind: str, i, params: Params, cfg: NemotronConfig, pos0, on,
           further, prefilling, rounds, first, rest, cache, counts):
    """One layer of `kind`, entry i of its stack. A layer that mixes a
    sequence: every slot's first lane all slots at once, then the further
    lanes of the slots that have any, a slot at a time (`lm.each_slot`,
    which has why the weights are sliced inside the body here). An expert
    layer mixes none: every valid lane of the step is a row of one call
    (`lm.all_lanes`), the held experts read once."""
    stack = params[kind]
    if kind == "moe":
        given = jnp.zeros((cfg.n_experts,), jnp.int32)
        if rest is None:
            first, given = _expert_block(
                first, lm.layer_weights(stack, i), params["experts"], i, cfg,
                given, on[:, None])
        else:
            def block(x, ok, g, given):
                return _expert_block(
                    x, lm.layer_weights(stack, i, turn=g), params["experts"],
                    i, cfg, given, ok, packed=True)

            first, rest, given = lm.all_lanes(block, first, on, rest,
                                              further, rounds, given)
        return first, rest, cache, counts + _expert_counts(given, cfg)
    p = lm.layer_weights(stack, i)
    if kind == "mamba":
        first, cache = _mamba(first, p, cfg, cache, i, on)
    else:
        first, cache = _attention(first, p, cfg, cache, i, pos0, on[:, None])
    if rest is not None:
        # the loop writes the leaves where the first lanes read them
        # (`lm.each_slot`: nothing else ties the two here)
        first, cache = lax.optimization_barrier((first, cache))

        def slot(b, carry):
            rest, cache = carry
            p = lm.layer_weights(stack, i, turn=b)
            xb, okb, at = lm.slot_lanes(b, rest, further, pos0 + 1)
            if kind == "mamba":
                xb, cache = _mamba(xb, p, cfg, cache, i, okb, slot=b)
            else:
                xb, cache = _attention(xb, p, cfg, cache, i, at, okb, slot=b)
            return lm.put_lanes(rest, xb, b), cache

        rest, cache = lm.each_slot(prefilling, slot, (rest, cache))
    return first, rest, cache, counts


def _read_positions(cache, pos0, length, on, further):
    """The positions whose rows one attention layer read for a step's valid
    lanes (`kimi._read_positions`' grouped-head case): every slot's first
    lane to its block through `gqa_attend` (all T in the plain form), a
    prefilling slot's further lanes the blocks to the slot's last lane."""
    T = cache["k"].shape[3]
    read = read_positions(pos0, on, T)
    if further is not None:
        turns, block = lm.gqa_blocks(pos0 + jnp.maximum(length, 1) - 1, T)
        read = read + jnp.sum(jnp.where(further.any(axis=1),
                                        turns * block, 0))
    return read.astype(jnp.uint32)


def _logits(params: Params, x, cfg: NemotronConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["lm_head"], cfg.dtype)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: NemotronConfig, program: int):
    """Both step programs (`kimi._forward`'s shape): a layer computes a lane
    only where the plan put a token, every slot's first lane all slots at
    once and the lanes after it a slot at a time where the layer mixes a
    sequence, C of them a slot with the last one padding; an expert layer
    takes every valid lane of the step in one call.

    The pattern is walked as runs of one kind: one loop over the runs, whose
    body holds one loop a kind, and a kind's loop turns as many times as the
    run is long if the run is of that kind and not at all if it is not
    (the published pattern's runs are all one layer long). No branch takes a
    layer's kind (a leaf that passes through a conditional untouched is
    copied on its way), the program holds three layer bodies whatever the
    depth, and nothing of a layer stands outside the runs' loop. The loops
    carry the cache, one buffer a leaf from layer to layer, written in place
    where the caller donates it, and close over the experts' stack, which
    they never slice."""
    B, C = tokens.shape
    lane = jnp.arange(C)
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=True)
    rounds = lm.lane_rounds(further, prefilling)
    counts = jnp.zeros((len(COUNTS),), jnp.uint32)
    leaves = {k: v for k, v in cache.items() if k != "counts"}
    kinds = cfg.layer_types
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
    entry = jnp.asarray(_stack_index(cfg))

    def layer(kind, l, carry):
        return _layer(kind, entry[l], params, cfg, pos0, on, further,
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
        attended = jnp.sum(jnp.where(ok, pos0[:, None] + lane + 1, 0))
        counts = counts.at[COUNTS.index("attended_positions")].set(
            attended.astype(jnp.uint32))
        if "attention" in bodies:
            counts = counts.at[COUNTS.index("read_positions")].set(
                _read_positions(cache, pos0, length, on, further))
        counts = cache["counts"].at[program].add(counts)
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {**leaves, "counts": counts})


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: NemotronConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0; the state does not read it. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, 1)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: NemotronConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): the recurrence through the
    state-update kernel, attention over the cached rows and the held
    experts' kernel, one token a slot; the chunk program's first lane, and
    nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, 0)
