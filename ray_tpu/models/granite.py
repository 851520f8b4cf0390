"""Granite 4.0-H's layers for serving: Mamba-2 mixers over a recurrent state
beside grouped-head attention over keys and values, in one cache.

What is served is `ibm-granite/granite-4.0-h-micro` (`model_type:
granitemoehybrid`, dense: no routed experts; preset `granite-4.0-h-micro`):
40 layers by `layer_types`, attention at 5, 15, 25, 35 and Mamba-2
elsewhere. With d the hidden size and eps 1e-5, no bias but the
convolution's:

    h0 = 12 E[token]                                   (embedding_multiplier)
    h += 0.22 mixer(RMSNorm(h));  h += 0.22 mlp(RMSNorm(h))      (residual_)
    mlp(u) = W_out (silu(a) * b),  [a, b] = W_in u     (shared_intermediate)
    logits = RMSNorm(h) E^T / 8                  (tied table; logits_scaling)

    attention: q = u W_q -> [32, 64], k, v = u W_k, u W_v -> [8, 64]; no
      positions; scores q . k * 0.015625 (attention_multiplier, not 1/8);
      causal softmax; query head j reads key-value head j // 4; W_o

    Mamba-2 (64 heads of 64 lanes, one group, N = 128; `models/mamba2.py`,
    which serves any number of groups):
      [z, xBC, dt] = W_in u           (4096 + 4352 + 64; held as w_zx, w_dt)
      xBC = silu(conv1d_causal(xBC; w [4, 4352], b)), the last 4 positions
      x [64, 64], B [128], C [128] = split(xBC)
      dt = softplus(dt + dt_bias);  A = -exp(A_log), a head
      S_t = exp(dt A) S_{t-1} + dt x_t B_t^T;   y_t = S_t C_t + D x_t
      y = RMSNorm_4096(y * silu(z)) * g;  W_out

The cache holds both kinds of leaf (`models/__init__.py`): `k` and `v`
[attention layers, slots, 8, 64, T] hold a value a token (`CACHE_TOKEN_AXIS`)
and `ssm` [Mamba layers, slots, N, H P] and `conv` [Mamba layers, slots, 3 x
4352] a slot's state, float32, with no token axis (`CACHE_STATE`): the SSM
state in `ops/ssm_update.py`'s layout and the last three inputs of the
convolution. A prefix leaves its rows and the state at its end behind;
`serve/kv_cache.py` pools both under one hash.

The mixer exists in two forms and no third. The recurrence, one token a
slot through the kernel `ssm_update`, is `decode_step` whole and, in
`prefill_chunk`, every slot's first lane (a decode lane riding along is that
and nothing else). A chunk's further lanes, M of them after position s, go
through the SSD form, with a_i = sum_{s<m<=i} dt_m A:

    y_i = e^{a_i} S_s C_i + sum_{s<j<=i} e^{a_i - a_j} (C_i . B_j) dt_j x_j
    S_{s+M} = e^{a_{s+M}} S_s + sum_j e^{a_{s+M} - a_j} dt_j x_j B_j^T

a slot at a time and only for the slots that prefill (`models/lm.py`, "The
lanes of a chunk", has the loop and the contract), as attention's scores
for a whole chunk are ([8, 4 M, T] floats a slot; for every lane of every
slot they would be [slots, 32, C, T]). Attention has the same two forms: a
first lane reads its slot's rows once and to its own position through
`ops/gqa_attend.py` (a kernel on the chip: PERF.md PR 63), a chunk's
further lanes all T of their slot in plain XLA. A lane past a slot's
length has dt = 0: it decays nothing and adds nothing; a slot with no valid
lane keeps its rows, its state and its window bit for bit, in both
programs.

The weights exist only in the dtype the replica holds them, a layer at a
time, as `models/brumby.py` makes its own: one stack a kind of layer. The
convolution, `dt_bias`, `A_log`, `D`, W_in's dt columns and the norms'
scales are float32, and so are the residual stream, everything projected
(z, xBC, dt, the MLP's hidden lanes, attention's scores), the convolution
and its window, dt, the decay, the state, its update and read-out, and the
logits. A product's operands are bf16, the weight as it is held and the
activation as the two bf16 pieces that add up to it (`lm.dot`); keys,
values and attention's weights go as one piece.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm, mamba2
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.gqa_attend import gqa_attend, read_block
from ray_tpu.ops.rows_write import rows_write

Params = Any


def _published_layer_types() -> tuple:
    return tuple("attention" if l % 10 == 5 else "mamba" for l in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    n_layer: int = 40
    layer_types: tuple = _published_layer_types()
    d_model: int = 2048
    d_ff: int = 8192                 # shared_intermediate_size
    n_head: int = 32
    n_kv_head: int = 8
    ssm_heads: int = 64              # mamba_n_heads
    ssm_head_dim: int = 64           # mamba_d_head
    ssm_state: int = 128             # mamba_d_state
    ssm_groups: int = 1              # mamba_n_groups
    ssm_conv: int = 4                # mamba_d_conv
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        assert len(self.layer_types) == self.n_layer, self.layer_types
        assert set(self.layer_types) <= {"mamba", "attention"}

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @property
    def ssm_inner(self) -> int:
        return mamba2.inner(self)

    @property
    def conv_width(self) -> int:
        return mamba2.conv_width(self)

    def layers_of(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @classmethod
    def preset(cls, name: str, **overrides) -> "GraniteConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # ibm-granite/granite-4.0-h-micro config.json: the defaults
    "granite-4.0-h-micro": dict(),
    "granite-tiny": dict(
        vocab_size=512, n_layer=6,
        layer_types=("mamba", "mamba", "attention") * 2, d_model=64,
        d_ff=128, n_head=4, n_kv_head=2, ssm_heads=4, ssm_head_dim=32,
        ssm_state=16, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): keys and values hold a value a
# token, along axis 4; the SSM state and the convolution's window hold a
# slot's state, [layers of the kind, slots, ...] with no token axis
CACHE_TOKEN_AXIS = {"k": 4, "v": 4}
CACHE_STATE = ("ssm", "conv")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads. Every matrix N(0, 0.02) and the MLP's down
# projection 0.02 / sqrt(2 n_layer), as a fresh Hugging Face model. The
# token table N(0, 0.005): the table is the head too, so a token's own row
# stands out among the logits by 12 sqrt(d) std / rms(h) of their spread
# (the embedding multiplier times the row's length over the stream's size
# at the last layer, ~1.8): at 0.05 that is 15 spreads and every greedy
# reply repeats its last token for ever, at 0.005 it is 1.5 and the largest
# logit is some other token's. The first layer reads the table through its
# norm, whatever its size. The Mamba-2 layer's own are `mamba2.init`'s.
EMBED_STD = 0.005


def _mlp_params(key, cfg: GraniteConfig) -> Params:
    k_in, k_out = jax.random.split(key)
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff
    return {"w_in": lm.normal(k_in, (D, 2 * F), 0.02, pd),
            "w_out": lm.normal(k_out, (F, D),
                             0.02 / math.sqrt(2 * cfg.n_layer), pd)}


def _init_layer(key: jax.Array, l, cfg: GraniteConfig, kind: str) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 8)
    pd, D = cfg.param_dtype, cfg.d_model
    out = {"mixer_norm": lm.ones(D), "mlp_norm": lm.ones(D),
           "mlp": _mlp_params(ks[0], cfg)}
    if kind == "attention":
        H, G, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        out["attn"] = {"wq": lm.normal(ks[1], (D, H * d), 0.02, pd),
                       "wk": lm.normal(ks[2], (D, G * d), 0.02, pd),
                       "wv": lm.normal(ks[3], (D, G * d), 0.02, pd),
                       "wo": lm.normal(ks[4], (H * d, D), 0.02, pd)}
        return out
    out["ssm"] = mamba2.init(ks[1:8], cfg)
    return out


def init_layer(key: jax.Array, l: int, cfg: GraniteConfig) -> Params:
    """Layer l's weights from `fold_in(key, l)` and nothing else, of the
    kind `cfg.layer_types[l]` names, by the one compiled program a kind
    (`lm.layer_program`): a layer made alone is, to the bit, the layer in
    `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg, cfg.layer_types[l])(
        key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: GraniteConfig) -> Params:
    """What is not a layer: the table (tied: it is the head too) and the
    final norm, from `fold_in(key, cfg.n_layer)`."""
    k_emb = jax.random.fold_in(key, cfg.n_layer)
    return {"wte": lm.normal(k_emb, (cfg.vocab_size, cfg.d_model), EMBED_STD,
                           cfg.param_dtype),
            "final_norm": lm.ones(cfg.d_model)}


def init_params(key: jax.Array, cfg: GraniteConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in: `mamba`
    and `attention`, one stack a kind of layer on a leading axis, in the
    order the layers have, a layer at a time (`lm.stack_layers`: the most
    that exists beside the tree is one layer)."""
    out = dict(init_ends(key, cfg))
    for kind in ("mamba", "attention"):
        layers = [l for l, t in enumerate(cfg.layer_types) if t == kind]
        if layers:
            out[kind] = lm.stack_layers(
                lambda i: init_layer(key, layers[i], cfg), len(layers))
    return out


resident_params = lm.resident_params


def resident_specs(cfg: GraniteConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the granite family is served on one chip: its weights, its rows "
        "and its state have no partition specs yet (tensor_parallel_size > "
        "1 is GPT-2's)")


def num_params(cfg: GraniteConfig) -> int:
    D, F = cfg.d_model, cfg.d_ff
    mlp = 3 * D * F + 2 * D
    attention = 2 * D * cfg.n_head * cfg.head_dim \
        + 2 * D * cfg.n_kv_head * cfg.head_dim
    return (cfg.layers_of("mamba") * (mamba2.num_params(cfg) + mlp)
            + cfg.layers_of("attention") * (attention + mlp)
            + cfg.vocab_size * D + D)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

def init_cache(cfg: GraniteConfig, batch: int, max_len: Optional[int] = None):
    """{"k", "v" [attention layers, B, G, 64, T]} in the compute dtype, by
    the 8 key-value heads, not broadcast, the positions on the lanes
    (`ops/rows_write.py`); {"ssm" [Mamba layers, B, N, H P],
    "conv" [Mamba layers, B, 3 x 4352]} float32 (the three inputs side by
    side on the lanes: as [.., 3, 4352] the compiler re-lays the leaf
    round the layers' loop), zero, which is what a sequence starts from.
    `max_len` sizes the rows alone: a slot's state is as large after one
    token as after a million."""
    T = max_len or cfg.max_seq_len
    rows = (cfg.layers_of("attention"), batch, cfg.n_kv_head, cfg.head_dim,
            T)
    return {"k": jnp.zeros(rows, cfg.dtype), "v": jnp.zeros(rows, cfg.dtype),
            **mamba2.init_cache(cfg, cfg.layers_of("mamba"), batch)}


# ---------------------------------------------------------------------------
# The layers
# ---------------------------------------------------------------------------
#
# Both programs take a slot's first lane through `_mamba_first` and
# `_attention_first`, all slots at once: that is the whole decode program,
# and in the chunk program it is every decode lane riding along and the
# first token of every chunk. A slot whose chunk has further lanes takes
# them through `_mamba_further` and `_attention_further`, a slot at a time
# and only such slots (`lm.each_slot`; `_further_lanes` is a slot's layer):
# the SSD form and a chunk's scores are computed for the lanes that prefill,
# never for the padding of the other slots' lanes, which at 48 slots of 64
# lanes is 98% of them and cost 190 of a chunk step's 235 ms when every
# slot went through the SSD form (PERF.md, PR 38).

def _mamba_first(x, bp, cfg: GraniteConfig, cache, l, pos, on):
    """One Mamba-2 mixer over every slot's first lane, x [B,1,D] float32,
    by the recurrence (`mamba2.first`): -> (x, cache). `on` [B]: the slots
    whose lane is valid; the others keep their state and window bit for
    bit."""
    del pos
    with jax.named_scope("attn"):
        o, cache = mamba2.first(rms_norm(x, bp["mixer_norm"], cfg.norm_eps),
                                bp["ssm"], cfg, cache, l, on)
    return x + cfg.residual_multiplier * o, cache


def _mamba_further(x, bp, cfg: GraniteConfig, cache, l, slot, pos, ok):
    """The same mixer over one slot's further lanes, x [1,M,D], by the SSD
    form from the state its first lane left (`mamba2.further`): -> (x,
    cache)."""
    del pos
    with jax.named_scope("attn"):
        o, cache = mamba2.further(
            rms_norm(x, bp["mixer_norm"], cfg.norm_eps), bp["ssm"], cfg,
            cache, l, slot, ok)
    return x + cfg.residual_multiplier * o, cache


def _qkv(x, bp, cfg: GraniteConfig):
    """x [B,M,D] -> q [B,M,G,R,d], k, v [B,M,G,d] in the compute dtype."""
    with jax.named_scope("gqa_project"):
        return lm.gqa_qkv(rms_norm(x, bp["mixer_norm"], cfg.norm_eps),
                          bp["attn"], cfg.n_kv_head, cfg.queries_per_kv,
                          cfg.head_dim, cfg.dtype)


def _attn_out(x, y, bp, cfg: GraniteConfig):
    B, M, _ = x.shape
    with jax.named_scope("gqa_project"):
        o = lm.dot(y.reshape(B, M, -1), bp["attn"]["wo"], cfg.dtype)
    return x + cfg.residual_multiplier * o


def rows_read_block(cache) -> int:
    """The positions of a slot's rows that a first lane's attention reads at
    a time, as `ops/gqa_attend.read_block` has them for these leaves. What
    `serve/llm.py` counts `positions_read` by; it counts a slot of a chunk
    step at all T, which a prefilling slot's further lanes read (its first
    lane its block besides) and a decode lane riding along does not: too
    many where decode lanes fill the chunk steps (PERF.md PR 63 has both
    ratios)."""
    return read_block(cache["k"].shape, cache["v"].shape,
                      cache["k"].shape[3])


def _attention_first(x, bp, cfg: GraniteConfig, cache, l, pos, on):
    """One grouped-head attention mixer over every slot's first lane, x
    [B,1,D], at position pos [B]: -> (x, cache). A slot that is `on` reads
    its rows once and to its own position (`ops/gqa_attend.py`: on the chip
    a kernel, elsewhere `lm.gqa_attend` over all T of every slot); one that
    is not reads nothing where the kernel runs, and its lane, zeros there
    and a value nobody reads in the plain form, meets no other slot's."""
    with jax.named_scope("attn"):
        q, k, v = _qkv(x, bp, cfg)
        with jax.named_scope("kv_update"):
            ck = rows_write(cache["k"], l, k[:, 0], pos, on)
            cv = rows_write(cache["v"], l, v[:, 0], pos, on)
        with jax.named_scope("gqa_attend"):
            y = gqa_attend(q[:, 0], ck, cv, l, pos, on,
                           cfg.attention_multiplier)
        x = _attn_out(x, y, bp, cfg)
    return x, {**cache, "k": ck, "v": cv}


def _attention_further(x, bp, cfg: GraniteConfig, cache, l, slot, pos, ok):
    """The same mixer over one slot's further lanes, x [1,M,D], the first of
    them at position pos: its scores are [G, R M, T] floats."""
    M = x.shape[1]
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    T = cache["k"].shape[4]
    with jax.named_scope("attn"):
        q, k, v = _qkv(x, bp, cfg)
        with jax.named_scope("kv_update"):
            ck = lm.gqa_write_slot(cache["k"], l, slot, k[0], pos, ok[0])
            cv = lm.gqa_write_slot(cache["v"], l, slot, v[0], pos, ok[0])
        with jax.named_scope("gqa_attend"):
            rows = [lax.dynamic_slice(leaf, (l, slot, 0, 0, 0),
                                      (1, 1, G, d, T))[0, 0]
                    for leaf in (ck, cv)]
            # [M,G,R,d] -> [G, R M, d]: a head's queries side by side
            qs = jnp.transpose(q[0], (1, 2, 0, 3)).reshape(G, R * M, d)
            at = jnp.broadcast_to(pos + jnp.tile(jnp.arange(M), R),
                                  (G, R * M))
            y = lm.gqa_attend(qs, *rows, at, cfg.attention_multiplier,
                              cfg.dtype)
            y = jnp.transpose(y.reshape(G, R, M, d), (2, 0, 1, 3))[None]
        x = _attn_out(x, y, bp, cfg)
    return x, {**cache, "k": ck, "v": cv}


def _mlp(x, bp, cfg: GraniteConfig):
    with jax.named_scope("mlp"):
        ab = lm.dot(rms_norm(x, bp["mlp_norm"], cfg.norm_eps),
                    bp["mlp"]["w_in"], cfg.dtype)
        a, b = ab[..., :cfg.d_ff], ab[..., cfg.d_ff:]
        o = lm.dot(jax.nn.silu(a) * b, bp["mlp"]["w_out"], cfg.dtype)
        return x + cfg.residual_multiplier * o


_FIRST = {"mamba": _mamba_first, "attention": _attention_first}
_FURTHER = {"mamba": _mamba_further, "attention": _attention_further}


def _further_lanes(kind: str, x, stack, cfg: GraniteConfig, cache, l, pos,
                   ok, prefilling):
    """Layer l of `kind`'s stack over the lanes after the first, x [B,M,D]
    with ok [B,M], the first of them at pos [B], for the slots `prefilling`
    a slot at a time (`lm.each_slot`, which has why the weights are sliced
    inside the body here)."""
    def slot(b, carry):
        x, cache = carry
        bp = lm.layer_weights(stack, l, turn=b)
        xb, okb, at = lm.slot_lanes(b, x, ok, pos)
        xb, cache = _FURTHER[kind](xb, bp, cfg, cache, l, b, at[0], okb)
        return lm.put_lanes(x, _mlp(xb, bp, cfg), b), cache

    return lm.each_slot(prefilling, slot, (x, cache))


def _logits(params: Params, x, cfg: GraniteConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return lm.dot(x, params["wte"].T, cfg.dtype) / cfg.logits_scaling


def _period(cfg: GraniteConfig):
    """(how many times the layer pattern repeats, one period as runs of a
    kind: [(kind, layers)]): the loop over the layers is a loop over the
    periods, whose body holds a loop a run."""
    types = cfg.layer_types
    size = next(n for n in range(1, len(types) + 1)
                if len(types) % n == 0
                and types == types[:n] * (len(types) // n))
    runs: list = []
    for kind in types[:size]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return len(types) // size, [(kind, n) for kind, n in runs]


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: GraniteConfig):
    B, C = tokens.shape
    ok = (jnp.arange(C)[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32) \
            * cfg.embedding_multiplier                           # [B, C, D]
    # the further lanes are C - 1 a slot: no experts, no tile to fill
    first, on, rest, further, prefilling = lm.split_lanes(x, ok, pad=False)
    periods, runs = _period(cfg)
    per_period = {kind: sum(n for k, n in runs if k == kind)
                  for kind in _FIRST}

    def layer(kind: str, l, first, rest, cache):
        bp = lm.layer_weights(params[kind], l)
        first, cache = _FIRST[kind](first, bp, cfg, cache, l, pos0, on)
        first = _mlp(first, bp, cfg)
        if rest is not None:
            # the loop writes the rows where the first lanes read them
            first, cache = lax.optimization_barrier((first, cache))
            rest, cache = _further_lanes(kind, rest, params[kind], cfg, cache,
                                         l, pos0 + 1, further, prefilling)
        return first, rest, cache

    def period(carry, n):
        done = {kind: n * per_period[kind] for kind in _FIRST}
        for kind, count in runs:
            start = done[kind]
            if count == 1:
                carry = layer(kind, start, *carry)
            else:
                carry = lax.fori_loop(
                    0, count, lambda j, c, kind=kind, start=start:
                    layer(kind, start + j, *c), carry)
            done[kind] = start + count
        return carry, None

    # the cache is a carry: one buffer a leaf from layer to layer, written
    # in place where the caller donates it
    with jax.named_scope("layers"):
        (first, rest, cache), _ = lax.scan(
            period, (first, rest, dict(cache)), jnp.arange(periods))
    x = lm.last_valid_lane(lm.join_lanes(first, rest, C), length)
    return _logits(params, x, cfg), cache


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: GraniteConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). The rows are written from
    pos0; the state does not read it. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: GraniteConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): the recurrence through the
    state-update kernel and attention over the cached rows, one token a
    slot; the chunk program's first lane, and nothing else of it."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg)
