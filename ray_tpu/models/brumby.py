"""Brumby's layer for serving: power retention in place of attention, over a
recurrent state in place of keys and values.

What is served is `manifestai/Brumby-14B-Base` (`model_type: brumby`; preset
`brumby-14b`): Qwen3-14B's shapes with every attention layer replaced by
power retention of degree 2 (Manifest AI's model card; "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239). With d the hidden size,
H query heads and G key-value heads of 128 lanes, R = H / G, no biases but
the gate's:

    h = RMSNorm(x)
    q = h W_q -> [H, 128];  k = h W_k, v = h W_v -> [G, 128]
    q, k: RMSNorm over the 128 lanes (one scale for all heads), then RoPE
    log g = logsigmoid(h W_g + b_g), float32: one gate a key-value head

    S_t = g_t S_{t-1} + phi(k_t) v_t^T        z_t = g_t z_{t-1} + phi(k_t)
    y_t^r = phi(q_t^r)^T S_t / (phi(q_t^r) . z_t + eps)

    x += concat(y) W_o;   x += SwiGLU(RMSNorm(x))
    final RMSNorm, untied head, logits float32

`phi(a) . phi(b) = (a . b)^2` (`ops/power_retention.py` has the expansion and
its layout). The state a key-value head keeps, S [128, 8,320] and z [8,320]
in float32, takes the place of a cache of keys and values: it has no token
axis, a token rewrites all of it, and what a prefix leaves behind is the
state at its end and nothing else (`CACHE_STATE`; `serve/kv_cache.py` pools
snapshots of it).

The mixer exists in two forms and no third. `decode_step` is the recurrence
above, one token a slot, through the Pallas kernel `retention_update`.
`prefill_chunk` takes C tokens a slot after position s, with
a_i = sum_{s < m <= i} log g_m:

    num_i = e^{a_i} phi(q_i)^T S_s + sum_{s < j <= i} e^{a_i - a_j} (q_i . k_j)^2 v_j
    den_i likewise with z_s and without v_j
    S_{s+C} = e^{a_{s+C}} S_s + sum_j e^{a_{s+C} - a_j} phi(k_j) v_j^T, z likewise

a slot at a time, so that the expanded queries of one slot's chunk
(C x H x 8,320 floats) are all that is held of them. A lane past a slot's
length has log g = 0 and adds nothing; a slot with no valid lane is
skipped (`lm.each_slot` turns over the others alone) and keeps its state
bit for bit, in both programs (`models/lm.py`, "The lanes of a chunk", has
the contract).

The weights exist only in the dtype the replica holds them, a layer at a
time, as `models/deepseek.py` makes its own; the gate's projection, its
bias and the norms' scales are float32, and so are the residual stream, q,
k, v and the gates once projected, the state and everything computed from
it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs
from ray_tpu.ops import power_retention as _pr

Params = Any


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    n_layer: int = 40
    n_head: int = 40
    n_kv_head: int = 8
    head_dim: int = 128
    d_model: int = 5120
    d_ff: int = 17408
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    retention_eps: float = 1e-6      # beside the normaliser
    dtype: Any = jnp.bfloat16        # compute
    param_dtype: Any = jnp.bfloat16  # what the replica holds

    @property
    def queries_per_kv(self) -> int:
        return self.n_head // self.n_kv_head

    @property
    def expanded_width(self) -> int:
        return _pr.expanded_width(self.head_dim)

    @classmethod
    def preset(cls, name: str, **overrides) -> "BrumbyConfig":
        return cls(**{**PRESETS[name], **overrides})


PRESETS = {
    # manifestai/Brumby-14B-Base config.json: the defaults
    "brumby-14b": dict(),
    "brumby-tiny": dict(vocab_size=512, n_layer=2, n_head=4, n_kv_head=2,
                        head_dim=16, d_model=64, d_ff=128, max_seq_len=128),
}

# the serving contract (`models/__init__.py`): no leaf holds a value a
# token; these hold a slot's state, [layers, slots, ...] with no token axis
CACHE_TOKEN_AXIS: dict = {}
CACHE_STATE = ("state", "norm")


# ---------------------------------------------------------------------------
# Weights, a layer at a time
# ---------------------------------------------------------------------------

# The seeded weights' spreads, N(0, std): every matrix 0.02 and the MLP's
# down projection 0.02 / sqrt(2 n_layer), as a fresh Hugging Face model, but
# the token table 0.3 (PR 29's argument for Kanana: at 0.02 the stream is a
# small part of what the first layers add to it, and every rounding is
# amplified by the next norm) and W_o 0.02. The gate's bias is drawn
# uniformly in [4, 8]: g = sigmoid(b + N(0, 1.4)) lies about 0.982-0.9997,
# a memory of 40 to 2,000 tokens, as a trained retention layer has. At
# b = 0 random weights give g ~ 0.5, the state forgets within a few tokens,
# and an error in carrying state across chunks, snapshots and slots hides
# inside any tolerance.
EMBED_STD, ATTN_OUT_STD = 0.3, 0.02
GATE_BIAS_RANGE = (4.0, 8.0)


def _init_layer(key: jax.Array, l, cfg: BrumbyConfig) -> Params:
    ks = jax.random.split(jax.random.fold_in(key, l), 9)
    pd, D, F = cfg.param_dtype, cfg.d_model, cfg.d_ff
    H, G, d = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    lo, hi = GATE_BIAS_RANGE
    return {
        "attn_norm": lm.ones(D),
        "attn": {
            "wq": lm.normal(ks[0], (D, H, d), 0.02, pd),
            "wk": lm.normal(ks[1], (D, G, d), 0.02, pd),
            "wv": lm.normal(ks[2], (D, G, d), 0.02, pd),
            "wg": lm.normal(ks[3], (D, G), 0.02, jnp.float32),
            "bg": jax.random.uniform(ks[4], (G,), jnp.float32, lo, hi),
            "q_norm": lm.ones(d), "k_norm": lm.ones(d),
            "wo": lm.normal(ks[5], (H * d, D), ATTN_OUT_STD, pd),
        },
        "mlp_norm": lm.ones(D),
        "mlp": {"wg": lm.normal(ks[6], (D, F), 0.02, pd),
                "wu": lm.normal(ks[7], (D, F), 0.02, pd),
                "wd": lm.normal(ks[8], (F, D),
                              0.02 / math.sqrt(2 * cfg.n_layer), pd)},
    }


def init_layer(key: jax.Array, l: int, cfg: BrumbyConfig) -> Params:
    """Layer l's weights from `fold_in(key, l)` and nothing else, by the one
    compiled program (`lm.layer_program`): a layer made alone is, to the
    bit, the layer in `init_params`' tree."""
    return lm.layer_program(_init_layer, cfg)(key, jnp.int32(l))


@functools.partial(jax.jit, static_argnums=(1,))
def init_ends(key: jax.Array, cfg: BrumbyConfig) -> Params:
    """What is not a layer: the table, the final norm and the untied head,
    from `fold_in(key, cfg.n_layer)`; one compiled program, as a layer's is
    (eager, each matrix would exist in float32 first: 3.1 GB)."""
    k_emb, k_head = jax.random.split(jax.random.fold_in(key, cfg.n_layer))
    pd, D, V = cfg.param_dtype, cfg.d_model, cfg.vocab_size
    return {"wte": lm.normal(k_emb, (V, D), EMBED_STD, pd),
            "final_norm": lm.ones(D),
            "lm_head": lm.normal(k_head, (D, V), 0.02, pd)}


def init_params(key: jax.Array, cfg: BrumbyConfig) -> Params:
    """The whole tree, every leaf made in the dtype it is held in, `blocks`
    stacked on a leading layer axis a layer at a time (`lm.stack_layers`:
    the most that exists beside the tree is one layer)."""
    return {**init_ends(key, cfg), "blocks": lm.stack_layers(
        lambda l: init_layer(key, l, cfg), cfg.n_layer)}


resident_params = lm.resident_params


def resident_specs(cfg: BrumbyConfig, rules=None) -> Params:
    raise NotImplementedError(
        "the brumby family is served on one chip: its weights and its state "
        "have no partition specs yet (tensor_parallel_size > 1 is GPT-2's)")


def num_params(cfg: BrumbyConfig) -> int:
    D, H, G, d = cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim
    layer = (2 * D * H * d + 2 * D * G * d + D * G + G + 2 * d + 2 * D
             + 3 * D * cfg.d_ff)
    return cfg.n_layer * layer + 2 * cfg.vocab_size * D + D


# ---------------------------------------------------------------------------
# The state
# ---------------------------------------------------------------------------

def init_cache(cfg: BrumbyConfig, batch: int, max_len: Optional[int] = None):
    """{"state" [n_layer, B, G, 128, W], "norm" [n_layer, B, G, W]} float32,
    W the expanded width: S^T and z of every slot and key-value head, zero,
    which is what a sequence starts from. `max_len` sizes nothing here: a
    slot's state is as large after one token as after a million."""
    del max_len
    L, G, d, W = cfg.n_layer, cfg.n_kv_head, cfg.head_dim, cfg.expanded_width
    return {"state": jnp.zeros((L, batch, G, d, W), jnp.float32),
            "norm": jnp.zeros((L, batch, G, W), jnp.float32)}


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

_HIGHEST = lax.Precision.HIGHEST


def _project(x, bp, cfg: BrumbyConfig, pos):
    """x [B,C,D] float32 -> q [B,C,H,d], k, v [B,C,G,d] and the gates'
    logarithms [B,C,G], all float32, q and k normed and turned."""
    p = bp["attn"]
    with jax.named_scope("retention_project"):
        h32 = rms_norm(x, bp["attn_norm"], cfg.norm_eps)
        h = h32.astype(cfg.dtype)
        q = jnp.einsum("bcd,dhk->bchk", h, lm.weight(p["wq"], cfg.dtype),
                       preferred_element_type=jnp.float32)
        k = jnp.einsum("bcd,dhk->bchk", h, lm.weight(p["wk"], cfg.dtype),
                       preferred_element_type=jnp.float32)
        v = jnp.einsum("bcd,dhk->bchk", h, lm.weight(p["wv"], cfg.dtype),
                       preferred_element_type=jnp.float32)
        # the gate reads the norm's float32 output, not its rounding
        log_g = jax.nn.log_sigmoid(
            jnp.einsum("bcd,dg->bcg", h32, p["wg"], precision=_HIGHEST)
            + p["bg"])
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        cos, sin = rope_freqs(pos, cfg.head_dim, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, log_g


def _retention_step(q, k, v, log_g, state, norm, l, active,
                    cfg: BrumbyConfig):
    """The recurrence for one token a slot: q [B,H,d], k, v [B,G,d], log_g
    [B,G] -> (y [B,H,d], state, norm)."""
    B = q.shape[0]
    G, R, d = cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim
    with jax.named_scope("retention_update"):
        state, norm, num, den = _pr.retention_update(
            state, norm, l, q.reshape(B, G, R, d), k, v, jnp.exp(log_g),
            active)
        y = num / (den[..., None] + cfg.retention_eps)
    return y.reshape(B, G * R, d), state, norm


def _retention_chunk(q, k, v, log_g, state, norm, l, ok, prefilling,
                     cfg: BrumbyConfig):
    """The chunked form for C lanes a slot: q [B,C,H,d], k, v [B,C,G,d],
    log_g [B,C,G], ok [B,C] -> (y [B,C,H,d], state, norm). A slot at a time
    (its state is one stretch of the leaf, and its expanded queries,
    C x H x W floats, all that is held of them), the slots `prefilling`
    alone (`lm.slots_first` of those with a valid lane): any other is
    skipped, and its state is what it was."""
    B, C = ok.shape
    G, R, d, W = (cfg.n_kv_head, cfg.queries_per_kv, cfg.head_dim,
                  cfg.expanded_width)
    with jax.named_scope("retention_chunk"):
        q = q.reshape(B, C, G, R, d)
        a = jnp.cumsum(jnp.where(ok[:, :, None], log_g, 0.0), axis=1)
        lane = jnp.arange(C)
        causal = lane[None, :] <= lane[:, None]                    # [i, j]

        def take(x, b):
            return lax.dynamic_index_in_dim(x, b, 0, keepdims=False)

        def slot(b, carry):
            state, norm, ys = carry
            s = lax.dynamic_slice(state, (l, b, 0, 0, 0),
                                  (1, 1, G, d, W))[0, 0]           # [G,d,W]
            z = lax.dynamic_slice(norm, (l, b, 0, 0), (1, 1, G, W))[0, 0]
            qb, kb, vb, ab, on = (take(x, b) for x in (q, k, v, a, ok))
            total = ab[-1]                                         # [G]
            into = jnp.exp(ab)                   # e^{a_i}: S_s's share [C,G]
            out_of = jnp.where(on[:, None], jnp.exp(total[None] - ab), 0.0)
            seen = (causal & on[None, :])[:, :, None]              # [i,j,1]
            within = jnp.where(seen, jnp.exp(jnp.where(
                seen, ab[:, None, :] - ab[None, :, :], 0.0)), 0.0)  # [i,j,G]
            phi_q, phi_k = _pr.phi(qb), _pr.phi(kb)    # [C,G,R,W] [C,G,W]
            num = jnp.einsum("cgrw,gvw->cgrv", phi_q, s, precision=_HIGHEST)
            den = jnp.einsum("cgrw,gw->cgr", phi_q, z, precision=_HIGHEST)
            weight = jnp.square(jnp.einsum(
                "igrd,jgd->igrj", qb, kb, precision=_HIGHEST)
            ) * jnp.moveaxis(within, 1, 2)[:, :, None, :]
            num = num * into[:, :, None, None] + jnp.einsum(
                "igrj,jgv->igrv", weight, vb, precision=_HIGHEST)
            den = den * into[:, :, None] + weight.sum(axis=-1)
            y = num / (den[..., None] + cfg.retention_eps)       # [C,G,R,d]
            keep = jnp.exp(total)
            s = keep[:, None, None] * s + jnp.einsum(
                "jgv,jgw->gvw", vb * out_of[:, :, None], phi_k,
                precision=_HIGHEST)
            z = keep[:, None] * z + jnp.einsum(
                "jg,jgw->gw", out_of, phi_k, precision=_HIGHEST)
            return (lax.dynamic_update_slice(state, s[None, None],
                                             (l, b, 0, 0, 0)),
                    lax.dynamic_update_slice(norm, z[None, None],
                                             (l, b, 0, 0)),
                    lax.dynamic_update_slice(ys, y[None], (b, 0, 0, 0, 0)))

        state, norm, ys = lm.each_slot(
            prefilling, slot,
            (state, norm, jnp.zeros((B, C, G, R, d), jnp.float32)))
    return ys.reshape(B, C, G * R, d), state, norm


def _mixer(x, bp, cfg: BrumbyConfig, state, norm, l, pos, ok, prefilling):
    """The recurrence for one lane a slot (`prefilling` None: the decode
    program), else the chunked form for the slots `prefilling`."""
    B, C, _ = x.shape
    with jax.named_scope("attn"):
        q, k, v, log_g = _project(x, bp, cfg, pos)
        if prefilling is None:
            y, state, norm = _retention_step(
                q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state, norm, l,
                ok[:, 0], cfg)
            y = y[:, None]
        else:
            y, state, norm = _retention_chunk(q, k, v, log_g, state, norm,
                                              l, ok, prefilling, cfg)
        with jax.named_scope("retention_project"):
            o = jnp.dot(y.reshape(B, C, -1).astype(cfg.dtype),
                        lm.weight(bp["attn"]["wo"], cfg.dtype),
                        preferred_element_type=x.dtype)
    return x + o, state, norm


def _mlp(x, bp, cfg: BrumbyConfig):
    with jax.named_scope("mlp"):
        h = rms_norm(x, bp["mlp_norm"], cfg.norm_eps).astype(cfg.dtype)
        p = bp["mlp"]
        wg, wu = lm.weight(p["wg"], cfg.dtype), lm.weight(p["wu"], cfg.dtype)
        up = jax.nn.silu(h @ wg) * (h @ wu)
        return x + jnp.dot(up, lm.weight(p["wd"], cfg.dtype),
                           preferred_element_type=x.dtype)


def _logits(params: Params, x, cfg: BrumbyConfig):
    with jax.named_scope("unembed_loss"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)
        return jnp.dot(x, lm.weight(params["lm_head"], cfg.dtype),
                       preferred_element_type=jnp.float32)


def _forward(params: Params, cache, tokens, pos0, length, active,
             cfg: BrumbyConfig, step: bool):
    B, C = tokens.shape
    lane = jnp.arange(C)
    pos = pos0[:, None] + lane[None, :]
    ok = (lane[None, :] < length[:, None]) & active[:, None]
    with jax.named_scope("embed"):
        x = params["wte"][tokens].astype(jnp.float32)              # [B, C, D]
        # the chunk program's slots: those with a valid lane, once a step
        prefilling = None if step else lm.slots_first(ok.any(axis=1))

    def body(carry, layer):
        x, state, norm = carry
        l, bp = layer
        x, state, norm = _mixer(x, bp, cfg, state, norm, l, pos, ok,
                                prefilling)
        return (_mlp(x, bp, cfg), state, norm), None

    # the state is a carry: one buffer from layer to layer, written in place
    # where the caller donates the cache
    with jax.named_scope("layers"):
        (x, state, norm), _ = lax.scan(
            body, (x, cache["state"], cache["norm"]),
            (jnp.arange(cfg.n_layer), params["blocks"]))
    return (_logits(params, lm.last_valid_lane(x, length), cfg),
            {"state": state, "norm": norm})


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: BrumbyConfig):
    """`gpt2.prefill_chunk`'s signature and every family's contract
    (`models/lm.py`, "The lanes of a chunk"): -> (logits [B, vocab] float32
    at each slot's last valid lane, the cache). RoPE reads pos0; the state
    does not. Donate `cache`."""
    return _forward(params, cache, tokens, pos0, length, active, cfg, False)


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: BrumbyConfig):
    """`gpt2.decode_step`'s contract: tokens [B], pos [B], active [B] ->
    (logits [B, vocab] float32, the cache): the recurrence, one token a
    slot, through the state-update kernel."""
    return _forward(params, cache, tokens[:, None], pos,
                    active.astype(jnp.int32), active, cfg, True)
