"""Latent attention (MLA) with positions, in its absorbed form over a latent
cache: the layer that the DeepSeek-V3 family (`models/deepseek.py`: Kanana)
and LongCat-Flash (`models/longcat.py`) share, and the cache's rows that
every latent family writes and reads (`cache_write`, `write_first`, `rows`:
Kimi's and Keye's too).

With d the hidden size, H heads, `qk_nope_head_dim` n, `qk_rope_head_dim` p,
`v_head_dim` v, `kv_lora_rank` r, u the normed input, no biases:

    q = u W_q -> [H, n + p]                          (no query latent), or
    c_q = s_q RMSNorm_q(u W_qa);  q = c_q W_qb       (`wqa` among the weights:
                                                      `q_lora_rank` r_q)
    [c, k_r] = u W_kva -> r + p;  c = s_kv RMSNorm_kv(c)
    RoPE(position) on q[.., n:] (each head) and on k_r (one key for all heads)

  absorbed form, with W_kvb split by head into W_uk [r, H, n], W_uv [r, H, v]:
    q' = q_nope W_uk^T -> [H, r]
    scores (q' . c_t + q_rope . k_r,t) / sqrt(n + p) against the cache
    out = concat((softmax . c) W_uv) W_o

The cache holds c after its norm and its factor and k_r after RoPE: r + p
values a token a layer and nothing by head. The two factors `scales` = (s_q,
s_kv) are LongCat's `mla_scale_q_lora` / `mla_scale_kv_lora`, sqrt(d / r_q)
and sqrt(d / r) on the normed latents (not on the shared rotary key); a
family without them passes none and nothing is multiplied.

The layer exists in the two precisions its families state, by `whole`:

  rounded (Kanana): the norm's output is rounded to the compute dtype and
    every product takes it as one piece; a decode step's row a slot is
    blended into a window of the leaf (`cache_write`).
  whole (LongCat; Kimi's `_mla` is this form without the rotation):
    everything projected stays float32, a product's activation goes as the
    two compute-dtype pieces that add up to it (`lm.dot`, `ops/pieces.py`)
    against the weight as it is held, the rows and the queries that meet
    them are the compute dtype's, and a decode step's rows are one scatter
    for all slots (`write_first`).

Every slot's one lane (the decode program whole) attends through the kernel
`ops/mla_attend.py`, a chunk's further lanes through its plain form
`attend_rows` against the one slot's rows.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm
from ray_tpu.models.llama import apply_rope, rms_norm, rope_freqs
from ray_tpu.ops.mla_attend import attend_rows, mla_attend
from ray_tpu.ops.pieces import pieces

Params = Any

# as `gpt2._WRITE_WINDOW`: the narrowest stretch of positions a write touches
_WRITE_WINDOW = 128


def cache_write(c, l, val, pos0, ok, slot=None):
    """Layer l of the carried leaf c [L,B,T,F] takes val [N,C,F]: lane i of
    row n goes to position pos0[n] + i where ok[n, i], in slot n (N = B), or
    in `slot` for the one row of that slot's own lanes; nothing else
    changes. `gpt2._cache_write` without the heads: per slot one window of
    W >= C positions is read, blended and written back in place."""
    T, F = c.shape[2:]
    N, C = val.shape[:2]
    W = min(T, max(C, _WRITE_WINDOW))
    start = jnp.clip(pos0 // W * W if C == 1 else pos0, 0, T - W)
    src = jnp.arange(W)[None, :] - (pos0 - start)[:, None]            # [N, W]
    hit = (src[:, :, None] == jnp.arange(C)) & ok[:, None, :]      # [N, W, C]
    moved = jnp.einsum("bwc,bcf->bwf", hit.astype(val.dtype), val,
                       precision=lax.Precision.HIGHEST)
    take = hit.any(axis=-1)                                           # [N, W]
    for b in range(N):
        at = (l, b if slot is None else slot, start[b], 0)
        old = lax.dynamic_slice(c, at, (1, 1, W, F))
        new = jnp.where(take[b][:, None], moved[b], old)
        c = lax.dynamic_update_slice(c, new, at)
    return c


def write_first(c, l, val, pos, ok, slot=None):
    """Layer l of the carried leaf c [L,B,T,F] takes val [B,1,F]: slot b's
    row goes to position pos[b] where ok[b, 0], one scatter for all slots
    (`cache_write` at one lane is a read, a blend and a write a slot: 900
    small operations a layer at 128 slots, two fifths of a decode step's and
    of what a trace of it holds: PERF.md, PR 40)."""
    del slot
    B, T = val.shape[0], c.shape[2]
    at = jnp.where(ok[:, 0], pos, T)            # past the end: dropped
    return c.at[l, jnp.arange(B), at].set(val[:, 0], mode="drop",
                                          unique_indices=True)


def rows(c, l, slot=None):
    """Layer l of the carried leaf c [L,B,T,F] as attention reads it: every
    slot's rows [B,T,F], or `slot`'s alone [1,T,F], where they lie."""
    if slot is None:
        return c[l]
    return lax.dynamic_slice(c, (l, slot, 0, 0), (1, 1) + c.shape[2:])[0]


def latent_scales(cfg) -> tuple:
    """(s_q, s_kv) of a config that has `mla_scale_q_lora` /
    `mla_scale_kv_lora`: sqrt(d / rank) where the flag is set, else None."""
    return (math.sqrt(cfg.d_model / cfg.q_lora_rank)
            if cfg.mla_scale_q_lora else None,
            math.sqrt(cfg.d_model / cfg.kv_lora_rank)
            if cfg.mla_scale_kv_lora else None)


def _by_head(x, w, spec: str, dtype):
    """The float32 x times a weight by the head, `spec` an einsum whose
    result comes out head first behind the pieces' axis ("a.., ..h.. ->
    ha.."): x as its two pieces, summed, the heads moved behind the lanes.
    (A product batched by the head comes out head first: the CPU backend has
    no other float32 product of two bf16 operands.)"""
    return jnp.moveaxis(jnp.sum(jnp.einsum(
        spec, pieces(x, dtype), w, preferred_element_type=jnp.float32),
        axis=1), 0, 2)


def attention(x, norm, p, cfg, lat, kr, l, pos0, pos, ok, slot=None,
              rope: bool = True, scales: tuple = (None, None),
              whole: bool = False):
    """x [N,C,D] float32 += absorbed attention of its C lanes (positions
    `pos` [N,C], written where `ok`) against layer l of the carried caches,
    `norm` the input norm's scale and `p` the layer's weights (`wq`, or
    `wqa`, `q_norm`, `wqb` for a query latent; `wkva`, `kv_norm`, `wkvb` [r,
    H, n + v], `wo`): row n is slot n (N = B), or the one row is `slot`'s
    own lanes against that slot's rows alone. Without `rope` the p lanes go
    un-rotated, a shared key that knows no position (Kimi Linear's
    `mla_use_nope`; `models/kimi.py` is held to this form). `scales` and
    `whole`: the module's docstring. -> (x, lat, kr)."""
    B, C, _ = x.shape
    H, r = cfg.n_head, cfg.kv_lora_rank
    n, v = cfg.qk_nope_head_dim, cfg.v_head_dim
    q_scale, kv_scale = scales

    def project(a, w):
        """a [.., K] times the weight w [K, ..] -> [.., ..]."""
        if whole:
            return lm.dot(a, w.reshape(w.shape[0], -1), cfg.dtype).reshape(
                a.shape[:-1] + w.shape[1:])
        return jnp.tensordot(a, lm.weight(w, cfg.dtype), 1)

    with jax.named_scope("attn"):
        h = rms_norm(x, norm, cfg.norm_eps)
        if not whole:
            h = h.astype(cfg.dtype)
        with jax.named_scope("mla_project"):
            if "wqa" in p:
                c_q = rms_norm(project(h, p["wqa"]), p["q_norm"],
                               cfg.norm_eps)
                q = project(c_q if q_scale is None else c_q * q_scale,
                            p["wqb"])
            else:
                q = project(h, p["wq"])                         # [N,C,H,n+p]
            ckr = project(h, p["wkva"])                           # [N,C,r+p]
            c = rms_norm(ckr[..., :r], p["kv_norm"], cfg.norm_eps)
            if kv_scale is not None:
                c = c * kv_scale
            q_rope, k_r = q[..., n:], ckr[..., r:]                # [N,C,H,p]
            if rope:
                cos, sin = rope_freqs(pos, cfg.qk_rope_head_dim,
                                      cfg.rope_theta)
                cos, sin = cos[:, :, None, :], sin[:, :, None, :]
                q_rope = apply_rope(q_rope, cos, sin)
                k_r = apply_rope(k_r[:, :, None], cos, sin)[:, :, 0]
            wkvb = lm.weight(p["wkvb"], cfg.dtype)
            if whole:
                q_abs = _by_head(q[..., :n], wkvb[..., :n],
                                 "abchn,rhn->habcr", cfg.dtype)
                q_abs, q_rope, c, k_r = (t.astype(cfg.dtype) for t in (
                    q_abs, q_rope, c, k_r))
            else:
                q_abs = jnp.einsum("bchn,rhn->bchr", q[..., :n],
                                   wkvb[..., :n])
        with jax.named_scope("kv_update"):
            first = slot is None and C == 1
            write = write_first if whole and first else cache_write
            lat = write(lat, l, c, pos0, ok, slot)
            kr = write(kr, l, k_r, pos0, ok, slot)
        with jax.named_scope("mla_attend"):
            scale = 1.0 / math.sqrt(cfg.qk_head_dim)
            if first:
                # every slot's one lane, the decode program's work: a
                # slot's rows read once and to its own position
                mixed = mla_attend(q_abs[:, 0], q_rope[:, 0], lat, kr, l,
                                   pos[:, 0], ok[:, 0], scale)[:, :, None]
            else:
                mixed = attend_rows(q_abs, q_rope, rows(lat, l, slot),
                                    rows(kr, l, slot), pos, scale)
            # [N,H,C,r] float32, the heads before the lanes
            if not whole:
                mixed = jnp.moveaxis(mixed, 1, 2).astype(cfg.dtype)
        with jax.named_scope("mla_project"):
            if whole:
                o = _by_head(mixed, wkvb[..., n:], "abhcr,rhv->habcv",
                             cfg.dtype)
                x = x + lm.dot(o.reshape(B, C, H * v), p["wo"], cfg.dtype)
            else:
                o = jnp.einsum("bchr,rhv->bchv", mixed, wkvb[..., n:])
                x = x + jnp.dot(o.reshape(B, C, H * v),
                                lm.weight(p["wo"], cfg.dtype),
                                preferred_element_type=x.dtype)
    return x, lat, kr
