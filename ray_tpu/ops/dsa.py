"""Learned sparse attention over a slot's cached rows, in three steps: the
indexer's scores for every row up to a query's position, an exact choice of
the `k` largest, and attention over the chosen rows alone (DeepSeek Sparse
Attention's lightning indexer, as `models/keye.py` serves it).

    I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s]),  s <= t      (float32)
    S_t     = the k positions of largest I[t, .]; all of them while t < k;
              ties go to the lower index
    o[t, h] = softmax_{s in S_t}(q[t, h] . K[s, g(h)] * scale) V[s, g(h)]

The caches are token-major, `[layers, slots, T, F]`: a row is one token's F
values side by side. The scores and the choice are plain XLA: the scores a
product of the query's two bf16 pieces against the rows as they are held,
the choice `lax.top_k` (exact, and it hands equal values over lower index
first: never `approx_max_k`, a choice by blocks or a window, which are
other models). Two forms of the choice, one set: by index (`select_rows`)
and as a mask over a slot's T rows (`select_mask`: the k-th largest value,
and of the rows that equal it the lowest indices that fill the set). The
read has two forms too. For one query a slot (the decode program and every
slot's first lane) it is `ops/dsa_attend.py`'s: on the TPU a Pallas kernel
that takes the mask and reads the slot's rows once, to its position,
through VMEM; elsewhere, and as what that kernel is tested against, the
plain path of this file, a gather of whole rows out of the leaf where it
lies by `select_rows`' indices (`gather_rows`) and attention over the copy
(`attend_selected`). For the lanes of a chunk it is `attend_masked` over
one slot's rows under the mask.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.pieces import pieces

_HIGHEST = lax.Precision.HIGHEST


def _times_rows(x, rows):
    """x [N,M,e] float32 . rows [N,T,e] -> [N,M,T] float32: x whole, as
    the two pieces of the rows' dtype side by side on the product's rows
    (one pass of the rows); a float32 cache is one product at full
    precision."""
    if rows.dtype == jnp.float32:
        return jnp.einsum("nme,nte->nmt", x, rows, precision=_HIGHEST)
    both = jnp.einsum("npme,nte->npmt", pieces(x, rows.dtype, axis=1), rows,
                      preferred_element_type=jnp.float32)
    return both[:, 0] + both[:, 1]


def index_scores(qi, w, rows, at):
    """The indexer's scores of Q queries a row: qi [N,Q,J,e] float32 (J
    indexer heads), w [N,Q,J] float32, rows [N,T,e] the indexer's one key a
    token as the cache holds it, `at` [N,Q] each query's position -> I
    [N,Q,T] float32, -inf past `at`. The ReLU, the weights and the sum over
    the heads are float32."""
    N, Q, J, e = qi.shape
    T = rows.shape[1]
    dots = _times_rows(qi.reshape(N, Q * J, e), rows).reshape(N, Q, J, T)
    scores = jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)
    seen = jnp.arange(T) <= at[..., None]
    return jnp.where(seen, scores, -jnp.inf)


def select_rows(scores, k: int):
    """scores [..., T] (-inf where a row is not to be seen) -> (idx [..., K]
    int32, chosen [..., K] bool), K = min(k, T): the K largest, equal values
    lower index first; `chosen` is false for an entry that stands for no
    row (fewer than K rows were to be seen)."""
    values, idx = lax.top_k(scores, min(k, scores.shape[-1]))
    return idx.astype(jnp.int32), values > -jnp.inf


def select_mask(scores, k: int):
    """`select_rows`' set as a mask [..., T]: the rows above the K-th
    largest value, and of those that equal it the lowest indices that fill
    the set."""
    T = scores.shape[-1]
    if k >= T:
        return scores > -jnp.inf
    kth = lax.top_k(scores, k)[0][..., -1:]
    above = scores > kth
    level = scores == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (level & (jnp.cumsum(level, axis=-1) <= room))) \
        & (scores > -jnp.inf)


def gather_rows(leaf, layer, idx):
    """leaf [L,B,T,F], idx [B,K] -> layer `layer`'s chosen rows [B,K,F],
    read out of the leaf where it lies (no copy of the layer's rows)."""
    B = idx.shape[0]
    return leaf[layer, jnp.arange(B)[:, None], idx]


def attend_selected(q, k_rows, v_rows, chosen, scale: float):
    """q [B,G,R,d] (R query heads a key-value head) over the chosen rows
    k_rows, v_rows [B,K,G,d], `chosen` [B,K] -> [B,G,R,d] float32: scores,
    softmax over the set, weighted values. Operands in the rows' dtype,
    float32 accumulation."""
    scores = jnp.einsum("bgrd,bkgd->bgrk", q, k_rows,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(chosen[:, None, None, :], scores,
                                     -1e30), axis=-1)
    return jnp.einsum("bgrk,bkgd->bgrd", probs.astype(v_rows.dtype), v_rows,
                      preferred_element_type=jnp.float32)


def attend_masked(q, k_rows, v_rows, keep, scale: float):
    """One slot's lanes over all its rows with the set as a mask: q
    [G,Q,d], k_rows, v_rows [T,G,d], keep [G,Q,T] or broadcastable ->
    [G,Q,d] float32."""
    scores = jnp.einsum("gqd,tgd->gqt", q, k_rows,
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(jnp.where(keep, scores, -1e30), axis=-1)
    return jnp.einsum("gqt,tgd->gqd", probs.astype(v_rows.dtype), v_rows,
                      preferred_element_type=jnp.float32)
