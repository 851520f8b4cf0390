"""Sequence/context parallelism: ring attention + Ulysses over an `sp` mesh axis.

The reference has NO sequence-parallel implementation (SURVEY.md §5.7 —
verified absent); this is new TPU-native design work. Two schedules:

- **ring_attention**: Q stays put; K/V chunks rotate around the `sp` axis via
  `lax.ppermute` (rides the ICI ring), with a flash-style online-softmax
  accumulator (running max / normalizer / f32 accumulator) merging each
  chunk's partial attention. Peak memory per chip is O(T_local^2) scores for
  one chunk pair, so global sequence length scales linearly with the number
  of chips.
- **ulysses_attention**: `lax.all_to_all` reshards [heads <-> seq] so each
  chip holds all tokens for a head subset, runs ordinary (flash) attention
  locally, and all-to-alls back. Cheaper for moderate T when heads % sp == 0.

Both are exposed (a) as `*_local` functions usable inside an existing
`shard_map`, and (b) as array-level wrappers that install their own
`shard_map` over the active mesh (ray_tpu.parallel.mesh.use_mesh).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import current_mesh, logical_to_spec
from ray_tpu.util.collective.hierarchy import (account_collective,
                                               ring_perm)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Chunk accumulation (shared by ring steps)
# ---------------------------------------------------------------------------

def _chunk_update(q, kc, vc, m, l, acc, scale, q_off, k_off, causal):
    """Merge one K/V chunk into the online-softmax state.

    q [B,H,Tq,D]; kc,vc [B,H,Tk,D]; m,l [B,H,Tq,1]; acc [B,H,Tq,D] (f32).
    q_off/k_off are the global positions of element 0 (traced scalars ok).
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[2], kc.shape[2]
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        k_pos = k_off + lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        mask = (q_pos >= k_pos)[None, None]
        s = jnp.where(mask, s, NEG_INF)
    else:
        mask = None
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if mask is not None:
        # a fully-masked chunk must contribute zero (finite NEG_INF arithmetic
        # would otherwise give p=1 when m is still at its initial value)
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * corr + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vc.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


# ---------------------------------------------------------------------------
# Ring attention
# ---------------------------------------------------------------------------

def ring_attention_local(q, k, v, axis_name: str, causal: bool = True,
                         scale: Optional[float] = None):
    """Ring attention on per-device shards (call inside shard_map/pjit-manual).

    q,k,v: [B, H, T_local, Dh] — the local sequence shard. Rotates K/V around
    `axis_name` with ppermute; `sp` steps, each overlapping the next permute
    with the current chunk's attention math under XLA's async collectives.
    """
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, H, T, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32)
    perm = None  # built per-step below (static python loop; sp is static)

    m = jnp.full((B, H, T, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, T, 1), jnp.float32)
    acc = jnp.zeros((B, H, T, D), jnp.float32)
    k_cur, v_cur = k, v
    n = q.shape[2]

    # `sp` is a traced value only under pjit-manual; under shard_map over a
    # concrete mesh axis it is static. We require static (mesh known).
    sp_static = int(sp) if not isinstance(sp, jax.core.Tracer) else None
    if sp_static is None:
        raise ValueError("ring_attention_local requires a concrete mesh axis")
    perm = ring_perm(sp_static)  # canonical collective-layer ring hop

    for step in range(sp_static):
        src = (idx - step) % sp_static          # owner of the chunk we hold
        m, l, acc = _chunk_update(
            qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32),
            m, l, acc, scale, q_off=idx * n, k_off=src * n, causal=causal)
        if step != sp_static - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
    l = jnp.maximum(l, 1e-30)
    return (acc / l).astype(q.dtype)


def ulysses_attention_local(q, k, v, axis_name: str, causal: bool = True,
                            scale: Optional[float] = None):
    """Ulysses: all-to-all heads<->seq, full local attention, all-to-all back.

    q,k,v: [B, H, T_local, Dh]; requires H % sp == 0.
    """
    sp = lax.psum(1, axis_name)
    H = q.shape[1]
    # tiled all_to_all: [B,H,Tl,D] -> [B,H/sp,T_global,D]
    qg = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    from ray_tpu.ops.flash_attention import mha_reference

    out = mha_reference(qg, kg, vg, causal=causal, scale=scale)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def _wrap_shard_map(local_fn, q, k, v, mesh, axis, causal, scale):
    spec = logical_to_spec("batch", "heads", "seq", None)
    sp = mesh.shape.get(axis, 1)
    if not isinstance(k, jax.core.Tracer):
        # eager entry: account the cluster wire bytes; in-jit callers are
        # covered by collective spans
        kb = getattr(k, "nbytes", 0)
        vb = getattr(v, "nbytes", 0)
        qb = getattr(q, "nbytes", 0)
        if local_fn is ring_attention_local:
            # K and V each rotate sp-1 hops around the ring
            op, nbytes = "ring_attention.ppermute", (sp - 1) * (kb + vb)
        else:
            # four tiled all_to_alls (q/k/v in, output back — output is
            # q-shaped), each moving (sp-1)/sp of its operand off-device
            op = "ulysses.all_to_all"
            nbytes = (sp - 1) * (2 * qb + kb + vb) // max(sp, 1)
        account_collective(op, nbytes, str(getattr(k, "dtype", "unknown")),
                           hop="intra")
    fn = functools.partial(local_fn, axis_name=axis, causal=causal, scale=scale)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ring_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                   axis: str = "sp", mesh=None):
    """Array-level ring attention: shards q,k,v over the mesh's `sp` axis.

    Falls back to dense reference attention when no mesh/sp axis is active.
    """
    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        from ray_tpu.ops.flash_attention import mha_reference

        return mha_reference(q, k, v, causal=causal, scale=scale)
    return _wrap_shard_map(ring_attention_local, q, k, v, mesh, axis, causal,
                           scale)


def ulysses_attention(q, k, v, causal: bool = True,
                      scale: Optional[float] = None, axis: str = "sp",
                      mesh=None):
    """Array-level Ulysses attention over the mesh's `sp` axis."""
    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        from ray_tpu.ops.flash_attention import mha_reference

        return mha_reference(q, k, v, causal=causal, scale=scale)
    return _wrap_shard_map(ulysses_attention_local, q, k, v, mesh, axis,
                           causal, scale)
