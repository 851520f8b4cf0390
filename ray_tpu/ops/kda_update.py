"""The one-token delta-rule update-and-read-out of a Kimi Delta Attention
layer's state, a Pallas kernel on the TPU.

A head keeps `S [N, P]` in float32 (N the key's lanes, P the value's; both
128 as published). One token does, for every head,

    S~ = Diag(a) S        u = b (v - S~^T k)        S <- S~ + k u^T
    o = S^T q = S~^T q + (k . q) u

with a decay `a` in (0, 1)^N a key channel and a scalar `b` a head. Unlike
`ops/ssm_update.py` and `ops/power_retention.py` (`S <- a S + outer`) the
rule reads the state before it writes it: `S~^T k` is a sum over all of a
head's rows, and only then is the rank-one correction known. A head's tile
is 64 KB, so it is read from HBM once into VMEM, passed over twice there
(the decay and the two read-outs `S~^T k`, `S~^T q`; then the correction)
and written once: the state's bytes in and out are what bounds the call.

The layout. The leaf is `[layers, slots, H, N, P]`: a head's tile has the
key's index on the sublanes and the value's on the lanes, so `v`, `u`, `o`
are rows and the decay, `k` and `q` are columns: a value a sublane, the same
on every lane. Spreading a column over a tile's lanes on the VPU costs a
lane shuffle a register (the first kernel did, and took 11 ms a call where
the state's bytes take 0.6: PERF.md, PR 40), so the MXU does it: the three
columns of eight heads come as one `[N, 128]` bf16 operand (16 lanes a
head: the three bf16 pieces that add up to each of a, k and q, exactly),
and one product with a constant 0/1 matrix `[128, 3 P]` that picks a head's
nine lanes gives the three tiles `[N, P]` whose every lane holds the
column, in float32: one pass of the MXU a head, no shuffle, nothing
rounded. The rows come as `rows [slots, 8, H P]` (b v, b and k . q, a
head's value over its P lanes).

The head count is the leaf's: the kernel's loop over heads, the columns'
operand (`H / 8` groups of 128 lanes) and the rows' width follow `state`'s
shape, and nothing is written for one count (32 as Kimi publishes it, 64 as
Solar does; a count that is no multiple of 8 pads its last group). `b` in
(0, 2), Solar's write strength, is arithmetic outside the kernel, which
takes `b v` and `b` as rows.

`kda_update` takes the whole leaf and the layer to work on; the kernel
aliases the state to its output, so under a jit that donates the cache
nothing of the state's size is held beside it. A slot that is not active is
copied through, bit for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops import slot_state
from ray_tpu.ops.pieces import pieces

# a grid step takes one slot's state of the layer whole, the leaf's H heads
# of 64 KB in one stretch of HBM, and holds four such buffers in VMEM (in and
# out, two each): 2 MB a slot and 8 MiB at 32 heads (Kimi), 4 MB and 16 MiB
# at 64 (Solar); the columns' operand and the rows are 0.3 MB more
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
STRIP = 8                    # sublanes of a float32 tile
LANES = 128
HEAD_LANES = 16              # lanes a head takes of the columns' operand
GROUP = LANES // HEAD_LANES  # heads an operand of 128 lanes holds
PIECES = 3                   # bf16 pieces that add up to a float32
_HIGHEST = lax.Precision.HIGHEST


def _update_plain(state, layer, a, k, q, v, b, active):
    """The same arithmetic in plain XLA (the CPU backend's path, and what
    the kernel is tested against)."""
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)  # [B,H,N,P]
    decayed = a[..., None] * s
    seen = jnp.einsum("bhnp,bhn->bhp", decayed, k, precision=_HIGHEST)
    u = b[..., None] * (v - seen)
    s_new = decayed + k[..., None] * u[:, :, None, :]
    o = jnp.einsum("bhnp,bhn->bhp", s_new, q, precision=_HIGHEST)
    s_new = jnp.where(active.astype(bool)[:, None, None, None], s_new, s)
    return lax.dynamic_update_index_in_dim(state, s_new, layer, 0), o


def _update_tile(s_ref, cols_ref, pick_ref, rows_ref, so_ref, o_ref):
    """One slot's state of one layer: H tiles of [N, P]."""
    heads, p = s_ref.shape[2], s_ref.shape[4]
    for h in range(heads):
        lanes = slice(h * p, (h + 1) * p)
        group = slice(h // GROUP * LANES, (h // GROUP + 1) * LANES)
        # [N, 3 P]: the decay, k and q of head h, each over P lanes
        spread = jnp.dot(cols_ref[0, :, group], pick_ref[h % GROUP],
                         preferred_element_type=jnp.float32)
        a, k, q = (spread[:, i * p:(i + 1) * p] for i in range(3))
        decayed = a * s_ref[0, 0, h]
        for_k = jnp.sum(decayed * k, axis=0, keepdims=True)        # [1, P]
        for_q = jnp.sum(decayed * q, axis=0, keepdims=True)
        bv, b, kq = (rows_ref[0, r:r + 1, lanes] for r in range(3))
        u = bv - b * for_k
        o_ref[0, :, lanes] = for_q + kq * u
        so_ref[0, 0, h] = decayed + k * u


@functools.lru_cache(maxsize=None)
def _pick(p: int) -> np.ndarray:
    """[GROUP, 128, 3 P] 0/1: entry j sums, for each of head j's three
    vectors, its three pieces' lanes into that vector's P lanes."""
    pick = np.zeros((GROUP, LANES, 3 * p), np.float32)
    for j in range(GROUP):
        for vector in range(3):
            for piece in range(PIECES):
                pick[j, j * HEAD_LANES + vector * PIECES + piece,
                     vector * p:(vector + 1) * p] = 1.0
    return pick


def _update_kernel(state, layer, a, k, q, v, b, active, interpret: bool):
    L, B, H, N, P = state.shape
    assert N % STRIP == 0 and P % LANES == 0, (N, P)
    groups = -(-H // GROUP)
    # [B, H, N, 3 vectors x 3 pieces] -> [B, N, H x 16 lanes]
    cols = pieces(jnp.stack([a, k, q], axis=-1), jnp.bfloat16, PIECES,
                  axis=-1).reshape(B, H, N, 3 * PIECES)
    cols = jnp.pad(cols, ((0, 0), (0, groups * GROUP - H), (0, 0),
                          (0, HEAD_LANES - 3 * PIECES)))
    cols = jnp.transpose(cols, (0, 2, 1, 3)).reshape(B, N, groups * LANES)
    kq = jnp.sum(k * q, axis=-1, keepdims=True)                    # [B,H,1]
    rows = jnp.stack([b[..., None] * v, jnp.broadcast_to(b[..., None], v.shape),
                      jnp.broadcast_to(kq, v.shape)], axis=1).reshape(
        B, 3, H * P)
    rows = jnp.pad(rows, ((0, 0), (0, STRIP - 3), (0, 0)))

    state, o = slot_state.update(
        "kda_update", _update_tile, state, layer, active,
        (cols, slot_state.Same(jnp.asarray(_pick(P), jnp.bfloat16)), rows),
        (1, H * P), vmem_limit_bytes=VMEM_LIMIT_BYTES, interpret=interpret)
    return state, o.reshape(B, H, P)


def kda_update(state: jax.Array, layer, a, k, q, v, b, active, *,
               kernel: bool | None = None, interpret: bool = False):
    """One token a slot through layer `layer` of the state.

    state [L, B, H, N, P] float32, the decay a, the key k and the query q
    [B, H, N], the value v [B, H, P], b [B, H], active [B] -> (state,
    o [B, H, P]): the read-out is of the state after the update and is
    garbage for a slot that is not active, whose state comes back bit for
    bit. On the TPU (or with `interpret`, or `kernel=True`) the state goes
    through the Pallas kernel, which writes the leaf in place; elsewhere
    through plain XLA."""
    if slot_state.use_kernel(kernel, interpret):
        return _update_kernel(state, layer, a, k, q, v, b, active, interpret)
    return _update_plain(state, layer, a, k, q, v, b, active)
