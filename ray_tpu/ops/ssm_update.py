"""The one-token update-and-read-out of a Mamba-2 layer's SSM state, a Pallas
kernel on the TPU.

A head keeps `S [P, N]` in float32 (P the head's lanes, N the state size).
One token does, for every head h,

    S_h <- exp(dt_h A_h) S_h + dt_h x_h B^T        y_h = S_h C

which reads and writes the whole state once and is bound by that. B and C
[N] belong to a group of heads: one group shares them among all the heads
(granite), G groups give each run of H / G heads its own (Nemotron-H: 8
groups of 16 heads).

The layout. A slot's state of one layer is held as `[N, H P]`: the state's
index on the sublanes, every head's lanes side by side on the lanes (4,096
for 64 heads of 64, a whole number of lane tiles; `[H, P, N]`, the
recurrence's own order, would put N = 128 on the lanes and make the
read-out `S C` a reduction along them, one shuffle tree a tile). So the
decay `exp(dt A)` and the input `dt x` are rows (a value a lane, spread
over a head's P lanes by the caller), B and C are columns, the rank-one
update is a column times a row, and the read-out a sum over sublanes: a
multiply-add a tile on the VPU, kept as eight sublane-partials until a
stretch of lanes has gone by. A group's heads lie side by side, so a group
is a stretch of H P / G lanes and its B and C the columns of the passes over
that stretch (16 heads of 64 are 1,024 lanes: a column a pass of
`LANE_TILE`).

`ssm_update` takes the whole leaf [layers, slots, N, H P] and the layer to
work on; the kernel aliases the state to its output: under a jit that
donates the cache nothing of the state's size is held beside it (Brumby's
lesson, PERF.md PR 33). A slot that is not active is copied through, bit
for bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops import slot_state

# a grid step takes one slot's state of the layer whole, in one stretch of
# HBM: 2 MB at granite's published sizes, its four buffers 8 MiB of VMEM;
# 4.19 MB at Nemotron-H's 128 heads, 16.8 MiB
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
STRIP = 8                    # sublanes of a float32 tile
LANE_TILE = 1024             # lanes a pass of the strips' loop covers


def _over_lanes(cols, lanes: int):
    """b or c by group [B, G, N] -> [B, N, lanes]: a group's column over the
    stretch of lanes its heads have."""
    return jnp.repeat(jnp.swapaxes(cols, 1, 2), lanes // cols.shape[1],
                      axis=-1)


def _update_plain(state, layer, decay, dtx, b, c, active):
    """The same arithmetic in plain XLA (the CPU backend's path, and what
    the kernel is tested against)."""
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)  # [B,N,F]
    b, c = (_over_lanes(t, s.shape[-1]) for t in (b, c))
    s_new = decay[:, None, :] * s + b * dtx[:, None, :]
    s_new = jnp.where(active.astype(bool)[:, None, None], s_new, s)
    y = jnp.sum(s_new * c, axis=1)
    return lax.dynamic_update_index_in_dim(state, s_new, layer, 0), y


def _update_tile(s_ref, decay_ref, dtx_ref, cols_ref, so_ref, y_ref, *,
                 tile: int, groups: int):
    """One slot's state of one layer: [N, lanes], one stretch of HBM."""
    n_state, lanes = s_ref.shape[-2:]
    for j in range(lanes // tile):
        at_lanes = slice(j * tile, (j + 1) * tile)
        g = 2 * (j * tile * groups // lanes)        # the pass's group's b
        decay = decay_ref[0, :, at_lanes]                       # [1, tile]
        dtx = dtx_ref[0, :, at_lanes]

        # a strip of eight of the state's rows at a time: the update and the
        # read-out's partial sums, in and out of VMEM once
        def strip(i, acc, at_lanes=at_lanes, decay=decay, dtx=dtx, g=g):
            at = pl.ds(pl.multiple_of(i * STRIP, STRIP), STRIP)
            b_col = jnp.broadcast_to(cols_ref[0, at, g:g + 1], (STRIP, tile))
            c_col = jnp.broadcast_to(cols_ref[0, at, g + 1:g + 2],
                                     (STRIP, tile))
            new = decay * s_ref[0, 0, at, at_lanes] + b_col * dtx
            so_ref[0, 0, at, at_lanes] = new
            return acc + new * c_col

        partial = lax.fori_loop(0, n_state // STRIP, strip,
                                jnp.zeros((STRIP, tile), jnp.float32))
        y_ref[0, :, at_lanes] = partial.sum(axis=0, keepdims=True)


def _update_kernel(state, layer, decay, dtx, b, c, active, interpret: bool):
    L, B, N, F = state.shape
    G = b.shape[1]
    # a pass of the strips' loop lies within one group's lanes
    tile = min(LANE_TILE, F // G)
    assert F % (G * tile) == 0 and tile % 128 == 0 and N % STRIP == 0, \
        (N, F, G)
    # group g's b and c are columns 2 g and 2 g + 1
    cols = jnp.swapaxes(jnp.stack([b, c], axis=-1), 1, 2).reshape(
        B, N, 2 * G)
    state, y = slot_state.update(
        "ssm_update", functools.partial(_update_tile, tile=tile, groups=G),
        state, layer, active, (decay[:, None], dtx[:, None], cols), (1, F),
        vmem_limit_bytes=VMEM_LIMIT_BYTES, interpret=interpret)
    return state, y[:, 0]


def ssm_update(state: jax.Array, layer, decay, dtx, b, c, active, *,
               kernel: bool | None = None, interpret: bool = False):
    """One token a slot through layer `layer` of the state.

    state [L, B, N, F] float32 (F = heads x lanes a head), decay and dtx
    [B, F] (`exp(dt A)` and `dt x`, a head's value over its lanes), b and c
    [B, N] (one group) or [B, G, N] (group g the heads whose lanes are
    g F / G ..), active [B] -> (state, y [B, F]): the read-out is of the state
    after the update and is garbage for a slot that is not active, whose
    state comes back bit for bit. On the TPU (or with `interpret`, or
    `kernel=True`) the state goes through the Pallas kernel, which writes
    the leaf in place; elsewhere through plain XLA."""
    if b.ndim == 2:
        b, c = b[:, None], c[:, None]
    if slot_state.use_kernel(kernel, interpret):
        return _update_kernel(state, layer, decay, dtx, b, c, active,
                              interpret)
    return _update_plain(state, layer, decay, dtx, b, c, active)
