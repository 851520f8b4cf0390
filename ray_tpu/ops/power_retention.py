"""Power retention of degree 2: the expansion `phi`, and the one-token
update-and-read-out of the state, a Pallas kernel on the TPU.

A key-value head keeps, in float32, `S = sum_j G_j phi(k_j) v_j^T` and the
normaliser `z = sum_j G_j phi(k_j)`, where `phi(a) . phi(b) = (a . b)^2`
and `G_j` is the product of the gates since token j. One token does

    S <- g S + phi(k) v^T      z <- g z + phi(k)
    num^r = phi(q^r)^T S       den^r = phi(q^r) . z       (r: the query heads
                                                           that share the head)

which reads and writes the whole state once and is bound by that.

The layout. `phi(a)` has d (d + 1) / 2 entries, `a_i a_j` for i <= j with
the off-diagonal ones times sqrt 2. They are laid out as d/2 + 1 rows of d
lanes: row s holds `c_s a_i a_{(i+s) mod d}` at lane i (c_0 = 1, else sqrt 2),
and the last row, s = d/2, only its first d/2 lanes (the pair {i, i + d/2}
once); its other lanes are zero and stay zero in the state. So a row is `a`
times a rotation of `a`, and the expanded width `expanded_width(d)` is a
whole number of lane tiles: 8,320 for d = 128, of which 8,256 are content
(0.78% of padding). The state is held transposed, `S^T` [d_v, width]: the
expanded axis on the lanes, so that `phi(k)` and `phi(q)` are rows, the
rank-one update is a column times a row, and the read-out a sum over lanes
that is kept as d lane-partials until the state has gone by.

`retention_update` takes the whole leaves [layers, slots, ...] and the layer
to work on; the kernel aliases the state to its output: under a jit that
donates the cache nothing of the state's size is held beside it. A slot that is not
active is copied through, bit for bit.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops import slot_state

# a grid step takes one slot's one key-value head whole, 4.26 MB in one
# stretch of HBM: its four buffers (two in, two out) are 17 MiB of VMEM.
# On a v5e a layer's call at 16 slots takes 1.6-1.8 ms in the decode
# program's trace (the state read and written at 74% of the HBM's peak, the
# expansions and the division in the time) and 2.06 ms dispatched alone,
# against 2.97 ms for the plain form; blocks of 640 and of 1,664 of the
# 8,320 lanes took longer, and a kernel that only copies its block is no
# faster: the pass over HBM is the cost (PERF.md, PR 33)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
STRIP = 8                    # sublanes of a float32 tile


def expanded_width(head_dim: int) -> int:
    return (head_dim // 2 + 1) * head_dim


def content_width(head_dim: int) -> int:
    return head_dim * (head_dim + 1) // 2


def _layout(d: int):
    """(the [d, W] selection of lane (i + s) mod d for every entry (s, i) of
    the layout, as bfloat16 zeros and ones; the [W] coefficients c_s, zero
    on the padding)."""
    half = d // 2
    s, i = np.divmod(np.arange((half + 1) * d), d)
    take = np.zeros((d, (half + 1) * d), np.float32)
    take[(i + s) % d, np.arange((half + 1) * d)] = 1.0
    scale = np.where(s == 0, 1.0, math.sqrt(2.0)).astype(np.float32)
    scale[(s == half) & (i >= half)] = 0.0
    return jnp.asarray(take, jnp.bfloat16), jnp.asarray(scale)


def phi(a: jax.Array) -> jax.Array:
    """a [..., d] -> [..., expanded_width(d)] float32, in the layout above.

    The rotations `a_{(i+s) mod d}` are one product with a matrix of zeros
    and ones (65 lane rotations of a small array are 400 operations a layer
    on the TPU, and one compiled alone crashes its compiler: PERF.md, PR
    33), exact: `a` goes in as the three bfloat16 pieces that add up to it,
    each picked by a one and accumulated in float32."""
    a = a.astype(jnp.float32)
    d = a.shape[-1]
    take, scale = _layout(d)
    high = a.astype(jnp.bfloat16)
    rest = a - high.astype(jnp.float32)
    middle = rest.astype(jnp.bfloat16)
    low = (rest - middle.astype(jnp.float32)).astype(jnp.bfloat16)
    turned = jnp.dot(jnp.stack([high, middle, low]), take,
                     preferred_element_type=jnp.float32)
    turned = (turned[0] + turned[1]) + turned[2]
    return jnp.tile(a, d // 2 + 1) * turned * scale


def _normaliser(norm, layer, phi_k, phi_q, g, active):
    """z <- g z + phi(k) for layer `layer`'s normalisers and the read-outs
    phi(q^r) . z: 1/128 of the state, plain XLA on every backend."""
    z = lax.dynamic_index_in_dim(norm, layer, 0, keepdims=False)  # [B,H,W]
    z = jnp.where(active.astype(bool)[:, None, None],
                  g[:, :, None] * z + phi_k, z)
    den = jnp.einsum("bhrw,bhw->bhr", phi_q, z, precision=lax.Precision.HIGHEST)
    return lax.dynamic_update_index_in_dim(norm, z, layer, 0), den


def _update_plain(state, layer, phi_k, phi_q, v, g, active):
    """The state's part of the same arithmetic in plain XLA (the CPU
    backend's path, and what the kernel is tested against)."""
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s_new = g[:, :, None, None] * s + v[:, :, :, None] * phi_k[:, :, None, :]
    s_new = jnp.where(active.astype(bool)[:, None, None, None], s_new, s)
    num = jnp.einsum("bhrw,bhvw->bhrv", phi_q, s_new,
                     precision=lax.Precision.HIGHEST)
    return lax.dynamic_update_index_in_dim(state, s_new, layer, 0), num


def _update_tile(s_ref, cols_ref, rows_ref, so_ref, num_ref, *, ratio: int,
                 precision):
    """One slot's one key-value head: its whole S^T, one stretch of HBM."""
    dv, lanes = s_ref.shape[-2:]
    rows = rows_ref[0, 0]               # [8, lanes]: R of phi(q), then phi(k)
    pk = rows[ratio:ratio + 1, :]

    # the update on the VPU, a tile of the state at a time: the gate and the
    # value over a strip's lanes once, then one multiply-add a tile, in and
    # out of VMEM once
    def strip(i, carry):
        at = pl.ds(pl.multiple_of(i * STRIP, STRIP), STRIP)
        v_tile = jnp.broadcast_to(cols_ref[0, 0, at, 0:1], (STRIP, 128))
        g_tile = jnp.broadcast_to(cols_ref[0, 0, at, 1:2], (STRIP, 128))
        for j in range(lanes // 128):
            tile = slice(j * 128, (j + 1) * 128)
            so_ref[0, 0, 0, at, tile] = (
                g_tile * s_ref[0, 0, 0, at, tile] + v_tile * pk[:, tile])
        return carry

    lax.fori_loop(0, dv // STRIP, strip, 0)
    # the read-out on the MXU: the rows against the updated state,
    # contracted over the lanes of both (phi(k)'s row rides along)
    num_ref[0, 0] = lax.dot_general(
        rows, so_ref[0, 0, 0], (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)


# the read-out's products: float32 operands split in bfloat16 pieces by the
# compiler, six passes of the MXU (`HIGHEST`); the state's update is exact
READ_OUT_PRECISION = lax.Precision.HIGHEST
QUERY_ROWS = 8               # a float32 tile's sublanes: R < 8 queries a head


def _update_kernel(state, layer, rows, R: int, v, g, active, interpret: bool):
    L, B, H, dv, W = state.shape
    assert W % 128 == 0 and dv % STRIP == 0, (W, dv)
    assert rows.shape == (B, H, QUERY_ROWS, W) and R < QUERY_ROWS, rows.shape
    # per slot and head a column of v and one of g, over the d_v sublanes
    cols = jnp.stack([v, jnp.broadcast_to(g[:, :, None], v.shape)], axis=-1)
    state, num = slot_state.update(
        "retention_update",
        functools.partial(_update_tile, ratio=R,
                          precision=READ_OUT_PRECISION),
        state, layer, active, (cols, rows), (QUERY_ROWS, dv), grid_axes=2,
        vmem_limit_bytes=VMEM_LIMIT_BYTES, interpret=interpret)
    return state, num[:, :, :R]


def retention_update(state: jax.Array, norm: jax.Array, layer, q, k, v, g,
                     active, *, kernel: bool | None = None,
                     interpret: bool = False):
    """One token a slot through layer `layer` of the state.

    state [L, B, H, d_v, W] and norm [L, B, H, W] float32 (W the expanded
    width of d), q [B, H, R, d] (the R queries that share each head), k
    [B, H, d], v [B, H, d_v], g [B, H] (the gate, not its logarithm), active
    [B] -> (state, norm, num [B, H, R, d_v], den [B, H, R]): the read-outs
    are of the state after the update, and are garbage for a slot that is
    not active, whose state comes back bit for bit. On the TPU (or with
    `interpret`, or `kernel=True`) the state goes through the Pallas kernel,
    which writes the leaf in place; elsewhere through plain XLA. The
    normalisers, 1/128 of the state, are plain XLA everywhere."""
    B, H, R, d = q.shape
    # one tile's rows a slot and head: the R expanded queries, the expanded
    # key, zeros: expanded together, and what the kernel takes as they are
    rows = phi(jnp.concatenate(
        [q, k[:, :, None, :], jnp.zeros((B, H, QUERY_ROWS - R - 1, d),
                                        jnp.float32)], axis=2))
    phi_q, phi_k = rows[:, :, :R], rows[:, :, R]
    norm, den = _normaliser(norm, layer, phi_k, phi_q, g, active)
    if slot_state.use_kernel(kernel, interpret):
        state, num = _update_kernel(state, layer, rows, R, v, g, active,
                                    interpret)
    else:
        state, num = _update_plain(state, layer, phi_k, phi_q, v, g, active)
    return state, norm, num, den
