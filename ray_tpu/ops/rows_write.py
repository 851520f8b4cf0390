"""One token's row written into a cache of keys or values held with the
positions on the lanes, a Pallas kernel on the TPU: a layer (granite, Kimi,
Solar, Nemotron) or every layer (GPT-2's decode step: `_write_every`) a call.

The leaf is `[layers, slots, G, d, T]`: a key-value head's d lanes on the
sublanes and the T positions on the lanes, which is how the TPU's compiler
lays a `[.., T, 64]` cache out anyway (`models/gpt2.py`, `_WRITE_WINDOW`).
A decode step writes one position a slot. In plain XLA that is a window of
128 positions a slot read, blended and written back, an operation a slot a
leaf a layer (384 a step at 48 slots, a tenth of the decode program's time:
PERF.md, PR 38); here a call's grid steps take a slot's tile of 128
positions each, picked by the position, and write it where they read it:
the leaf is aliased to the output. A slot that is not `on` gets its tile
back bit for bit.

A head of 128 lanes is held the other way, `[layers, slots, G, T, d]`, a
position a row of the head's lanes (`models/lm.py`, "grouped-head attention",
has why): a slot's tile of 128 positions is then [G, 128, d] and one of its
rows is written. Which way round a leaf lies is read off its shape against
`val`'s d (`positions_last`). (One scatter for all slots instead made the
compiler re-lay both leaves round every step, T before G: 2.1 GB each.)

A ring leaf (`ring=True`: `[layers, slots, G, W, d]`, the last W positions
of a sliding-window layer: `models/lm.py`, "a sliding window's rows") is a
leaf of rows whose every slot is W positions long and takes position p at
row p mod W. W may equal d, so a ring says that it is one. A ring whose d is
not W may hold the positions on the lanes like any leaf, `[.., d, W]`
(MiMo's keys of 192 lanes: `[.., W, 192]` in bf16 is tiled to 256 lanes, a
third more bytes), and that is read off its shape (`ring_positions_last`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.slot_state import use_kernel

TILE = 128                   # positions a grid step reads and writes


def _write_plain(c, layer, val, pos, on, positions_last: bool):
    """The same in plain XLA (the CPU backend's path, and what the kernel is
    tested against): the whole layer blended."""
    old = lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)  # [B,G,d,T]
    axis = 3 if positions_last else 2
    hit = (jnp.arange(old.shape[axis])[None, :] == pos[:, None]) & on[:, None]
    hit = jnp.expand_dims(hit, (1, 2) if positions_last else (1, 3))
    new = jnp.where(hit, jnp.expand_dims(val, axis), old)
    return lax.dynamic_update_index_in_dim(c, new, layer, 0)


def _kernel(layer_ref, tile_ref, lane_ref, c_ref, val_ref, out_ref, *,
            axis: int):
    del layer_ref, tile_ref
    slot = pl.program_id(0)
    old = c_ref[0, 0]                             # [G, d, TILE] | [G, TILE, d]
    at = lax.broadcasted_iota(jnp.int32, old.shape, axis)
    # a slot that is not on has lane -1: nothing is picked
    out_ref[0, 0] = jnp.where(at == lane_ref[slot],
                              jnp.broadcast_to(val_ref[0], old.shape), old)


def _write_kernel(c, layer, val, pos, on, interpret: bool,
                  positions_last: bool):
    L, B, G = c.shape[:3]
    d, T = c.shape[3:] if positions_last else c.shape[3:][::-1]
    assert T % TILE == 0, T

    def tile(slot, layer, tiles, lanes):
        if positions_last:
            return layer[0], slot, 0, 0, tiles[slot]
        return layer[0], slot, 0, tiles[slot], 0

    def own(slot, layer, tiles, lanes):
        return slot, 0, 0, 0

    window = (1, 1, G, d, TILE) if positions_last else (1, 1, G, TILE, d)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec(window, tile),
                  pl.BlockSpec((1, G, d, 1) if positions_last
                               else (1, G, 1, d), own)],
        out_specs=pl.BlockSpec(window, tile))
    pos = jnp.clip(pos, 0, T - 1)
    return pl.pallas_call(
        functools.partial(_kernel, axis=2 if positions_last else 1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        # operands count the three prefetched scalars: the leaf is written
        # where it is read
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="rows_write", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos // TILE,
      jnp.where(on, pos % TILE, -1), c,
      jnp.expand_dims(val, 3 if positions_last else 2))


# layers a grid step of `_write_every` takes: two read 0.971 ms at GPT-2 XL's
# shape where one reads 1.025 and four 0.970 (`benchmarks/
# cache_write_windows.py`), in 3.3 MB of VMEM
DEPTH = 2


def _kernel_every(tile_ref, lane_ref, c_ref, val_ref, out_ref):
    del tile_ref
    depth, _, G, d, _ = c_ref.shape
    take = lax.broadcasted_iota(jnp.int32, (d, TILE), 1) == lane_ref[
        pl.program_id(1)]
    for l in range(depth):
        val = val_ref[l, 0]                                  # [d, G]
        for g in range(G):
            out_ref[l, 0, g] = jnp.where(
                take, jnp.broadcast_to(val[:, g:g + 1], (d, TILE)),
                c_ref[l, 0, g])


def _write_every(c, val, pos, on, interpret: bool, depth: int = DEPTH):
    """Every layer's row, val [L, B, G, d], into c [L, B, G, d, T] in one
    call: a grid step takes a slot's tile of `depth` layers (the largest
    divisor of L that is no more). The rows come in as [L, B, d, G], a head
    a lane: a grid step's [G, d, 1] is a tile of its own a head in HBM, as
    many bytes as the tile it goes into (157 MB a leaf at GPT-2 XL's shape,
    written by a copy and read back: 1.89 ms a step's write where this form
    takes 0.97, the pace of an in-place fusion over the same bytes)."""
    L, B, G, d, T = c.shape
    assert T % TILE == 0, T
    depth = max(n for n in range(1, depth + 1) if L % n == 0)

    def tile(layers, slot, tiles, lanes):
        return layers, slot, 0, 0, tiles[slot]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(L // depth, B),
        in_specs=[pl.BlockSpec((depth, 1, G, d, TILE), tile),
                  pl.BlockSpec((depth, 1, d, G),
                               lambda layers, slot, *_: (layers, slot, 0, 0))],
        out_specs=pl.BlockSpec((depth, 1, G, d, TILE), tile))
    pos = jnp.clip(pos, 0, T - 1)
    return pl.pallas_call(
        _kernel_every, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        # operands count the two prefetched scalars
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="rows_write", interpret=interpret,
    )(pos // TILE, jnp.where(on, pos % TILE, -1), c, jnp.swapaxes(val, 2, 3))


def positions_last(rows_shape, d: int) -> bool:
    """Whether rows [..., d, T] (True) or [..., T, d] hold a head of d
    lanes: the two cannot be told apart where T is d."""
    assert rows_shape[-1] != rows_shape[-2], rows_shape
    assert d in rows_shape[-2:], (rows_shape, d)
    return rows_shape[-2] == d


def ring_positions_last(ring_shape, d: int) -> bool:
    """`positions_last` for a ring: a square ring [.., W, d = W] is rows."""
    return (ring_shape[-1] != ring_shape[-2]
            and positions_last(ring_shape, d))


def leaves_lie(k_shape, v_shape, d: int, ring: bool = False) -> tuple:
    """(whether the keys' leaf holds the positions on the lanes, whether the
    values' does) for a q of d lanes. The values lie as the keys do where
    the two leaves are alike; a values' leaf of another shape ([.., T, n]
    beside keys [.., d, T]: MiMo's keys of 192 lanes and values of 128) and
    a ring's values are rows."""
    keys = ring_positions_last(k_shape, d) if ring else positions_last(
        k_shape, d)
    return keys, keys and not ring and tuple(v_shape) == tuple(k_shape)


def rows_write(c: jax.Array, layer, val, pos, on, *, ring: bool = False,
               kernel: bool | None = None, interpret: bool = False):
    """Layer `layer` of the leaf c [L, B, G, d, T] (or [L, B, G, T, d])
    takes val [B, G, d] at position pos[b] of every slot that is `on` [B];
    nothing else changes. With `layer` None every layer of c [L, B, G, d, T]
    takes its own row, val [L, B, G, d], in one call. With `ring` c is rings
    [L, B, G, W, d] (or, d not W, [L, B, G, d, W]) and the row is pos[b] mod
    W.
    On the TPU (or with `interpret`, or `kernel=True`) through the Pallas
    kernel, which writes the leaf in place; elsewhere through plain XLA."""
    if ring:
        last = ring_positions_last(c.shape, val.shape[-1])
        pos = pos % c.shape[4 if last else 3]
    else:
        last = positions_last(c.shape, val.shape[-1])
    if layer is None:
        assert last, c.shape
        if use_kernel(kernel, interpret):
            return _write_every(c, val, pos, on, interpret)
        hit = (jnp.arange(c.shape[-1]) == pos[:, None]) & on[:, None]
        return jnp.where(hit[:, None, None], val[..., None], c)
    if use_kernel(kernel, interpret):
        return _write_kernel(c, layer, val, pos, on, interpret, last)
    return _write_plain(c, layer, val, pos, on, last)
