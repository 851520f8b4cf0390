"""One token's row written into a cache of keys or values held with the
positions on the lanes, a Pallas kernel on the TPU.

The leaf is `[layers, slots, G, d, T]`: a key-value head's d lanes on the
sublanes and the T positions on the lanes, which is how the TPU's compiler
lays a `[.., T, 64]` cache out anyway (`models/gpt2.py`, `_WRITE_WINDOW`).
A decode step writes one position a slot. In plain XLA that is a window of
128 positions a slot read, blended and written back, an operation a slot a
leaf a layer (384 a step at 48 slots, a tenth of the decode program's time:
PERF.md, PR 38); here it is one call a leaf a layer whose grid steps take a
slot's tile of 128 positions each, picked by the position, and write it
where they read it: the leaf is aliased to the output. A slot that is not
`on` gets its tile back bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128                   # positions a grid step reads and writes


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _write_plain(c, layer, val, pos, on):
    """The same in plain XLA (the CPU backend's path, and what the kernel is
    tested against): the whole layer blended."""
    old = lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)  # [B,G,d,T]
    hit = (jnp.arange(c.shape[-1])[None, :] == pos[:, None]) & on[:, None]
    new = jnp.where(hit[:, None, None, :], val[..., None], old)
    return lax.dynamic_update_index_in_dim(c, new, layer, 0)


def _kernel(layer_ref, tile_ref, lane_ref, c_ref, val_ref, out_ref):
    del layer_ref, tile_ref
    slot = pl.program_id(0)
    old = c_ref[0, 0]                                         # [G, d, TILE]
    lanes = lax.broadcasted_iota(jnp.int32, old.shape, 2)
    # a slot that is not on has lane -1: nothing is picked
    out_ref[0, 0] = jnp.where(lanes == lane_ref[slot],
                              jnp.broadcast_to(val_ref[0], old.shape), old)


def _write_kernel(c, layer, val, pos, on, interpret: bool):
    L, B, G, d, T = c.shape
    assert T % TILE == 0, T

    def tile(slot, layer, tiles, lanes):
        return layer[0], slot, 0, 0, tiles[slot]

    def own(slot, layer, tiles, lanes):
        return slot, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(B,),
        in_specs=[pl.BlockSpec((1, 1, G, d, TILE), tile),
                  pl.BlockSpec((1, G, d, 1), own)],
        out_specs=pl.BlockSpec((1, 1, G, d, TILE), tile))
    pos = jnp.clip(pos, 0, T - 1)
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(c.shape, c.dtype),
        # operands count the three prefetched scalars: the leaf is written
        # where it is read
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="rows_write", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), pos // TILE,
      jnp.where(on, pos % TILE, -1), c, val[..., None])


def rows_write(c: jax.Array, layer, val, pos, on, *,
               kernel: bool | None = None, interpret: bool = False):
    """Layer `layer` of the leaf c [L, B, G, d, T] takes val [B, G, d] at
    position pos[b] of every slot that is `on` [B]; nothing else changes.
    On the TPU (or with `interpret`, or `kernel=True`) through the Pallas
    kernel, which writes the leaf in place; elsewhere through plain XLA."""
    if kernel is None:
        kernel = interpret or _on_tpu()
    if kernel:
        return _write_kernel(c, layer, val, pos, on, interpret)
    return _write_plain(c, layer, val, pos, on)
