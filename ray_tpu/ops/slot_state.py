"""One token a slot through its own recurrent state of one layer, in place:
the pass that `ops/power_retention.py`, `ops/ssm_update.py` and
`ops/kda_update.py` stand on.

A state leaf is `[layers, slots, ...]` float32, a slot's state of a layer
one stretch of HBM. `update` runs a kernel's body once a slot (or once a
slot and head: the leading `len(grid)` axes after the layers'), handing it
the slot's state of the layer worked on where it lies in the leaf, and
aliases the leaf to its output: under a jit that donates the cache nothing
of the state's size is held beside it. A slot that is not active is copied
through, bit for bit, and its read-out is zero; the body is the active
slot's.

What is a kernel's own is the tile's arithmetic, the small operands beside
the state and its `vmem_limit_bytes`: a constant of the kernel, whose four
buffers of a slot's state (in and out, two each) it must hold.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_kernel(kernel: bool | None, interpret: bool) -> bool:
    """Whether a Pallas kernel of `ops/` runs or its plain form: on the TPU
    (or with `interpret`) unless `kernel` says."""
    return (interpret or on_tpu()) if kernel is None else kernel


class Same(NamedTuple):
    """An operand every grid step takes whole."""
    array: jax.Array


def _kernel(layer_ref, active_ref, s_ref, *refs, body: Callable):
    del layer_ref
    slot = pl.program_id(0)
    *_, so_ref, out_ref = refs

    @pl.when(active_ref[slot] == 0)
    def _():
        so_ref[...] = s_ref[...]
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(active_ref[slot] != 0)
    def _():
        body(s_ref, *refs)


def update(name: str, body: Callable, state, layer, active, operands,
           read_out, *, vmem_limit_bytes: int, grid_axes: int = 1,
           interpret: bool = False):
    """Layer `layer` of the leaf `state` `[L, *grid, ...]` through
    `body(s_ref, *operand_refs, so_ref, out_ref)`, a grid step a slot (a
    slot and head where `grid_axes` is 2) that is `active` [B] ->
    (state, read-out `[*grid, *read_out]` float32). `operands` are arrays
    `[*grid, ...]`, a grid step's own block each, or `Same`."""
    grid = state.shape[1:1 + grid_axes]

    def leaf(*at):
        return (at[grid_axes][0], *at[:grid_axes]) + (0,) * (
            state.ndim - 1 - grid_axes)

    def own(x):
        return pl.BlockSpec(
            (1,) * grid_axes + x[grid_axes:],
            lambda *at: at[:grid_axes] + (0,) * (len(x) - grid_axes))

    whole = pl.BlockSpec((1,) * (1 + grid_axes) + state.shape[1 + grid_axes:],
                         leaf)
    read_out = grid + tuple(read_out)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid,
        in_specs=[whole] + [
            pl.BlockSpec(x.array.shape, lambda *at, x=x: (0,) * x.array.ndim)
            if isinstance(x, Same) else own(x.shape) for x in operands],
        out_specs=[whole, own(read_out)])
    return pl.pallas_call(
        functools.partial(_kernel, body=body),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(read_out, jnp.float32)],
        # operands count the two prefetched scalars: the state is written
        # where it is read
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * grid_axes,
            vmem_limit_bytes=vmem_limit_bytes),
        name=name, interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), active.astype(jnp.int32),
      state, *(x.array if isinstance(x, Same) else x for x in operands))
