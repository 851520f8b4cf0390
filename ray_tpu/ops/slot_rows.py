"""One token a slot against its own rows, a block of positions at a time and
only as far as the slot's position: the grid, the plan and the fold that
`ops/mla_attend.py`, `ops/gqa_attend.py` and `ops/dsa_attend.py` stand on.

A cache leaf holds every slot's rows of every layer. A decode step's one
token a slot attends over its own rows 0 .. pos: `attend` runs a kernel's
body on the grid (slot, block of positions), handing it a block of the
slot's rows of the layer worked on where it lies in the leaf (nothing
slices or copies a layer), and folds what the body yields, head by head,
into a running maximum, sum and accumulator in float32 (the online
softmax); one division when the slot ends. A block past a slot's position
computes nothing and moves nothing: its index is clamped at the slot's last
needed block, which the pipeline finds already in VMEM; a slot that is not
live is given the index the slot before it ended on, and reads nothing at
all (`plan`).

A ring leaf (the last W positions of a sliding-window layer, position p at
row p mod W: `ops/gqa_attend.py`) is a leaf whose T is W, one block: `plan`
holds a position past the leaf at its last row, W - 1, and the body's mask
`t <= pos` is then the ring's mask by age, every row live once the ring is
full and rows 0 .. pos before.

What is a kernel's own is its `Kernel`: the operands, where the positions
lie in each leaf, and the body of one block: how a block's scores are made
and which values they weigh.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.slot_state import use_kernel

# Positions a grid step takes of a slot's rows, at most. The trade: a grid
# step costs its own time whether the slot's position is reached or not
# (0.14 us one that does nothing, ~0.35 one that works; slots x T / block of
# them a call) against the half block a slot reads past its position. Each
# kernel's file has the table that measured it for its leaves; all three
# came to 1,024
BLOCK = 1024
LANES = 128
# two buffers of a block of every leaf, the block's scores and their
# probabilities: 2.7 MB (mla), 4 MB (dsa) and 8 MB (gqa) at 1,024 positions.
# (At 64 MB the compiler carried the whole `conv` leaf of Solar's decode
# program through VMEM and back in every KDA layer:
# `tests/test_tpu_compile.py`)
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
MASKED = -1e30


def block_of(T: int) -> int:
    """Positions a grid step takes, `BLOCK` at most: all T where they fit
    one block, else the longest stretch of whole lane tiles within a block
    that divides T, else `BLOCK` itself with the last block ragged."""
    most = min(T, BLOCK)
    whole = [n for n in range(LANES, most + 1, LANES) if T % n == 0]
    return most if most == T or not whole else whole[-1]


def plan(pos, live, T: int, block: int):
    """What the index maps and the kernel read a slot: (the slot whose rows
    a grid step takes, the first and the last block it may take, the
    position or -1 for a slot that is not live). A live slot takes its own
    blocks 0 .. pos // block; one that is not takes the block the last live
    slot before it ended on, so that its grid steps move nothing."""
    B = pos.shape[0]
    live = live.astype(bool)
    pos = jnp.where(live, jnp.clip(pos, 0, T - 1), -1).astype(jnp.int32)
    before = lax.cummax(jnp.where(live, jnp.arange(B, dtype=jnp.int32), -1))
    src = jnp.maximum(before, 0)
    last = jnp.maximum(pos, 0)[src] // block
    return src, jnp.where(live, 0, last), last, pos


def read_positions(pos, live, T: int, *, kernel: bool | None = None,
                   interpret: bool = False):
    """The positions whose rows one call of `attend` reads, summed over the
    live slots (uint32): all T a slot plain, its position rounded up to a
    block here."""
    live = live.astype(bool)
    each = T
    if use_kernel(kernel, interpret):
        n = block_of(T)
        each = jnp.minimum((jnp.clip(pos, 0, T - 1) // n + 1) * n, T)
    return jnp.sum(jnp.where(live, each, 0)).astype(jnp.uint32)


class Leaf(NamedTuple):
    """A cache leaf `[layers, slots, ...]` (or, not `layered`, an array a
    slot `[slots, ...]`) with the positions along `positions`: rows
    `[.., block, n]`, lane-major `[.., n, block]`, a mask `[B, 1, block]`,
    a head axis before the positions. A grid step takes a block of the
    positions and every other axis whole."""
    array: jax.Array
    positions: int
    layered: bool = True


class Block(NamedTuple):
    """What a body is told of the block it works on."""
    j: jax.Array            # the block's number
    pos: jax.Array          # the slot's position
    block: int
    T: int

    def at(self, shape, axis: int):
        """The positions of a block's entries, along `axis` of `shape`."""
        return self.j * self.block + lax.broadcasted_iota(
            jnp.int32, shape, axis)

    def held(self, shape, axis: int):
        """Which entries of the block the leaf holds; None where every
        block lies within it."""
        return self.at(shape, axis) < self.T if self.T % self.block else None


def zero_past_end(values, held):
    """The last block hangs over the leaf's end: what lies there is whatever
    VMEM held, and 0 x NaN is no 0."""
    if held is None:
        return values
    return jnp.where(held, values, jnp.zeros_like(values))


def weigh(p, values):
    """The probabilities p [Q, block], rounded to the rows' dtype, against
    the values [block, n] they weigh."""
    return jnp.dot(p.astype(values.dtype), values,
                   preferred_element_type=jnp.float32)


class Kernel(NamedTuple):
    """What is a kernel's own. `inputs` in the order the body takes their
    refs: an array `[slots, ...]` is the slot's own block, a `Leaf` a block
    of its positions. `acc` is the accumulator's shape `[.., Q, n]`, the
    result's a slot. `body(blk, *refs)` yields, for each head or group of
    heads it holds, `(at, scores, values)`: the index of the head's part of
    the accumulators (`...`: all), the block's scores `[.., Q, block]`
    masked past the slot's position, and the values
    `weigh(probabilities, values)` takes. `start`, where the softmax has a
    sink (a learned score a head that takes probability and weighs no
    value: MiMo's sliding layers): float32 `[.., Q]`, every slot's running
    maximum before its first block, beside a sum of 1 and an empty
    accumulator; without one the fold starts empty."""
    name: str
    body: Callable
    inputs: tuple
    acc: tuple
    weigh: Callable = weigh
    start: jax.Array | None = None


def _kernel(layer_ref, src_ref, first_ref, last_ref, pos_ref, *refs,
            body: Callable, weigh: Callable, block: int, T: int,
            sink: bool):
    """One block of one slot's rows of one layer."""
    del layer_ref, src_ref, first_ref, last_ref
    *refs, o_ref, m_ref, l_ref, acc_ref = refs
    if sink:
        *refs, start_ref = refs
    slot, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot]                               # -1: the slot is dead

    @pl.when(j == 0)
    def _():
        if sink:
            m_ref[...] = start_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, MASKED)
            l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block <= pos)
    def _():
        for at, s, values in body(Block(j, pos, block, T), *refs):
            m_old = m_ref[at]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            shrink = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_ref[at] = shrink * l_ref[at] + jnp.sum(p, axis=-1,
                                                     keepdims=True)
            acc_ref[at] = shrink * acc_ref[at] + weigh(p, values)
            m_ref[at] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        total = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(total == 0.0, 1.0, total)


def _spec(x, block: int) -> pl.BlockSpec:
    """A slot's own block of an array `[slots, ...]`; of a `Leaf`, a block
    of the positions of slot `src[slot]`, clamped between `first[slot]` and
    `last[slot]`."""
    if not isinstance(x, Leaf):
        rest = (0,) * (x.ndim - 1)
        return pl.BlockSpec((1,) + x.shape[1:],
                            lambda slot, j, *_: (slot,) + rest)
    shape, at, slot_axis = x.array.shape, x.positions, int(x.layered)

    def index(slot, j, layer, src, first, last, pos):
        where = [0] * len(shape)
        if slot_axis:
            where[0] = layer[0]
        where[slot_axis] = src[slot]
        where[at] = jnp.clip(j, first[slot], last[slot])
        return tuple(where)

    return pl.BlockSpec(tuple(
        1 if i <= slot_axis else block if i == at else n
        for i, n in enumerate(shape)), index)


def attend(kernel: Kernel, layer, pos, live, *, block: int | None = None,
           interpret: bool = False):
    """Every slot's one token against its own rows 0 .. pos[b] of layer
    `layer` -> `[B, *kernel.acc]` float32, garbage for a slot that is not
    live. `block`: the positions a grid step takes where `block_of(T)` is
    not to decide (the tools that measured `BLOCK`)."""
    T = next(x.array.shape[x.positions] for x in kernel.inputs
             if isinstance(x, Leaf))
    n = block or block_of(T)
    out = jax.ShapeDtypeStruct((pos.shape[0],) + kernel.acc, jnp.float32)
    sums_shape = kernel.acc[:-1] + (1,)
    sums = pltpu.VMEM(sums_shape, jnp.float32)
    start = () if kernel.start is None else (
        kernel.start.astype(jnp.float32).reshape(sums_shape),)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(out.shape[0], -(-T // n)),
        # the start is the same for every slot and block: fetched once
        in_specs=[_spec(x, n) for x in kernel.inputs] + [
            pl.BlockSpec(sums_shape, lambda *_: (0,) * len(sums_shape))
        ] * len(start),
        out_specs=_spec(out, n),
        scratch_shapes=[sums, sums, pltpu.VMEM(kernel.acc, jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, body=kernel.body, weigh=kernel.weigh,
                          block=n, T=T, sink=bool(start)),
        grid_spec=grid_spec, out_shape=out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=kernel.name, interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *plan(pos, live, T, n),
      *(x.array if isinstance(x, Leaf) else x for x in kernel.inputs),
      *start)
