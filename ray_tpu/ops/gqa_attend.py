"""One token's grouped-head attention over a slot's rows by head (decode),
a Pallas kernel on the TPU: `ops/mla_attend.py`'s sibling.

The cache of a softmax layer whose heads have 128 lanes holds a token's
keys and values by the G key-value heads, two leaves `[layers, slots, G, T,
d]`, a position a row (`models/lm.py`, "grouped-head attention over rows by
head"). A slot's one token brings R queries a key-value head:

    s_t = q . k_t * scale,  t <= pos;   o = sum_t softmax(s)_t v_t   [G, R, d]

In plain XLA (`lm.gqa_attend`) that is float32 scores `[B, G, 2 R, T]` of a
float32 query's two pieces against all T positions of every slot whatever
its position, written to HBM, read back by the softmax, and the
probabilities written again as two bf16 pieces (41% of the chip's roofline
at 40 slots x 25,600 positions, half of Solar's decode step: PERF.md PR
52). Here a slot's rows go through VMEM once, a block of positions of all G
heads of both leaves at a time and only as far as the slot's own position:
the block's scores `[G, 2 R, block]` whose halves add up, the running
maximum and sum `[G, R, 1]` and the accumulator `[G, R, d]` in float32, the
block's probabilities as two pieces against the v block, one division when
the slot ends. The precision is the plain form's, piece for piece: a q that
is not of the rows' dtype and its probabilities meet the rows as the two
pieces that add up to them (`ops/pieces.py`'s arithmetic, stacked on the
rows as `lm._row_pieces` stacks them), a q of the rows' dtype as one.

`gqa_attend` takes the two leaves whole and the layer to work on; the grid
(slot, block), the clamped block index and the slot that is not live are
`mla_attend`'s (`_plan`). Leaves that hold the positions on the lanes
(`[.., d, T]`, a head of 64: granite) are not this kernel's: `lm.gqa_attend`
stays their path, as it is every leaf's off the chip and what the kernel is
tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import mla_attend as _mla
from ray_tpu.ops.rows_write import positions_last

# Positions a grid step takes of a slot's rows, at most (G heads of both
# leaves: 4 MB of bf16 at 1,024). `mla_attend.BLOCK` has the trade. On the
# v5e at 40 slots x 8 heads x 25,600 positions, live at 16.4k-25.2k, a call
# takes 4.64 / 4.70 / 4.88 / 4.90 ms at 512 / 1,024 / 2,048 / 2,560
# positions (the plain form 9.09; the rows' bytes at the HBM's peak 4.15),
# and with 4 of the 40 slots live 0.71 / 0.62 / 0.57 / 0.58 (plain 9.09):
# `benchmarks/gqa_attend_blocks.py`, PERF.md PR 52
BLOCK = 1024
# two buffers of a block of both leaves (8 MB at 1,024 positions), the
# block's scores and their probabilities in float32 and as pieces. (At 64
# MB the compiler carried the whole `conv` leaf of Solar's decode program
# through VMEM and back in every KDA layer: `tests/test_tpu_compile.py`)
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_MASKED = _mla._MASKED


def _pieces(x, dtype, two: bool):
    """x [G, Q, n] as the rows a product with rows of `dtype` takes: rounded
    to it, or its two pieces stacked on the rows, [G, 2 Q, n]: x's rounding
    and what that left (inside a kernel's body no simplifier sees the pair
    of conversions: `ops/pieces.py`). The halves are joined in float32: 8
    rows of bf16 are half a tile."""
    if not two:
        return x.astype(dtype)
    high = x.astype(dtype).astype(jnp.float32)
    return jnp.concatenate([high, x - high], axis=1).astype(dtype)


def _halves_added(y, two: bool):
    """The pieces' rows of a product, added."""
    rows = y.shape[1] // 2
    return y[:, :rows] + y[:, rows:] if two else y


def _kernel(layer_ref, src_ref, first_ref, last_ref, pos_ref, q_ref, k_ref,
            v_ref, o_ref, m_ref, l_ref, acc_ref, *, block: int, T: int,
            scale: float):
    """One block of one slot's rows of one layer, all G heads."""
    del layer_ref, src_ref, first_ref, last_ref
    slot, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot]                               # -1: the slot is dead

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block <= pos)
    def _():
        q, k, v = q_ref[0], k_ref[0, 0], v_ref[0, 0]  # [G,R,d], [G,block,d]
        two = q.dtype != k.dtype
        s = _halves_added(jnp.einsum(
            "gqd,gtd->gqt", _pieces(q, k.dtype, two), k,
            preferred_element_type=jnp.float32), two) * scale
        t = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(t <= pos, s, _MASKED)                  # [G, R, block]
        if T % block:
            # the last block hangs over the leaf's end: what lies there is
            # whatever VMEM held, and 0 x NaN is no 0
            row = j * block + lax.broadcasted_iota(jnp.int32, v.shape, 1)
            v = jnp.where(row < T, v, jnp.zeros_like(v))
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
        shrink = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = shrink * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = shrink * acc_ref[...] + _halves_added(jnp.einsum(
            "gqt,gtd->gqd", _pieces(p, v.dtype, two), v,
            preferred_element_type=jnp.float32), two)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        total = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(total == 0.0, 1.0, total)


def _block(T: int) -> int:
    return _mla._block(T, BLOCK)


def _attend_kernel(q, ck, cv, layer, pos, live, scale, block,
                   interpret: bool):
    B, G, R, d = q.shape
    T = ck.shape[3]
    block = block or _block(T)

    def rows(slot, j, layer, src, first, last, pos):
        return (layer[0], src[slot], 0,
                jnp.clip(j, first[slot], last[slot]), 0)

    def own(slot, j, *_):
        return slot, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, -(-T // block)),
        in_specs=[pl.BlockSpec((1, G, R, d), own),
                  pl.BlockSpec((1, 1, G, block, d), rows),
                  pl.BlockSpec((1, 1, G, block, d), rows)],
        out_specs=pl.BlockSpec((1, G, R, d), own),
        scratch_shapes=[pltpu.VMEM((G, R, 1), jnp.float32),
                        pltpu.VMEM((G, R, 1), jnp.float32),
                        pltpu.VMEM((G, R, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, block=block, T=T, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, R, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="gqa_attend", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      *_mla._plan(pos, live, T, block), q, ck, cv)


def gqa_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, layer, pos, live,
               scale: float, *, kernel: bool | None = None,
               interpret: bool = False):
    """Every slot's one token against its own rows of layer `layer`.

    q [B, G, R, d] (float32, or the rows' dtype), the leaves ck, cv
    [L, B, G, T, d] whole, pos [B] (slot b attends positions 0 .. pos[b]),
    live [B] -> [B, G, R, d] float32, garbage for a slot that is not live.
    On the TPU (or with `interpret`, or `kernel=True`) through the Pallas
    kernel, which reads a live slot's rows once and to its position;
    elsewhere `lm.gqa_attend` over the whole layer."""
    assert not positions_last(ck.shape, q.shape[-1]), (ck.shape, q.shape)
    if _mla._use_kernel(kernel, interpret):
        return _attend_kernel(q, ck, cv, layer, pos, live, scale, None,
                              interpret)
    from ray_tpu.models import lm       # not at the top: `models` imports us

    k, v = (lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for c in (ck, cv))
    return lm.gqa_attend(q, k, v, jnp.broadcast_to(
        pos[:, None, None], q.shape[:3]), scale, ck.dtype)


def read_positions(pos, live, T: int, **how):
    """`mla_attend.read_positions` at this kernel's block."""
    return _mla.read_positions(pos, live, T, most=BLOCK, **how)
