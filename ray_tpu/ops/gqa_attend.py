"""One token's grouped-head attention over a slot's rows by head (decode),
a Pallas kernel on the TPU, on `ops/slot_rows.py`'s grid.

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
`slot_rows.attend`'s. Leaves that hold the positions on the lanes
(`[.., d, T]`, a head of 64: granite) are not this kernel's: `lm.gqa_attend`
stays their path, as it is every leaf's off the chip and what the kernel is
tested against.

A ring leaf (`ring=True`: `[layers, slots, G, W, d]`, the last W positions
of a sliding-window layer, position p at row p mod W: `models/lm.py`, "a
sliding window's rows") goes through the same body as one block of W
positions, under the name `swa_attend`, with the mask by age where a leaf of
rows has `t <= pos`: row r holds position pos - ((pos - r) mod W) and is
live iff that is not negative, which is r <= pos while the ring fills and
every row once pos >= W - 1. That is the rows' own mask with the position
held at W - 1, and `slot_rows.plan` holds it there: the ring takes no line
of its own in the kernel. W and d may be equal (128 and 128), so a ring
says that it is one; it cannot be read off the shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import slot_rows
from ray_tpu.ops.rows_write import positions_last
from ray_tpu.ops.slot_rows import MASKED, Leaf, read_positions  # noqa: F401

# `slot_rows.BLOCK` for these leaves (G heads of both: 4 MB of bf16 at
# 1,024), on the v5e: at 40 slots x 8 heads x 25,600 positions, live at
# 16.4k-25.2k, a call takes 4.64 / 4.70 / 4.88 / 4.90 ms at 512 / 1,024 /
# 2,048 / 2,560 positions (the plain form 9.09; the rows' bytes at the HBM's
# peak 4.15), and with 4 of the 40 slots live 0.71 / 0.62 / 0.57 / 0.58
# (plain 9.09): `benchmarks/gqa_attend_blocks.py`, PERF.md PR 52


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


def _block_body(blk, q_ref, k_ref, v_ref, *, two: bool, scale: float):
    """All G heads at once."""
    q, k, v = q_ref[0], k_ref[0, 0], v_ref[0, 0]      # [G,R,d], [G,block,d]
    s = _halves_added(jnp.einsum(
        "gqd,gtd->gqt", _pieces(q, k.dtype, two), k,
        preferred_element_type=jnp.float32), two) * scale
    s = jnp.where(blk.at(s.shape, 2) <= blk.pos, s, MASKED)  # [G, R, block]
    yield ..., s, slot_rows.zero_past_end(v, blk.held(v.shape, 1))


def _weigh(p, v, *, two: bool):
    return _halves_added(jnp.einsum(
        "gqt,gtd->gqd", _pieces(p, v.dtype, two), v,
        preferred_element_type=jnp.float32), two)


def rows_kernel(q, ck, cv, scale, name="gqa_attend") -> slot_rows.Kernel:
    """This kernel on `slot_rows.attend`'s grid: a q that is not of the
    rows' dtype, and its probabilities, as two pieces."""
    two = q.dtype != ck.dtype
    return slot_rows.Kernel(
        name,
        functools.partial(_block_body, two=two, scale=float(scale)),
        (q, Leaf(ck, 3), Leaf(cv, 3)), q.shape[1:],
        functools.partial(_weigh, two=two))


def gqa_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, layer, pos, live,
               scale: float, *, ring: bool = False,
               kernel: bool | None = None, interpret: bool = False):
    """Every slot's one token against its own rows of layer `layer`.

    q [B, G, R, d] (float32, or the rows' dtype), the leaves ck, cv
    [L, B, G, T, d] whole, pos [B] (slot b attends positions 0 .. pos[b]),
    live [B] -> [B, G, R, d] float32, garbage for a slot that is not live.
    With `ring` the leaves are rings [L, B, G, W, d] that hold position
    pos[b] already, and slot b attends the rows that are the sequence's,
    positions max(0, pos[b] - W + 1) .. pos[b].
    On the TPU (or with `interpret`, or `kernel=True`) through the Pallas
    kernel, which reads a live slot's rows once and to its position;
    elsewhere `lm.gqa_attend` over the whole layer (a ring:
    `lm.gqa_attend_band` over the positions its rows hold)."""
    assert ring or not positions_last(ck.shape, q.shape[-1]), (ck.shape,
                                                               q.shape)
    if slot_rows.use_kernel(kernel, interpret):
        name = "swa_attend" if ring else "gqa_attend"
        return slot_rows.attend(rows_kernel(q, ck, cv, scale, name), layer,
                                pos, live, interpret=interpret)
    from ray_tpu.models import lm       # not at the top: `models` imports us

    k, v = (lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for c in (ck, cv))
    at = jnp.broadcast_to(pos[:, None, None], q.shape[:3])
    if ring:
        W = ck.shape[3]
        return lm.gqa_attend_band(q, k, v, lm.ring_positions(pos, W)[:, None],
                                  at, W, scale, ck.dtype)
    return lm.gqa_attend(q, k, v, at, scale, ck.dtype)
