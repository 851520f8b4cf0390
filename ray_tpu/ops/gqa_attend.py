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
`slot_rows.attend`'s. `lm.gqa_attend` is every leaf's path off the chip and
what the kernel is tested against.

Leaves that hold the positions on the lanes (`[layers, slots, G, d, T]`, a
head of 64 on the sublanes: GPT-2's cache as the chip lays it out, granite's)
go through a body of their own, `_lanes_body`, on the same grid: a block's
scores are `q [G, R, d] x k [G, d, block]` as they lie, and the weighted
values contract the last axes of `p [G, R, block]` and `v [G, d, block]`.
Which body a call takes is read off the leaf's shape
(`rows_write.positions_last`). Their block is this file's, `block_last`,
which follows the leaf's length.
A step that has not written its new row yet (GPT-2's decode step writes
every layer's after its loop) hands the row over as `own = (k, v)`, each
`[B, G, d]`: the slot attends the leaf's rows before `pos` and its own row
at `pos`, as if the row had been written first.

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

Keys and values need not be alike (MiMo: a key has 192 lanes and a value
128, `[.., T, 192]` in bf16 is tiled to 256 lanes, and a global layer holds
4 key-value heads where a sliding layer's rings hold 8). The keys then hold
the positions on the lanes, `[layers, slots, G, 192, T]`, 192 on the
sublanes, and the values a position a row, `[layers, slots, G, T, 128]`:
both products as they lie, `_lanes_body`'s scores and `_weigh`'s weighted
values, and the result `[G, R, 128]`, the values' width. Which way round
each leaf lies is `rows_write.leaves_lie`'s to say; such leaves take
`slot_rows.BLOCK` like rows (their lanes stand at thousands of positions).

A `sink` [G, R] (float32: a learned score a query head, MiMo's sliding
layers) takes part in the softmax's denominator and weighs no value:

    p_t = exp(s_t - m) / (exp(b - m) + sum_t' exp(s_t' - m)),  m = max(b, s)

which is the fold started at a running maximum of b, a sum of 1 and an empty
accumulator (`slot_rows.Kernel.start`): no line of the body knows of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import slot_rows
from ray_tpu.ops.rows_write import leaves_lie
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


# `slot_rows.BLOCK` for leaves with the positions on the lanes, on the v5e
# (`benchmarks/gqa_attend_blocks.py --shapes gpt2,gpt2-chat,granite`, PRs 61
# and 63; us a call = a layer, the plain form first, then 128 / 256 / 512 /
# 1,024 positions a grid step; in brackets the attended rows' bytes at the
# HBM's peak over the time):
#   GPT-2 XL, 8 slots x 25 heads x 64 x 1,024, one bf16 q a head, own row:
#     8 live at 16-320 (the decode cell)  79.2 | 33.6 (22%)  39.5  51.0  76.2
#     1 live at 300-1,000 (the chat cell)  79.0 | 22.8  20.4  17.7  17.2 (41%)
#   granite, 48 slots x 8 heads x 64 x 8,192, four bf16 q a head, live at
#   3,100-7,200 (0.60 ms of rows): 1,114 (54%) | 1,374  936  772 (77.5%)  802
#   and as `block_last` runs it, 512:                        772 (77.5%)
# A grid step that works moves its rows at the HBM's pace (1.2-1.3 us at 128
# positions of 6.4 KB, 8.8 at 1,024: 0.82 and 6.5 MB), one that does not
# costs 0.14 us and a call ~7 us of its own. GPT-2's lanes stand at a few
# hundred positions of 1,024 and what a block costs them is the half block
# read past a position: 128. granite's stand at thousands of 8,192 and pay
# for 3,072 grid steps a call at 128. So the block follows the leaf's
# length: `BLOCK_LAST` positions at least, and above that `STEPS_LAST` grid
# steps a slot at most, which is 128 at 1,024 and 512 at 8,192, both rows'
# best (a slot then reads a 32nd of the leaf past its position on average:
# granite's 5.7% of what it attends, a quarter of the 22.5% its kernel
# stands under its rows' bytes; the rest is the grid's and the call's),
# and never beyond `slot_rows.BLOCK`, the longest block any table measured
# (granite's whole 131,072 positions: a 16th would not fit VMEM). Timed at
# those two lengths alone: at any other the rule is a line through them that
# the TPU's compiler takes (`tests/test_tpu_compile.py`: 4,096, 16,384 and
# 131,072) and no run has timed. In granite's decode step the call takes
# 730 us at ~39 live lanes, 66.6% of the attended rows' bytes (PERF.md PR 63)
BLOCK_LAST = 128
STEPS_LAST = 16


def block_last(T: int) -> int:
    """`slot_rows.block_of` for leaves with the positions on the lanes,
    under `BLOCK_LAST` and `STEPS_LAST`: a block is whole lane tiles that
    divide T, or the whole leaf."""
    most = min(T, max(BLOCK_LAST, min(T // STEPS_LAST, slot_rows.BLOCK)))
    whole = [n for n in range(slot_rows.LANES, most + 1, slot_rows.LANES)
             if T % n == 0]
    return most if most == T or not whole else whole[-1]


def _block(T: int, values_last: bool) -> int:
    """The positions a grid step takes of leaves T long: `block_last` where
    the values lie with the positions on the lanes, `slot_rows.block_of`
    for rows and rings."""
    return block_last(T) if values_last else slot_rows.block_of(T)


def read_block(ck_shape, cv_shape, d: int, *, kernel: bool | None = None,
               interpret: bool = False) -> int:
    """The positions of a slot's rows that `gqa_attend` reads at a time over
    leaves of these shapes (no rings) for a q of d lanes: its kernel's block
    where one runs (a live slot's rows to its position rounded up to one),
    all T in the plain form. What an engine counts `positions_read` by."""
    values_last = leaves_lie(ck_shape, cv_shape, d)[1]
    T = cv_shape[4 if values_last else 3]
    return _block(T, values_last) if slot_rows.use_kernel(
        kernel, interpret) else T


def _lanes_body(blk, q_ref, k_ref, v_ref, *own, two: bool, scale: float,
                values_last: bool = True):
    """All G heads at once, the keys' positions on the lanes; the values'
    too, or (not `values_last`) the values a position a row, [G, block, n].
    With the slot's own row (`own`: refs of its k and v, [1, G, 1, d]) the
    block that holds `pos` takes the row's score in column `pos`, and
    `_weigh_lanes` its values."""
    q, k, v = q_ref[0], k_ref[0, 0], v_ref[0, 0]      # [G,R,d], [G,d,block]
    q = _pieces(q, k.dtype, two)
    s = _halves_added(jnp.einsum(
        "gqd,gdt->gqt", q, k, preferred_element_type=jnp.float32), two)
    at = blk.at(s.shape, 2)
    mine = None
    if own:
        k_own, v_own = (ref[0] for ref in own)                  # [G, 1, d]
        mine = at == blk.pos, v_own
        s = jnp.where(mine[0], _halves_added(jnp.sum(
            q.astype(jnp.float32) * k_own.astype(jnp.float32),
            axis=-1, keepdims=True), two), s)
    s = jnp.where(at <= blk.pos, s * scale, MASKED)          # [G, R, block]
    yield ..., s, (slot_rows.zero_past_end(v, blk.held(
        v.shape, 2 if values_last else 1)), mine)


def _weigh_lanes(p, values, *, two: bool, values_last: bool = True):
    """p [G, R, block] against v [G, d, block] (or, not `values_last`,
    [G, block, d]) -> [G, R, d]; the slot's own row takes its probability
    apart from the block's, whose lane `pos` holds whatever the cache
    held."""
    v, mine = values
    if not values_last:
        return _weigh(p, v, two=two)

    def product(p):
        return _halves_added(jnp.einsum(
            "gqt,gdt->gqd", _pieces(p, v.dtype, two), v,
            preferred_element_type=jnp.float32), two)

    if mine is None:
        return product(p)
    hit, v_own = mine
    p_own = jnp.sum(jnp.where(hit, p, 0.0), axis=-1, keepdims=True)
    p_own = _halves_added(_pieces(p_own, v.dtype, two).astype(jnp.float32),
                          two)
    return product(jnp.where(hit, 0.0, p)) + p_own * v_own.astype(jnp.float32)


def rows_kernel(q, ck, cv, scale, name="gqa_attend", *, last: bool = False,
                own=(), values_last: bool | None = None,
                sink=None) -> slot_rows.Kernel:
    """This kernel on `slot_rows.attend`'s grid: a q that is not of the
    rows' dtype, and its probabilities, as two pieces; `last`: the leaves
    hold the positions on the lanes, and may lack the slot's `own` row;
    `values_last` where the values lie otherwise than the keys; `sink`
    [G, R]: the fold's start."""
    two = q.dtype != ck.dtype
    assert last or not own, "rows by head are written before they are read"
    if values_last is None:
        values_last = last
    assert last or not values_last, "values on the lanes lie beside keys so"
    # `_lanes_body` alone knows of values that lie otherwise than its keys
    way = {"values_last": False} if last and not values_last else {}
    body, weigh = ((_lanes_body, _weigh_lanes) if last
                   else (_block_body, _weigh))
    return slot_rows.Kernel(
        name, functools.partial(body, two=two, scale=float(scale), **way),
        (q, Leaf(ck, 4 if last else 3), Leaf(cv, 4 if values_last else 3),
         *(row[:, :, None] for row in own)),
        # the result is as wide as a value
        q.shape[1:-1] + (cv.shape[3 if values_last else 4],),
        functools.partial(weigh, two=two, **way), sink)


def gqa_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, layer, pos, live,
               scale: float, *, ring: bool = False, own=(), sink=None,
               kernel: bool | None = None, interpret: bool = False):
    """Every slot's one token against its own rows of layer `layer`.

    q [B, G, R, d] (float32, or the rows' dtype), the leaves ck, cv
    [L, B, G, T, d] (or [L, B, G, d, T]) whole, pos [B] (slot b attends
    positions 0 .. pos[b]), live [B] -> [B, G, R, d] float32, garbage for a
    slot that is not live. With `own` = (k, v), each [B, G, d], the leaves
    [L, B, G, d, T] do not hold position pos[b] yet: slot b attends their
    rows before it and its own row at it.
    With `ring` the leaves are rings [L, B, G, W, d] that hold position
    pos[b] already, and slot b attends the rows that are the sequence's,
    positions max(0, pos[b] - W + 1) .. pos[b].
    Keys [L, B, G, d, T] beside values [L, B, G, T, n] (rings: [.., d, W]
    and [.., W, n]) give [B, G, R, n]. With `sink` [G, R] every query's
    softmax has that score beside its rows', which weighs no value.
    On the TPU (or with `interpret`, or `kernel=True`) through the Pallas
    kernel, which reads a live slot's rows once and to its position;
    elsewhere `lm.gqa_attend` over the whole layer (a ring:
    `lm.gqa_attend_band` over the positions its rows hold)."""
    last, values_last = leaves_lie(ck.shape, cv.shape, q.shape[-1], ring)
    if slot_rows.use_kernel(kernel, interpret):
        name = "swa_attend" if ring else "gqa_attend"
        return slot_rows.attend(
            rows_kernel(q, ck, cv, scale, name, last=last, own=own,
                        values_last=values_last, sink=sink), layer,
            pos, live, block=_block(cv.shape[4 if values_last else 3],
                                    values_last), interpret=interpret)
    from ray_tpu.models import lm       # not at the top: `models` imports us

    k, v = (lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for c in (ck, cv))
    at = jnp.broadcast_to(pos[:, None, None], q.shape[:3])
    if sink is not None:
        sink = jnp.broadcast_to(sink, q.shape[:3])
    if ring:
        W = cv.shape[3]
        if last:
            k = jnp.swapaxes(k, -1, -2)
        return lm.gqa_attend_band(q, k, v, lm.ring_positions(pos, W)[:, None],
                                  at, W, scale, ck.dtype, sink=sink)
    if own:
        # the rows as they will lie once the step has written them
        hit = jnp.arange(k.shape[3]) == pos[:, None, None, None]
        k, v = (jnp.where(hit, row[..., None], c)
                for row, c in zip(own, (k, v)))
    return lm.gqa_attend(q, k, v, at, scale, ck.dtype, sink=sink)
