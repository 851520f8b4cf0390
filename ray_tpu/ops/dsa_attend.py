"""One token's grouped-head attention over the rows a learned indexer chose
(decode), a Pallas kernel on the TPU, on `ops/slot_rows.py`'s grid:
`ops/dsa.py`'s third step.

The cache of a sparse-attention layer holds a token's keys and values of all
G key-value heads side by side, two leaves `[layers, slots, T, G x d]`
(`models/keye.py`). A slot's one token brings R queries a key-value head and
a set S of at most `topk` of its rows:

    s_t = q . k_t * scale,  t in S;   o = sum_t softmax(s)_t v_t   [G, R, d]

In plain XLA (`dsa.attend_selected` over `dsa.gather_rows`) that is a gather
of the set's rows out of each leaf into a copy `[slots x topk, G x d]`, the
copy laid out again by head, and two products over it: at 32 slots x 2,048
rows of 1 KB the two gathers alone took 38% of Keye's decode step, 13 ns a
row, and the scope 6.3% of its roofline (PERF.md PR 54). A kernel that
fetched the set's rows one by one would have to start a copy every 7.5 ns to
do as well; a copy started from a kernel takes 44.5 ns, and cannot name one
row (a bf16 leaf lies in tiles of positions x 128 lanes: Mosaic refuses a
slice of fewer than 8 positions). Where the set is one row in five of those
a slot holds, as at 2,048 of 8-13 thousand, the cheaper read is the dense
one: here a slot's rows go through VMEM once, a block of positions of both
leaves at a time and only as far as the slot's own position, and the set is
a mask `keep [slots, T]` on the block's scores. Head g's keys are the
block's lanes `g d .. (g + 1) d` as they lie (nothing is transposed or laid
out again); its scores `[R, block]`, the running maximum and sum `[R, 1]`
and the accumulator `[R, d]` are float32, the block's probabilities go
against the v block's same lanes in the rows' dtype, one division when the
slot ends. The precision is the plain form's: q, the rows and the
probabilities one piece in the rows' dtype, float32 accumulation.

The grid (slot, block), the clamped block index and the slot that is not
live are `slot_rows.attend`'s. A block that holds no row of the set
leaves a running maximum of `MASKED` and weights of 1 behind; the first
block with a chosen row shrinks them to nothing (exp(-1e30) is 0), and a
live slot's set is never empty.

`rows_chosen` gives the set in the form this platform's attention reads,
the mask where the kernel runs and `dsa.select_rows`' indices elsewhere:
one set, to the row (`dsa.select_mask`). `dsa_attend` follows the form it is
handed: off the chip the plain path stays, and is what the kernel is tested
against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu.ops import dsa, slot_rows
from ray_tpu.ops.slot_rows import MASKED, Leaf

# `slot_rows.BLOCK` for these leaves (both: 2 MB of bf16 at 1,024 positions
# of 4 x 128), on the v5e: at 32 slots x 13,312 positions, live at
# 8.2k-12.9k with 2,048 rows chosen, a call takes 1.11 / 1.05 / 1.11 ms at
# 512 / 1,024 / 2,048 positions (the gather's form 2.29; the dense bytes at
# the HBM's peak 0.85, the chosen rows' 0.16), and with 4 of the 32 slots
# live 0.24 / 0.19 / 0.17 (plain 2.29): `benchmarks/dsa_attend_blocks.py`,
# PERF.md PR 54


def _block_body(blk, q_ref, keep_ref, k_ref, v_ref, *, scale: float):
    """The G heads in turn, head g the block's lanes g d .. (g + 1) d."""
    _, G, _, d = q_ref.shape
    t = blk.at((1, blk.block), 1)
    # past the leaf's end the mask is whatever VMEM held: pos < T
    seen = (keep_ref[0] != 0) & (t <= blk.pos)                 # [1, block]
    ends = (((1,), (1,)), ((), ()))               # both operands' last axis
    held = blk.held((blk.block, 1), 0)
    for g in range(G):
        lanes = pl.ds(g * d, d)
        k, v = k_ref[0, 0, :, lanes], v_ref[0, 0, :, lanes]    # [block, d]
        s = lax.dot_general(q_ref[0, g], k, ends,
                            preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, MASKED)                         # [R, block]
        yield g, s, slot_rows.zero_past_end(v, held)


def rows_kernel(q, ck, cv, keep, scale) -> slot_rows.Kernel:
    """This kernel on `slot_rows.attend`'s grid: the set as a mask
    `[B, 1, T]` of int32, a block of it beside the leaves'."""
    return slot_rows.Kernel(
        "dsa_attend", functools.partial(_block_body, scale=float(scale)),
        (q.astype(ck.dtype), Leaf(keep.astype(jnp.int32)[:, None], 2, False),
         Leaf(ck, 2), Leaf(cv, 2)), q.shape[1:])


def rows_chosen(scores, k: int, *, kernel: bool | None = None,
                interpret: bool = False):
    """scores [B, T] (`dsa.index_scores`' of one query a slot) -> the k
    largest in the form `dsa_attend` reads them here: on the TPU (or with
    `interpret`, or `kernel=True`) `dsa.select_mask`'s `keep` [B, T],
    elsewhere `dsa.select_rows`' `(idx, chosen)` [B, K]. The same rows
    either way."""
    if slot_rows.use_kernel(kernel, interpret):
        return dsa.select_mask(scores, k)
    return dsa.select_rows(scores, k)


def dsa_attend(q: jax.Array, ck: jax.Array, cv: jax.Array, layer, pos, live,
               rows, scale: float, *, interpret: bool = False):
    """Every slot's one token against its chosen rows of layer `layer`.

    q [B, G, R, d] in the rows' dtype, the leaves ck, cv [L, B, T, G d]
    whole, pos [B] (slot b's rows are 0 .. pos[b]), live [B], `rows` as
    `rows_chosen` gave them -> [B, G, R, d] float32, garbage for a slot that
    is not live. A mask goes through the Pallas kernel, which reads a live
    slot's rows once and to its position; indices through
    `dsa.attend_selected` over a gather of the rows they name."""
    if not isinstance(rows, tuple):
        return slot_rows.attend(rows_kernel(q, ck, cv, rows, scale), layer,
                                pos, live, interpret=interpret)
    idx, chosen = rows
    B, G, _, d = q.shape
    k_rows, v_rows = (dsa.gather_rows(c, layer, idx).reshape(B, -1, G, d)
                      for c in (ck, cv))
    return dsa.attend_selected(q, k_rows, v_rows, chosen, scale)


def read_positions(pos, live, T: int, k: int, *, kernel: bool | None = None,
                   interpret: bool = False):
    """The positions whose rows one call of `dsa_attend` reads, summed over
    the live slots (uint32): a slot's position rounded up to a block where
    the kernel runs (`slot_rows.read_positions`), the chosen rows,
    min(pos + 1, k), plain."""
    if slot_rows.use_kernel(kernel, interpret):
        return slot_rows.read_positions(pos, live, T, kernel=True)
    return jnp.sum(jnp.where(live.astype(bool), jnp.minimum(pos + 1, k),
                             0)).astype(jnp.uint32)
