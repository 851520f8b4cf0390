"""One token's grouped-head attention over the rows a learned indexer chose
(decode), a Pallas kernel on the TPU: `ops/mla_attend.py`'s and
`ops/gqa_attend.py`'s sibling for `ops/dsa.py`'s third step.

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
live are `mla_attend`'s (`_plan`). A block that holds no row of the set
leaves a running maximum of `_MASKED` and weights of 1 behind; the first
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
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import dsa, mla_attend as _mla

# Positions a grid step takes of a slot's rows, at most (both leaves: 2 MB
# of bf16 at 1,024 positions of 4 x 128). `mla_attend.BLOCK` has the trade.
# On the v5e at 32 slots x 13,312 positions, live at 8.2k-12.9k with 2,048
# rows chosen, a call takes 1.11 / 1.05 / 1.11 ms at 512 / 1,024 / 2,048
# positions (the gather's form 2.29; the dense bytes at the HBM's peak
# 0.85, the chosen rows' 0.16), and with 4 of the 32 slots live 0.24 / 0.19
# / 0.17 (plain 2.29): `benchmarks/dsa_attend_blocks.py`, PERF.md PR 54
BLOCK = 1024
# two buffers of a block of both leaves (4 MB at 1,024 positions) and of
# the mask, a head's scores and probabilities in float32. (Not 64 MB:
# `gqa_attend.VMEM_LIMIT_BYTES`)
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_MASKED = _mla._MASKED


def _kernel(layer_ref, src_ref, first_ref, last_ref, pos_ref, q_ref,
            keep_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block: int, T: int, scale: float):
    """One block of one slot's rows of one layer, the G heads in turn."""
    del layer_ref, src_ref, first_ref, last_ref
    slot, j = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot]                               # -1: the slot is dead
    G, _, d = acc_ref.shape

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block <= pos)
    def _():
        t = j * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        # past the leaf's end the mask is whatever VMEM held: pos < T
        seen = (keep_ref[0] != 0) & (t <= pos)                 # [1, block]
        ends = (((1,), (1,)), ((), ()))           # both operands' last axis
        if T % block:
            # the last block hangs over the leaf's end: what lies there is
            # whatever VMEM held, and 0 x NaN is no 0
            held = j * block + lax.broadcasted_iota(
                jnp.int32, (block, 1), 0) < T
        for g in range(G):
            lanes = pl.ds(g * d, d)
            k, v = k_ref[0, 0, :, lanes], v_ref[0, 0, :, lanes]  # [block,d]
            s = lax.dot_general(q_ref[0, g], k, ends,
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _MASKED)                    # [R, block]
            if T % block:
                v = jnp.where(held, v, jnp.zeros_like(v))
            m_old = m_ref[g]
            m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
            shrink = jnp.exp(m_old - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = shrink * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = shrink * acc_ref[g] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        total = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(total == 0.0, 1.0, total)


def _block(T: int) -> int:
    return _mla._block(T, BLOCK)


def _attend_kernel(q, ck, cv, layer, pos, live, keep, scale, block,
                   interpret: bool):
    B, G, R, d = q.shape
    T = ck.shape[2]
    block = block or _block(T)

    def block_of(slot, j, first, last):
        return jnp.clip(j, first[slot], last[slot])

    def rows(slot, j, layer, src, first, last, pos):
        return layer[0], src[slot], block_of(slot, j, first, last), 0

    def mask(slot, j, layer, src, first, last, pos):
        return src[slot], 0, block_of(slot, j, first, last)

    def own(slot, j, *_):
        return slot, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, -(-T // block)),
        in_specs=[pl.BlockSpec((1, G, R, d), own),
                  pl.BlockSpec((1, 1, block), mask),
                  pl.BlockSpec((1, 1, block, G * d), rows),
                  pl.BlockSpec((1, 1, block, G * d), rows)],
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
        name="dsa_attend", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      *_mla._plan(pos, live, T, block), q.astype(ck.dtype),
      keep.astype(jnp.int32)[:, None], ck, cv)


def rows_chosen(scores, k: int, *, kernel: bool | None = None,
                interpret: bool = False):
    """scores [B, T] (`dsa.index_scores`' of one query a slot) -> the k
    largest in the form `dsa_attend` reads them here: on the TPU (or with
    `interpret`, or `kernel=True`) `dsa.select_mask`'s `keep` [B, T],
    elsewhere `dsa.select_rows`' `(idx, chosen)` [B, K]. The same rows
    either way."""
    if _mla._use_kernel(kernel, interpret):
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
        return _attend_kernel(q, ck, cv, layer, pos, live, rows, scale, None,
                              interpret)
    idx, chosen = rows
    B, G, _, d = q.shape
    k_rows, v_rows = (dsa.gather_rows(c, layer, idx).reshape(B, -1, G, d)
                      for c in (ck, cv))
    return dsa.attend_selected(q, k_rows, v_rows, chosen, scale)


def read_positions(pos, live, T: int, k: int, *, kernel: bool | None = None,
                   interpret: bool = False):
    """The positions whose rows one call of `dsa_attend` reads, summed over
    the live slots (uint32): a slot's position rounded up to a block where
    the kernel runs (`mla_attend.read_positions` at this block), the chosen
    rows, min(pos + 1, k), plain."""
    if _mla._use_kernel(kernel, interpret):
        return _mla.read_positions(pos, live, T, kernel=True, most=BLOCK)
    return jnp.sum(jnp.where(live.astype(bool), jnp.minimum(pos + 1, k),
                             0)).astype(jnp.uint32)
