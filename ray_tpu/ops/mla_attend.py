"""One token's absorbed attention over a slot's latent rows (MLA, decode),
a Pallas kernel on the TPU.

The cache of a latent-attention layer holds, a token, the latent `c` (r
values, after its norm) and the shared rotary key `k_r` (p values): two
leaves `[layers, slots, T, r | p]`. With the key's up-projection folded into
the query (`models/deepseek.py`'s absorbed form) a slot's one query a head
attends over its own rows:

    s_t = (q_abs . c_t + q_r . k_r,t) * scale,  t <= pos
    mixed = sum_t softmax(s)_t c_t                               [H, r]

In plain XLA that is two products over all T positions of every slot
whatever its position, float32 scores `[B, H, 1, T]` written to HBM, a
softmax over them and a third product that reads the latent a second time
(19% of the chip's roofline at 128 slots x 10,240 positions, PERF.md PR 41).
Here a slot's rows go through VMEM once, a block of positions at a time and
only as far as the slot's own position: the scores of a block `[H, block]`
(float32 from the bf16 operands, positions on the lanes), the running
maximum, sum and accumulator `[H, r]` in float32, the block's probabilities
rounded to the rows' dtype for their product with the same latent block,
and one division when the slot ends. The precision is the plain form's.

`mla_attend` takes the two leaves whole and the layer to work on: the index
map picks a block of one slot's rows where it lies, and nothing slices or
copies a layer of a leaf. (The TPU's compiler holds a bf16 `[.., T, 64]`
array with the T positions on the lanes, the rotary key's leaf among them:
the kernel takes that leaf as `[.., p, T]`, the same bytes, and a block of
it `[p, block]` is the scores' second product as it lies. Handed over as
`[.., T, p]` the whole leaf is copied, padded to 128 lanes, in every call:
`tests/test_tpu_compile.py` pins that it is not.)

The grid is (slot, block). A block past a slot's position computes nothing
and moves nothing: its index is clamped at the slot's last needed block,
which the pipeline finds already in VMEM; a slot that is not live is given
the index the slot before it ended on, and reads nothing at all.

`attend_rows` is the plain form at any number of lanes a row: what a
chunk's further lanes run against one slot's rows, the path off the chip,
and what the kernel is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Positions a grid step takes of a slot's rows, at most. The trade: a grid
# step costs its own time whether the slot's position is reached or not
# (0.14 us one that does nothing, ~0.35 one that works; slots x T / block of
# them a call) against the half block a slot reads past its position. On the
# v5e at 128 slots x 10,240 positions, live at 4,200-9,300, a call takes
# 2.82 / 1.99 / 1.67 / 1.68 / 1.77 / 1.78 / 1.92 ms at 256 / 512 / 1,024 /
# 1,280 / 2,048 / 2,560 / 5,120 positions (the plain form 5.03; the rows'
# bytes at the HBM's peak 1.23), and at 32 x 4,096, live at 2,100-3,650,
# 0.32 / 0.24 / 0.209 / 0.211 / 0.213 at 256 .. 4,096 (plain 0.40):
# `benchmarks/mla_attend_blocks.py`, PERF.md PR 41
BLOCK = 1024
LANES = 128
# two buffers of a block of both leaves, the block's scores and their
# probabilities: 2.7 MB at 1,024 positions
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_MASKED = -1e30


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attend_rows(q_abs, q_r, latents, keys, pos, scale):
    """q_abs [N,C,H,r] and q_r [N,C,H,p], C lanes a row at positions pos
    [N,C], against the rows latents [N,T,r] and keys [N,T,p] -> mixed
    [N,H,C,r] float32: scores and softmax in float32, the probabilities
    rounded to the rows' dtype before the weighted sum."""
    T = latents.shape[1]
    scores = (jnp.einsum("bchr,btr->bhct", q_abs, latents,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bchp,btp->bhct", q_r, keys,
                           preferred_element_type=jnp.float32)) * scale
    t_idx = jnp.arange(T)[None, None, None, :]
    scores = jnp.where(t_idx <= pos[:, None, :, None], scores, _MASKED)
    probs = jax.nn.softmax(scores, axis=-1).astype(latents.dtype)
    # the heads before the lanes: with the lanes first the CPU backend has
    # no float32 product of two bf16 operands
    return jnp.einsum("bhct,btr->bhcr", probs, latents,
                      preferred_element_type=jnp.float32)


def _block(T: int, most: int | None = None) -> int:
    """Positions a grid step takes, `most` at most (`BLOCK`, or a sibling's
    own): all T where they fit one block, else the longest stretch of whole
    lane tiles within a block that divides T, else `most` itself with the
    last block ragged."""
    most = min(T, most or BLOCK)
    whole = [n for n in range(LANES, most + 1, LANES) if T % n == 0]
    return most if most == T or not whole else whole[-1]


def _kernel(layer_ref, src_ref, first_ref, last_ref, pos_ref, qa_ref, qr_ref,
            lat_ref, kr_ref, o_ref, m_ref, l_ref, acc_ref, *, block: int,
            T: int, scale: float):
    """One block of one slot's rows of one layer."""
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
        lat = lat_ref[0, 0]                                    # [block, r]
        ends = (((1,), (1,)), ((), ()))           # both operands' last axis
        s = (lax.dot_general(qa_ref[0], lat, ends,
                             preferred_element_type=jnp.float32)
             + jnp.dot(qr_ref[0], kr_ref[0, 0],               # [p, block]
                       preferred_element_type=jnp.float32)) * scale
        t = j * block + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(t <= pos, s, _MASKED)                    # [H, block]
        if T % block:
            # the last block hangs over the leaf's end: what lies there is
            # whatever VMEM held, and 0 x NaN is no 0
            row = j * block + lax.broadcasted_iota(jnp.int32, lat.shape, 0)
            lat = jnp.where(row < T, lat, jnp.zeros_like(lat))
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        shrink = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = shrink * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = shrink * acc_ref[...] + jnp.dot(
            p.astype(lat.dtype), lat, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        total = l_ref[...]
        o_ref[0] = acc_ref[...] / jnp.where(total == 0.0, 1.0, total)


def _plan(pos, live, T: int, block: int):
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


def _attend_kernel(q_abs, q_r, lat, kr, layer, pos, live, scale, block,
                   interpret: bool):
    B, H, r = q_abs.shape
    T, p = lat.shape[2], kr.shape[3]
    block = block or _block(T)

    def block_of(slot, j, first, last):
        return jnp.clip(j, first[slot], last[slot])

    def rows(slot, j, layer, src, first, last, pos):
        return layer[0], src[slot], block_of(slot, j, first, last), 0

    def lanes(slot, j, layer, src, first, last, pos):
        return layer[0], src[slot], 0, block_of(slot, j, first, last)

    def own(slot, j, *_):
        return slot, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5, grid=(B, -(-T // block)),
        in_specs=[pl.BlockSpec((1, H, r), own),
                  pl.BlockSpec((1, H, p), own),
                  pl.BlockSpec((1, 1, block, r), rows),
                  pl.BlockSpec((1, 1, p, block), lanes)],
        out_specs=pl.BlockSpec((1, H, r), own),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, r), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, block=block, T=T, scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="mla_attend", interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      *_plan(pos, live, T, block), q_abs, q_r, lat, jnp.swapaxes(kr, 2, 3))


def _use_kernel(kernel, interpret: bool) -> bool:
    return (interpret or _on_tpu()) if kernel is None else kernel


def mla_attend(q_abs: jax.Array, q_r: jax.Array, lat: jax.Array,
               kr: jax.Array, layer, pos, live, scale: float, *,
               kernel: bool | None = None, interpret: bool = False):
    """Every slot's one token against its own rows of layer `layer`.

    q_abs [B, H, r] and q_r [B, H, p] in the rows' dtype, the leaves lat
    [L, B, T, r] and kr [L, B, T, p] whole, pos [B] (slot b attends
    positions 0 .. pos[b]), live [B] -> mixed [B, H, r] float32, garbage
    for a slot that is not live. On the TPU (or with `interpret`, or
    `kernel=True`) through the Pallas kernel, which reads a live slot's
    rows once and to its position; elsewhere `attend_rows` over the whole
    layer."""
    if _use_kernel(kernel, interpret):
        return _attend_kernel(q_abs, q_r, lat, kr, layer, pos, live, scale,
                              None, interpret)
    rows = (lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for c in (lat, kr))
    return attend_rows(q_abs[:, None], q_r[:, None], *rows, pos[:, None],
                       scale)[:, :, 0]


def read_positions(pos, live, T: int, *, kernel: bool | None = None,
                   interpret: bool = False, most: int | None = None):
    """The positions whose rows one call of `mla_attend` (or of a sibling
    whose blocks are at most `most`) reads, summed over the live slots
    (uint32): all T a slot plain, its position rounded up to a block here."""
    live = live.astype(bool)
    each = T
    if _use_kernel(kernel, interpret):
        block = _block(T, most)
        each = jnp.minimum((jnp.clip(pos, 0, T - 1) // block + 1) * block, T)
    return jnp.sum(jnp.where(live, each, 0)).astype(jnp.uint32)
