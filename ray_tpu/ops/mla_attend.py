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

The grid (slot, block), the clamped block index, the slot that is not live
and the fold over a slot's blocks are `ops/slot_rows.py`'s; the body of one
block, below, is this kernel's.

`attend_rows` is the plain form at any number of lanes a row: what a
chunk's further lanes run against one slot's rows, the path off the chip,
and what the kernel is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import slot_rows
from ray_tpu.ops.slot_rows import MASKED, Leaf, read_positions  # noqa: F401

# `slot_rows.BLOCK` for these leaves, on the v5e: at 128 slots x 10,240
# positions, live at 4,200-9,300, a call takes 2.82 / 1.99 / 1.67 / 1.68 /
# 1.77 / 1.78 / 1.92 ms at 256 / 512 / 1,024 / 1,280 / 2,048 / 2,560 /
# 5,120 positions (the plain form 5.03; the rows' bytes at the HBM's peak
# 1.23), and at 32 x 4,096, live at 2,100-3,650, 0.32 / 0.24 / 0.209 /
# 0.211 / 0.213 at 256 .. 4,096 (plain 0.40):
# `benchmarks/mla_attend_blocks.py`, PERF.md PR 41


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
    scores = jnp.where(t_idx <= pos[:, None, :, None], scores, MASKED)
    probs = jax.nn.softmax(scores, axis=-1).astype(latents.dtype)
    # the heads before the lanes: with the lanes first the CPU backend has
    # no float32 product of two bf16 operands
    return jnp.einsum("bhct,btr->bhcr", probs, latents,
                      preferred_element_type=jnp.float32)


def _block_body(blk, qa_ref, qr_ref, lat_ref, kr_ref, *, scale: float):
    """All H heads at once: the block's latents are the values too."""
    lat = lat_ref[0, 0]                                        # [block, r]
    ends = (((1,), (1,)), ((), ()))               # both operands' last axis
    s = (lax.dot_general(qa_ref[0], lat, ends,
                         preferred_element_type=jnp.float32)
         + jnp.dot(qr_ref[0], kr_ref[0, 0],                   # [p, block]
                   preferred_element_type=jnp.float32)) * scale
    s = jnp.where(blk.at(s.shape, 1) <= blk.pos, s, MASKED)    # [H, block]
    yield ..., s, slot_rows.zero_past_end(lat, blk.held(lat.shape, 0))


def rows_kernel(q_abs, q_r, lat, kr, scale) -> slot_rows.Kernel:
    """This kernel on `slot_rows.attend`'s grid: the rotary key's leaf as
    `[.., p, T]`, the same bytes (the docstring has why)."""
    return slot_rows.Kernel(
        "mla_attend", functools.partial(_block_body, scale=float(scale)),
        (q_abs, q_r, Leaf(lat, 2), Leaf(jnp.swapaxes(kr, 2, 3), 3)),
        q_abs.shape[1:])


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
    if slot_rows.use_kernel(kernel, interpret):
        return slot_rows.attend(rows_kernel(q_abs, q_r, lat, kr, scale),
                                layer, pos, live, interpret=interpret)
    rows = (lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for c in (lat, kr))
    return attend_rows(q_abs[:, None], q_r[:, None], *rows, pos[:, None],
                       scale)[:, :, 0]
