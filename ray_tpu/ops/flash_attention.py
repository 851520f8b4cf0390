"""Flash attention as a Pallas TPU kernel (fwd + custom VJP bwd).

Design notes (TPU-first, see /opt/skills/guides/pallas_guide.md):
- three kernels: forward, dq and dk/dv. Each has a 4-D grid (batch, heads,
  outer tiles, inner tiles); the inner axis walks K/V tiles (forward, dq)
  or Q tiles (dk/dv) and is the only sequential one. A program holds one
  square `[block, Dh]` tile of each operand (a whole head up to T=4096,
  `block_sizes`), the float32 accumulators live in VMEM scratch across the
  inner axis, and the scores exist only as a slab of `sub_block` rows by
  the keys those rows see, in VMEM: nothing of the sequence's length is
  resident, and T=16k and 64k compile where the first kernel stopped at 8k;
- operands stay in the dtype they arrive in (bf16 in every training
  program) for all seven products, accumulated in float32
  (`preferred_element_type`); `p` and `ds` are rounded to that dtype only
  as MXU operands, as the dense path rounds `probs`. The scale is applied
  to the float32 scores after the product (1/sqrt(128) is no power of
  two) and to dq/dk once, on the float32 accumulator. Row maximum,
  normaliser, `lse`, `delta` and every accumulator are float32;
- causal: a tile above the diagonal is neither fetched (its index map
  repeats the last needed tile, so no DMA is issued) nor computed; a tile
  below it needs no mask; on the diagonal tile a slab of rows multiplies
  only the keys up to its own (the causal half is skipped `sub_block` rows
  at a time) and masks by `broadcasted_iota`;
- `lse` and `delta` travel as `[B, H, 1, T]`: a `[B, H, T, 1]` float32
  operand of a Mosaic call is padded to 128 lanes in HBM (268 MB at the
  OLMoE cell's shapes, which the first kernel held three times). dk/dv
  computes its scores transposed (`k q'`, keys along the rows), so the
  row form broadcasts as it is and no product needs a transposed operand;
  forward and dq turn a tile of it into a column once per q tile;
- the backward recomputes the scores tile by tile from `lse`; its
  residuals (`out` and `lse`; q, k, v are the caller's) carry
  `checkpoint_name`s (`RESIDUAL_NAMES`) so that a rematerialisation policy
  that saves matmul outputs can save them too: a Pallas call is not a dot,
  and without the names the forward kernel would run again in the backward
  pass.

Measured on a TPU v5e (benchmarks/flash_crossover.py, PR 31: forward and
forward + backward, causal, bf16, ms; % of the 197 TFLOP/s bf16 peak forward
+ backward at the FLOPs a causal kernel with 128-wide blocks executes, which
`benchmarks/chip/families/olmoe.flash_attention_cost` counts too). "first
kernel" is what this file held until PR 31: float32 operands, 128 x 128
blocks in a loop, a head's K/V whole in VMEM. The kept kernel won forward +
backward at all three training cells' shapes, so it is the one kernel; its
split backward (7 products) beat splash's fused one (5), which is the next
thing to try here (ROADMAP S1):

                          small-1k           xl-1k              olmoe-4k
                          B20 H12 T1024 Dh64 B8 H25 T1024 Dh64  B8 H16 T4096 Dh128
  implementation          fwd   f+b     %    fwd   f+b     %    fwd    f+b     %
  XLA dense               3.08  10.03   8.2  2.54   8.19   8.4  does not fit the chip
  first kernel            4.04  12.06   6.9  3.29   9.81   7.0  25.95  78.11  16.6
  kept, tiles 128/128     6.86  17.74   4.7  5.72  14.74   4.7  48.29 127.25  10.2
  kept, tiles chosen      1.38   3.68  22.5  1.15   3.06  22.5   4.21  15.27  84.8
  jax flash_attention 128 5.82  21.59   3.8  4.09  17.60   3.9  41.43 150.28   8.6
  jax flash_attention 512 1.49   7.54  11.0  1.05   6.29  11.0   5.99  31.17  41.5
  splash_attention 512    1.73   5.35  15.5  1.21   4.04  17.1   6.99  26.38  49.1
  splash 512, fused bwd   1.73   4.26  19.4  1.21   3.40  20.3   6.99  21.45  60.4

  GPT-2 small's heads (H12 Dh64) at 20,480 tokens, f+b ms, dense / kept:
  T=128 1.20 / 4.56, T=256 2.65 / 3.63, T=512 5.13 / 3.15, T=1024 10.03 /
  3.68, T=2048 19.16 / 5.13: the crossover lies between 256 and 512.

  The kept kernel's tiles (`block`/`sub_block`), f+b ms at small-1k |
  olmoe-4k: 512/128 4.89 | 26.74, 1024/128 3.68 | 19.83, 1024/256 3.88 |
  18.82, 1024/1024 (a tile's scores whole, nothing skipped inside it) 4.53 |
  20.13, 2048/256 - | 21.71 (2048/128 17.15, 2048/512 17.19), 4096/256 - |
  15.27; at T=2048: 1024/256 5.73, 2048/128 5.22, 2048/256 5.13. An earlier
  form with non-square tiles and no skipping inside a tile: 128x128 18.30 |
  134.85, 256x256 8.50 | 53.11, 512x512 5.07 | 26.12. A grid step costs
  more than the causal work a narrower tile skips, at every shape.

On the `tpu` backend the kernels are compiled by Mosaic; on the `cpu`
backend — the tests' virtual mesh, and nothing else — the same kernels run
under `interpret=True`, with numerics validated against `mha_reference` in
tests/test_ops_attention.py. Any other backend is an error, not a
fallback. tests/test_tpu_compile.py compiles them for a described v5e at
the training cells' shapes and at T=16384.

The reference framework has no comparable op (attention lives in user
frameworks); this is the TPU-native capability SURVEY.md §5.7 calls out.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# what `_vjp_fwd` calls its two residuals; `models/*` add them to the
# rematerialisation policies that save matmul outputs
RESIDUAL_NAMES = ("flash_out", "flash_lse")
# scoped VMEM a kernel may use: the default (16 MiB on a v5e core) is less
# than the backward's float32 temporaries at the widest tiles
_VMEM_LIMIT_BYTES = 64 * 2 ** 20

_NT = (((1,), (1,)), ((), ()))      # a @ b'
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"flash_attention compiles for the tpu backend and interprets "
            f"on the cpu test backend; {backend!r} is neither")
    return backend == "cpu"


def mha_reference(q, k, v, causal: bool = True, scale: Optional[float] = None):
    """Dense reference attention. q,k,v: [B, H, T, Dh]."""
    *_, T, Dh = q.shape
    Tk = k.shape[-2]
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        # offset aligns the causal diagonal when Tq != Tk (decode steps)
        qi = jnp.arange(T)[:, None] + (Tk - T)
        ki = jnp.arange(Tk)[None, :]
        s = jnp.where(qi >= ki, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


_BLOCK_CHOICES = (4096, 2048, 1024, 512, 256, 128)


def block_sizes(seq_q: int, seq_k: int, head_dim: int) -> tuple:
    """(block, sub_block) for a call, from the shapes it sees. A program
    holds a `[block, Dh]` tile of each operand: the widest of
    `_BLOCK_CHOICES` that divides both sequences (a whole head up to
    T=4096), because a grid step and the K/V it fetches again cost more
    than a wider tile does. Inside a tile the scores are computed a slab
    of `sub_block` rows at a time, against only the keys the slab sees
    (on the diagonal tile; `sub_block` is how finely the causal half is
    skipped): 128 rows, 256 where the slab is 2,048 or more wide. Read off
    the table in the module's docstring, which found the same winners at
    Dh 64 and 128, so `head_dim` decides nothing yet."""
    del head_dim
    block = next((b for b in _BLOCK_CHOICES
                  if seq_q % b == 0 and seq_k % b == 0), None)
    return block, block and (128 if block <= 1024 else 256)


def tiles_divide(seq_len: int) -> bool:
    """Whether the kernels take this sequence length (the rule in
    `models/lm.resolve_attn_impl` asks)."""
    return seq_len > 0 and seq_len % _BLOCK_CHOICES[-1] == 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _visit_tile(causal, one_tile, q_tile, k_tile, visit):
    """Run `visit(diagonal)` for a square tile pair as the causal diagonal
    has it: below (every slab sees the whole tile), on it (`diagonal`),
    above (nothing). A grid of one tile a head (`one_tile`: T <= 4096) has
    only the diagonal one: nothing else is lowered or compiled for it."""
    if not causal:
        visit(False)
    elif one_tile:
        visit(True)
    else:
        pl.when(k_tile < q_tile)(lambda: visit(False))
        pl.when(k_tile == q_tile)(lambda: visit(True))


def _sub(a, sub):
    return pl.ds(a * sub, sub)


def _seen(a, sub, block, diagonal):
    """The keys of a tile that q rows `_sub(a, sub)` of the same tile see:
    all of them, or on the diagonal tile those up to the slab's own."""
    return pl.ds(0, (a + 1) * sub if diagonal else block)


def _seeing(b, sub, block, diagonal):
    """The q rows of a tile that see keys `_sub(b, sub)` of the same tile:
    all of them, or on the diagonal tile those from the slab's own on."""
    return pl.ds(b * sub, block - b * sub) if diagonal else pl.ds(0, block)


def _visible(a, sub, shape, transposed=False):
    """Key position <= query position on the diagonal tile, for slab `a`:
    `[sub, seen]` with the slab's queries along the rows (`_seen`), or
    `[sub, seeing]` with the slab's keys along the rows (`_seeing`, whose
    first query is the slab's first key)."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return c >= r if transposed else r + a * sub >= c


def _eye():
    return (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))


def _store_as_row(col, row_ref):
    """col [n, 1] float32 -> row_ref[0, 0] ([1, n]), 128 at a time: exact
    (a select and a sum over one non-zero), and no transpose to lower."""
    eye = _eye()
    for c in range(0, col.shape[0], LANES):
        row_ref[0, 0, :, c:c + LANES] = jnp.sum(
            jnp.where(eye, col[c:c + LANES], 0.0), axis=0, keepdims=True)


def _store_as_col(row_ref, col_ref):
    """row_ref[0, 0] ([1, n]) -> col_ref ([n, 1] scratch), as above."""
    eye = _eye()
    for c in range(0, col_ref.shape[0], LANES):
        col_ref[c:c + LANES, :] = jnp.sum(
            jnp.where(eye, row_ref[0, 0, :, c:c + LANES], 0.0),
            axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, causal, scale, block, sub, one_tile):
    i, j = pl.program_id(2), pl.program_id(3)
    n = block // sub

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def visit(diagonal):
        for a in range(n):                  # a slab: `sub` rows of q
            rows, cols = _sub(a, sub), _seen(a, sub, block, diagonal)
            q, k, v = q_ref[0, 0, rows, :], k_ref[0, 0, cols, :], \
                v_ref[0, 0, cols, :]
            s = _dot(q, k, _NT) * scale                    # [sub, seen] f32
            if diagonal:
                s = jnp.where(_visible(a, sub, s.shape), s, NEG_INF)
            m = m_scr[rows, :]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_scr[rows, :] = l_scr[rows, :] * corr + jnp.sum(
                p, axis=-1, keepdims=True)
            acc_scr[rows, :] = acc_scr[rows, :] * corr + _dot(
                p.astype(v.dtype), v, _NN)
            m_scr[rows, :] = m_new

    _visit_tile(causal, one_tile, i, j, visit)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        # every row has seen a key (its own, under the causal mask): l >= 1
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        _store_as_row(m_scr[...] + jnp.log(l), lse_ref)


def _call(kernel, grid, in_specs, out_specs, out_shape, scratch_shapes):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
    )


def _resolve_blocks(q, k, block, sub):
    Tq, Tk, Dh = q.shape[2], k.shape[2], q.shape[3]
    auto = block_sizes(Tq, Tk, Dh)
    block = block or auto[0]
    sub = sub or (block and min(block, auto[1]))
    if (not block or Tq % block or Tk % block or block % sub
            or sub % LANES):
        raise ValueError(
            f"flash_attention: seq lens ({Tq},{Tk}) must divide into tiles "
            f"of a multiple of {LANES} (asked: tiles of {block}, computed "
            f"{sub} at a time); pad the sequence")
    return block, sub


def _specs(causal, Dh, block):
    """BlockSpecs on a (b, h, q tile, k tile) grid: a `[block, Dh]` tile
    by the q index, one by the k index, a `[1, block]` row of statistics.
    Under the causal mask the k index stops at the q tile's own: a
    repeated index fetches nothing."""
    def k_tile(i, j):
        return jnp.minimum(j, i) if causal else j
    by_q = pl.BlockSpec((1, 1, block, Dh), lambda b, h, i, j: (b, h, i, 0))
    by_k = pl.BlockSpec((1, 1, block, Dh),
                        lambda b, h, i, j: (b, h, k_tile(i, j), 0))
    row = pl.BlockSpec((1, 1, 1, block), lambda b, h, i, j: (b, h, 0, i))
    return by_q, by_k, row


def _fwd(q, k, v, causal, scale, block, sub):
    B, H, Tq, Dh = q.shape
    Tk = k.shape[2]
    block, sub = _resolve_blocks(q, k, block, sub)
    by_q, by_k, row = _specs(causal, Dh, block)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block=block, sub=sub,
                               one_tile=Tq == Tk == block)
    return _call(
        kernel, (B, H, Tq // block, Tk // block),
        in_specs=[by_q, by_k, by_k], out_specs=[by_q, row],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Tq), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)])(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   lse_scr, delta_scr, dq_scr,
                   *, causal, scale, block, sub, one_tile):
    i, j = pl.program_id(2), pl.program_id(3)
    n = block // sub

    @pl.when(j == 0)
    def _():
        _store_as_col(lse_ref, lse_scr)
        _store_as_col(delta_ref, delta_scr)
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def visit(diagonal):
        for a in range(n):
            rows, cols = _sub(a, sub), _seen(a, sub, block, diagonal)
            q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
            k, v = k_ref[0, 0, cols, :], v_ref[0, 0, cols, :]
            s = _dot(q, k, _NT) * scale
            if diagonal:
                s = jnp.where(_visible(a, sub, s.shape), s, NEG_INF)
            p = jnp.exp(s - lse_scr[rows, :])
            ds = p * (_dot(do, v, _NT) - delta_scr[rows, :])   # unscaled
            dq_scr[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    _visit_tile(causal, one_tile, i, j, visit)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr,
                    *, causal, scale, block, sub, one_tile):
    j, i = pl.program_id(2), pl.program_id(3)       # k tile outer, q inner
    n = block // sub

    @pl.when(i == 0)
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def visit(diagonal):
        for b in range(n):                  # a slab: `sub` rows of k and v
            rows, cols = _sub(b, sub), _seeing(b, sub, block, diagonal)
            k, v = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]
            q, do = q_ref[0, 0, cols, :], do_ref[0, 0, cols, :]
            st = _dot(k, q, _NT) * scale                   # [sub, seeing]
            if diagonal:
                st = jnp.where(_visible(b, sub, st.shape, transposed=True),
                               st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, 0, :, cols])      # a row, broadcast
            dv_scr[rows, :] += _dot(pt.astype(do.dtype), do, _NN)
            dst = pt * (_dot(v, do, _NT) - delta_ref[0, 0, :, cols])
            dk_scr[rows, :] += _dot(dst.astype(q.dtype), q, _NN)

    _visit_tile(causal, one_tile, i, j, visit)

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(causal, scale, block, sub, residuals, do):
    q, k, v, out, lse = residuals
    B, H, Tq, Dh = q.shape
    Tk = k.shape[2]
    block, sub = _resolve_blocks(q, k, block, sub)
    # delta_i = rowsum(dO_i * O_i), the softmax-jacobian diagonal term
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B, H, 1, Tq)
    params = dict(causal=causal, scale=scale, block=block, sub=sub,
                  one_tile=Tq == Tk == block)

    by_q, by_k, row = _specs(causal, Dh, block)
    dq = _call(
        functools.partial(_bwd_dq_kernel, **params),
        (B, H, Tq // block, Tk // block),
        in_specs=[by_q, by_k, by_k, by_q, row, row], out_specs=by_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)],
    )(q, k, v, do, lse, delta)

    # grid (b, h, k tile, q tile): under the causal mask the q index starts
    # at the k tile's own
    def q_tile(j, i):
        return jnp.maximum(i, j) if causal else i
    of_q = pl.BlockSpec((1, 1, block, Dh),
                        lambda b, h, j, i: (b, h, q_tile(j, i), 0))
    of_k = pl.BlockSpec((1, 1, block, Dh), lambda b, h, j, i: (b, h, j, 0))
    of_row = pl.BlockSpec((1, 1, 1, block),
                          lambda b, h, j, i: (b, h, 0, q_tile(j, i)))
    dk, dv = _call(
        functools.partial(_bwd_dkv_kernel, **params),
        (B, H, Tk // block, Tq // block),
        in_specs=[of_q, of_k, of_k, of_q, of_row, of_row],
        out_specs=[of_k, of_k],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, Dh), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)],
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _scale_of(q, scale):
    return scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block: Optional[int] = None,
                    sub_block: Optional[int] = None):
    """Fused attention. q,k,v: [B, H, T, Dh] -> [B, H, T, Dh]. `causal`
    masks key positions after the query's own (Tq == Tk). The tile widths
    come from the shapes (`block_sizes`) unless given."""
    return _fwd(q, k, v, causal, _scale_of(q, scale), block, sub_block)[0]


def _vjp_fwd(q, k, v, causal, scale, block, sub_block):
    out, lse = _fwd(q, k, v, causal, _scale_of(q, scale), block, sub_block)
    out = checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, scale, block, sub_block, residuals, g):
    return _bwd(causal, _scale_of(residuals[0], scale), block, sub_block,
                residuals, g)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)


def flash_attention_on_mesh(q, k, v, causal: bool = True, mesh=None):
    """`flash_attention` inside a sharded program. The TPU compiler cannot
    partition a Mosaic kernel on its own ("Mosaic kernels cannot be
    automatically partitioned"), so under a multi-device mesh each device
    runs the kernel on its own (batch, heads) shard through `shard_map`.
    The sequence stays whole: a mesh that shards it takes ring attention."""
    from jax import shard_map

    from ray_tpu.parallel.mesh import current_mesh, logical_to_spec

    mesh = mesh or current_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal)
    spec = logical_to_spec("batch", "heads", None, None)
    return shard_map(lambda q, k, v: flash_attention(q, k, v, causal),
                     mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                     check_vma=False)(q, k, v)
