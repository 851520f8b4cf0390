"""An expert layer's whole routed SwiGLU over ragged row groups, one Pallas
kernel on the TPU.

`xs` [R, D] holds the (token, slot) pairs' rows sorted by expert,
`group_sizes` says how many rows each expert has, `wg`, `wu` [G, D, F] and
`wd` [G, F, D] are the experts' matrices, stacked. Row i of the result is

    (silu(xs[i] @ wg[e]) * (xs[i] @ wu[e])) @ wd[e],   e = the group of i

Built from three grouped matmuls (`ops/grouped_matmul.py`, which training
and every many-rows regime keep) that is nine calls a layer when the rows are
float32 against matrices held in bf16 (`models/kimi.py`): the rows' two bf16
pieces made in HBM, three products whose float32 results `[2R, F]`,
`[2R, F]`, `[2R, D]` are written and read back to add the pieces' rows, the
SwiGLU one more pass, and megablox's group metadata made three times over
all G groups. At a decode step's few rows an expert (Kimi: 1,024 rows, ~250
of them on ~44 held experts a layer) the three kernels took 1.44 ms a layer
where the touched matrices' bytes take 0.75 (a contraction of 2,304 cut at
2,048, 128-row tiles over ~500 piece-rows) and what stands round them 0.27
more: 44-47% of what the chip allows (PERF.md, PR 42). This kernel takes
0.83 ms alone and 0.95-0.97 in that step.

Here a grid step takes one touched expert's rows and one tile of F. In VMEM:
a float32 row is split into the two pieces of the matrices' dtype that add
up to it (`high = x.astype(bf16)`, `low = (x - high).astype(bf16)`), both
stacked on the rows of one MXU operand so that a weight tile is passed once;
`g` and `u` accumulate in float32 and the pieces' rows are added; `h =
silu(g) * u` in float32 is split the same way and multiplied by the `wd`
tile into the float32 output block `[rows, D]`, which stays in VMEM across
the F tiles and across the experts that share its rows, and is written once.
SwiGLU is elementwise in F, so the F tiles are independent and nothing of
width F reaches HBM. Rows in the matrices' own dtype go as one piece.

The stacks are taken where they lie: the weights' index maps pick (expert,
F tile) blocks out of `[G, D, F]`, whatever part of the stack a layer's
experts are, and nothing is sliced or copied. The grid runs over the
(expert, row tile) pairs that hold rows, found once a call from
`group_sizes` (`_plan`: two `cumsum`s and a comparison), not over G: an
expert with no rows costs nothing, a row tile no expert of the stack has rows
in is neither read nor written (the caller masks, as after
`grouped_matmul`), and an expert's matrices are read once a row tile it has
rows in: once, unless its rows cross one of the R / `TILE_ROWS` - 1 tile
boundaries. Within a tile the expert's rows are taken `SUB_ROWS` at a time
with the weight tile resident, so an expert with many rows re-reads nothing.

Which widths run the overhang branch (`F % tf` in the kernel: the last
column tile hangs over the matrices' end and is masked): 768 (Keye, Kanana),
one and a half tiles of 512, where the other choice is two steps too; not
1,024 (Kimi, OLMoE), which 512 divides; not 1,280 (Solar), for which
`_column_tile` takes 640, two steps that compute no column twice where three
of 512 would compute 1,536 for 1,280; not 2,688 (Nemotron-H), which goes
whole; and not 2,048 (LongCat-Flash), four tiles of 512. That one is the
widest row the kernel meets, d = 6,144: a weight tile [6144, 512] is 6.3 MB,
the three matrices' double buffers 37.7 MB of `WEIGHT_TILES_BYTES` 40, and
the float32 row and output blocks of 256 rows 6.3 MB each, two buffers of
each, inside `VMEM_LIMIT_BYTES` (a column tile of 1,024 is refused: VMEM).
At 1,536 pairs of which 15 fall on 6 of a layer's 16 held experts a call
takes 0.662 ms where the three grouped matmuls take 1.235 and the touched
matrices' bytes 0.554; column tiles of 256 and 128 take 0.660 and 0.642,
blocks of 128 and 64 rows 0.658 and 0.653: the call is the touched
matrices' DMA whatever the tile, and the tiles stay (PERF.md, PR 55).

The form of two matrices (`wg` None): an expert is `wu` [G, D, F] and `wd`
[G, F, D] and row i of the result `relu(xs[i] @ wu[e])^2 @ wd[e]`
(Nemotron-H's `relu2` experts, whose D is the latent's 1,024 and not the
hidden size): the same grid, plan and pieces, one matrix less a step. At
2,816 rows of which 709 fall on 100 of a layer's 128 held experts a call
takes 1.51 ms on the v5e where the two grouped matmuls take 2.82 and the
touched matrices' bytes 1.35 (PERF.md, PR 53).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of `xs` a grid step's block holds (and of the output block that stays
# in VMEM while the experts with rows in it go by), rows one product takes
# (twice that on the MXU when a row is two pieces), and columns of F a grid
# step takes. On the v5e at Kimi's shape (`benchmarks/expert_mlp_tiles.py` has
# the sweep): 0.830 ms a call; 128 rows a product 0.877 (the MXU's work passes
# the weight tile's DMA), 32 rows 0.844 with 128-row blocks; 256 columns 0.882
# and 128 columns 0.873 (a grid step costs ~1.2 us), 1,024 columns 0.834 for
# 28 MB of weights' buffers; blocks of 128 rows 0.849 (more experts cross a
# block's end and are read twice), 512 rows no better. The buffers at these
# tiles: 14.2 MB of weights, 4.7 of rows, 4.7 of output
TILE_ROWS, SUB_ROWS, TILE_F = 256, 64, 512
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# what the weights' tiles may take of it, two buffers each of three matrices
WEIGHT_TILES_BYTES = 40 * 1024 * 1024
LANES = 128


def _pieces(x, dtype, n: int):
    """x [rows, width] as `n` pieces of `dtype` stacked on the rows: as it
    is, or the two that add up to a wider x."""
    high = x.astype(dtype)
    if n == 1:
        return high
    low = (x - high.astype(x.dtype)).astype(dtype)
    return jnp.concatenate([high, low], axis=0)


def _whole(both, n: int):
    """The pieces' rows of a product, added."""
    if n == 1:
        return both
    rows = both.shape[0] // 2
    return both[:rows] + both[rows:]


def _kernel(group_ref, tile_ref, lo_ref, hi_ref, x_ref, *refs, sub: int,
            tf: int, F: int, pieces: int):
    """One F tile of one expert's rows in one row tile. `refs`: the tiles of
    the matrices into F (`wg` and `wu`, or `wu` alone), `wd`'s, the output
    block."""
    del group_ref
    *up_refs, wd_ref, o_ref = refs
    v, f = pl.program_id(0), pl.program_id(1)
    before = tile_ref[jnp.maximum(v - 1, 0)]

    @pl.when((f == 0) & ((v == 0) | (tile_ref[v] != before)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    lo, hi = lo_ref[v], hi_ref[v]
    dtype = wd_ref.dtype

    def rows(s, carry):
        at = pl.multiple_of(s * sub, sub)
        p = _pieces(x_ref[pl.ds(at, sub), :], dtype, pieces)
        *gate, u = (_whole(jnp.dot(p, w_ref[0],
                                   preferred_element_type=jnp.float32),
                           pieces) for w_ref in up_refs)
        row = at + lax.broadcasted_iota(jnp.int32, u.shape, 0)
        mine = (row >= lo) & (row < hi)
        wd = wd_ref[0]
        if F % tf:
            # the last tile hangs over the matrices' end: what lies there
            # is whatever VMEM held, and 0 x NaN is no 0
            col = f * tf + lax.broadcasted_iota(jnp.int32, u.shape, 1)
            mine &= col < F
            at_f = f * tf + lax.broadcasted_iota(jnp.int32, wd.shape, 0)
            wd = jnp.where(at_f < F, wd, jnp.zeros_like(wd))
        # the other experts' rows, and the rows past the last: nothing
        h = jnp.where(mine, jax.nn.silu(gate[0]) * u if gate
                      else jnp.square(jnp.maximum(u, 0.0)), 0.0)
        o_ref[pl.ds(at, sub), :] += _whole(
            jnp.dot(_pieces(h, dtype, pieces), wd,
                    preferred_element_type=jnp.float32), pieces)
        return carry

    lax.fori_loop(lo // sub, (hi + sub - 1) // sub, rows, 0)


def _plan(group_sizes, first_group, G: int, R: int, tm: int):
    """The grid's (expert, row tile) pairs, in the rows' order: (the expert
    of the stack, the row tile, the expert's first row in the tile and the
    one past its last) a pair, [V] each for the V pairs there can be at
    most, and how many there are. `group_sizes` [G_all] counts all the
    groups the rows are sorted by; the stack holds groups `first_group`..+G
    of them."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    if first_group is None:
        sizes, ends = group_sizes[:G], ends[:G]
    else:
        sizes = lax.dynamic_slice(group_sizes, (first_group,), (G,))
        ends = lax.dynamic_slice(ends, (first_group,), (G,))
    starts = ends - sizes
    first_tile = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    upto = jnp.cumsum(tiles)
    before = upto - tiles
    # a group that has rows is a pair, and one more a tile boundary crossed
    V = min(G, R) + -(-R // tm) - 1
    v = jnp.arange(V, dtype=jnp.int32)
    # pair v is of the one group whose pairs are before..upto: what that
    # group has, as sums over a [V, G] mask (one fusion; a gather each cost
    # 5 us a layer's call)
    here = (before[None, :] <= v[:, None]) & (v[:, None] < upto[None, :])

    def of(a):
        return jnp.sum(jnp.where(here, a[None, :], 0), axis=1)

    tile = of(first_tile - before) + v
    return (of(jnp.arange(G, dtype=jnp.int32)), tile,
            jnp.maximum(of(starts) - tile * tm, 0),
            jnp.minimum(of(ends) - tile * tm, tm)), upto[-1]


def _column_tile(D: int, F: int, itemsize: int, matrices: int = 3) -> int:
    """Columns of F a grid step takes. `TILE_F`, the last tile hanging over
    the matrices' end and masked, for a width of at most two tiles (768:
    Keye's and Kanana's, two steps either way, and what the table above was
    measured at; 1,024 divides). A wider F that `TILE_F` does not divide
    (1,280 = 2.5 tiles: three steps would compute 1,536 columns, a fifth of
    the work for nothing) takes the most whole lane tiles that divide F, at
    least half of `TILE_F`, whose weight tiles (two buffers of each of the
    `matrices`) fit `WEIGHT_TILES_BYTES`, and keeps the overhang where there
    are none: 640 at d = 4,096 in three matrices (two steps, 31.5 MB of
    weights' buffers; 1,280 whole would be 63 MB), and all 2,688 at d =
    1,024 in two (Nemotron-H's experts in their latent: one step an expert,
    22 MB; `benchmarks/expert_mlp_tiles.py --model solar | nemotron` time
    each against the other tiles and the overhang). 2,048 at d = 6,144 in
    three (LongCat-Flash) is the first case's: `TILE_F` divides it, four
    steps an expert, 37.7 MB of weights' buffers (`--model longcat`)."""
    if F <= 2 * TILE_F or F % TILE_F == 0:
        return min(TILE_F, F)
    fits = [n for n in range(TILE_F // 2, F + 1, LANES) if F % n == 0
            and 2 * matrices * D * n * itemsize <= WEIGHT_TILES_BYTES]
    return max(fits, default=TILE_F)


def _tiles(R: int, D: int, F: int, itemsize: int, tiles=None,
           matrices: int = 3) -> tuple:
    """(rows a block, rows a product, columns of F a grid step), cut to the
    problem: a product's rows whole bf16 sublane tiles, a block whole
    products and within the rows where there are that many."""
    tm, sub, tf = tiles or (TILE_ROWS, SUB_ROWS,
                            _column_tile(D, F, itemsize, matrices))
    sub = min(sub, -(-R // 16) * 16)
    tm = max(sub, min(tm, R) // sub * sub)
    return tm, sub, min(tf, F)


def expert_mlp(xs: jax.Array, wg: jax.Array | None, wu: jax.Array,
               wd: jax.Array, group_sizes: jax.Array,
               first_group: jax.Array | None = None, *,
               tiles: tuple | None = None,
               interpret: bool = False) -> jax.Array:
    """xs [R, D] sorted by group, wg, wu [G, D, F], wd [G, F, D],
    group_sizes [G_all] int32 -> [R, D] in xs's dtype; `wg` None is the form
    of two matrices, relu(xs wu)^2 wd. Float32 rows against narrower
    matrices go as two pieces, rows in the matrices' dtype as one;
    accumulation, the hidden lanes and the output block are float32 either
    way.
    With `first_group` (an int32 scalar) the stacks are groups
    first_group..+G of the G_all the rows are sorted by. The rows of the
    other groups come back zero where a group of the stack shares their row
    tile and unwritten elsewhere: the caller masks."""
    R, D = xs.shape
    G, _, F = wu.shape
    ups = (wu,) if wg is None else (wg, wu)
    pieces = 1 if xs.dtype == wu.dtype else 2
    if pieces == 2 and xs.dtype != jnp.float32:
        raise ValueError(f"rows {xs.dtype} against matrices {wu.dtype}")
    tm, sub, tf = _tiles(R, D, F, wu.dtype.itemsize, tiles, len(ups) + 1)
    x = xs if R >= tm else jnp.pad(xs, ((0, tm - R), (0, 0)))
    plan, visits = _plan(group_sizes, first_group, G, R, tm)

    def rows(v, f, group, tile, lo, hi):
        return tile[v], 0

    def up(v, f, group, tile, lo, hi):
        return group[v], 0, f

    def down(v, f, group, tile, lo, hi):
        return group[v], f, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(visits, -(-F // tf)),
        in_specs=[pl.BlockSpec((tm, D), rows),
                  *(pl.BlockSpec((1, D, tf), up) for _ in ups),
                  pl.BlockSpec((1, tf, D), down)],
        out_specs=pl.BlockSpec((tm, D), rows))
    out = pl.pallas_call(
        functools.partial(_kernel, sub=sub, tf=tf, F=F, pieces=pieces),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name="expert_mlp", interpret=interpret,
    )(*plan, x, *ups, wd)
    return out[:R].astype(xs.dtype)
