"""Grouped matrix multiplication over ragged row groups: the experts' product
of a dropless Mixture-of-Experts layer.

`lhs` [M, K] holds rows sorted by group, `group_sizes` [G] says how many
rows each group has (they sum to at most M), `rhs` [G, K, N] is one matrix
a group; row i of the result is `lhs[i] @ rhs[group of i]`. Work is
proportional to M, whatever the split: no capacity, no padding to the
largest group.

On the `tpu` backend this is JAX's megablox Pallas kernel (`gmm`, with its
custom VJP: `gmm` against the transposed matrices for d lhs, `tgmm` for
d rhs). PR 25 measured it on a v5e at OLMoE's shapes (M=65,536 rows of
2048 -> 1024 and back, 64 groups, forward and backward of the three
products, the f32 -> bf16 casts of the weights included): tiles (512,
1024, 1024) 32.7 ms = 76 TFLOP/s; (512, 512, 512) 39.9 ms; the default
(128, 128, 128) 276 ms; `jax.lax.ragged_dot` (which the TPU compiler
lowers to a grouped-matmul kernel of its own) 42.3 ms, and it names its
operations `ragged-dot-*`, outside every `jax.named_scope`, so a trace
could not lay its time to the layer that called it. (1024, 1024, 1024) and
(512, 2048, 1024) overflow the 16 MiB of scoped VMEM.

Serving meets the same kernel at few rows a group (PR 29, a v5e,
`benchmarks/chip/rehearse/gmm_few_rows.py`: the three products of a
DeepSeek-V3 expert layer, 2048 -> 768 and back over 128 groups, bf16). A
decode step's 192 rows touch 106 groups, whose weights alone take 1.22 ms at
819 GB/s: the training tiles cut to the rows (64, 1024, 768) 1.42 ms; the
whole contraction in one tile (64, 2048, 768) / (64, 768, 2048) 1.38 ms
(88% of the bandwidth); 32, 16 or 8 rows a tile 1.39-1.45; `ragged_dot`
2.10; gathering each row's matrices for an `einsum` 14.9 ms. A chunk step's
24,576 rows (192 a group): (512, 1024, 768) 4.89 ms, (256, 1024, 768) 3.98,
(128, 1024, 768) 4.53, (256, 2048, 768) 3.44, (128, 2048, 768) 3.30,
`ragged_dot` 6.96; at 12,288 rows 4.16, 2.90, 3.10, -, 2.51 and 5.72 (and
(64, 2048, 768) 2.62). A row tile far above a group's rows multiplies
mostly masked rows, and a contraction cut in two reads each row tile
twice: so below `TILE_M` rows a group the tiles are 128 rows by the whole
contraction.

Which regime takes which path (`models/moe.py`, `_one_kernel`): rows in the
matrices' own dtype come here, three calls an expert layer, at any number of
rows a group: training (OLMoE, with the custom VJP) and Kanana's serving
programs. Float32 rows against bf16 matrices at fewer than `TILE_M` rows a
group (Kimi's serving programs) do not since PR 42: there the three calls
were nine, with the rows' two pieces and both pieces' float32 products in
HBM between them, and `ops/expert_mlp.py` does the layer's whole SwiGLU in
one kernel. Float32 rows at many rows a group still come here as two pieces
(`moe._rows_times_experts`). On the `cpu` backend
— the tests' virtual mesh, and nothing else — the same arithmetic is
`jax.lax.ragged_dot`; `interpret=True` runs the kernel itself there
(tests/test_olmoe.py).

Under a mesh the caller runs it per device through `shard_map` (the TPU
compiler does not partition a Mosaic kernel), and a device that holds a
shard of the groups says which with `first_group` (the kernel's
`group_offset`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# rows, contraction, columns: the largest tiles whose double buffers and
# float32 accumulator fit the v5e's scoped VMEM (measured, see above)
TILE_M, TILE_K, TILE_N = 512, 1024, 1024
# below TILE_M rows a group: fewer rows a tile, the whole contraction in
# one, and as many columns as leave the weights' tile at 4 MiB
FEW_ROWS_TILE_M, FEW_ROWS_TILE_K, FEW_ROWS_WEIGHTS = 128, 2048, 2048 * 1024


def _tiling(m: int, k: int, n: int, groups: int) -> tuple:
    """The measured tiles, cut to the problem: the kernel wants M a whole
    number of row tiles (K and N may end in a partial tile)."""
    if m >= groups * TILE_M:
        tm, tk, tn = TILE_M, min(TILE_K, k), min(TILE_N, n)
    else:
        tm, tk = FEW_ROWS_TILE_M, min(FEW_ROWS_TILE_K, k)
        tn = min(n, max(128, FEW_ROWS_WEIGHTS // tk // 128 * 128))
    while m % tm:
        tm //= 2
    return tm, tk, tn


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   first_group: jax.Array | None = None,
                   interpret: bool = False, out_dtype=None) -> jax.Array:
    """lhs [M, K], rhs [G, K, N], group_sizes [G] int32 -> [M, N] in
    lhs's dtype, or in `out_dtype` (float32 accumulation). With `first_group` (an int32
    scalar) `rhs` is a shard, groups first_group..+G of the `group_sizes`
    [G_all] that `lhs` is sorted by; the rows of the other groups come back
    unwritten from the kernel (zero from `ragged_dot`): the caller masks."""
    backend = jax.default_backend()
    out_dtype = out_dtype or lhs.dtype
    if backend == "cpu" and not interpret:
        if first_group is None:
            return lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=out_dtype)
        # one more group in front takes the rows before the shard's
        before = jnp.sum(jnp.where(
            jnp.arange(group_sizes.shape[0]) < first_group, group_sizes, 0))
        own = lax.dynamic_slice(group_sizes, (first_group,), (rhs.shape[0],))
        return lax.ragged_dot(
            lhs, jnp.concatenate([jnp.zeros_like(rhs[:1]), rhs]),
            jnp.concatenate([before[None], own]),
            preferred_element_type=out_dtype)
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"grouped_matmul compiles for the tpu backend and runs as "
            f"ragged_dot on the cpu test backend; {backend!r} is neither")
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = _tiling(lhs.shape[0], lhs.shape[1], rhs.shape[2], rhs.shape[0])
    return gmm(lhs, rhs, group_sizes, out_dtype, tiling, first_group,
               interpret=interpret)
