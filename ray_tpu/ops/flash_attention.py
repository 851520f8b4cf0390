"""Flash attention as a Pallas TPU kernel (fwd + custom VJP bwd).

Design notes (TPU-first, see /opt/skills/guides/pallas_guide.md):
- grid is (batch, heads, q-blocks); K/V for the whole (b, h) stay in VMEM and
  the kernel walks key blocks with an online-softmax accumulator (running
  max m, normalizer l, f32 accumulator) so scores never materialize in HBM;
- causal masking is positional (broadcasted_iota) and the key-block loop is
  truncated to the causal frontier, skipping ~half the FLOPs;
- matmuls run on the MXU with `preferred_element_type=f32`; softmax math is
  f32 regardless of input dtype;
- backward recomputes scores blockwise (flash-style) from the saved
  logsumexp: a dq kernel gridded over q-blocks and a dk/dv kernel gridded
  over k-blocks.

On the `tpu` backend the kernels are compiled by Mosaic; on the `cpu`
backend — the tests' virtual mesh, and nothing else — the same kernels run
under `interpret=True`, with numerics validated against `mha_reference` in
tests/test_flash_attention.py. Any other backend is an error, not a
fallback. Because K/V stay whole in VMEM, the compiled kernel has a
sequence-length ceiling (`_check_resident_kv`), raised as a clear error at
trace time; tests/test_tpu_compile.py pins where it sits.

The reference framework has no comparable op (attention lives in user
frameworks); this is the TPU-native capability SURVEY.md §5.7 calls out.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# Mosaic's default scoped-VMEM limit on a v5e core
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"flash_attention compiles for the tpu backend and interprets "
            f"on the cpu test backend; {backend!r} is neither")
    return backend == "cpu"


def _check_resident_kv(seq_k: int, head_dim: int, dtype) -> None:
    """The kernels map a head's whole [Tk, Dh] K and V into VMEM: two
    arrays, double-buffered, the minor dim padded to 128 lanes. Refuse here
    what the TPU compiler would refuse later as a scoped-vmem overflow."""
    resident = 4 * seq_k * max(head_dim, 128) * jnp.dtype(dtype).itemsize
    if resident >= _SCOPED_VMEM_BYTES:
        raise ValueError(
            f"flash_attention: sequence length {seq_k} is too long for "
            f"this kernel, which keeps a head's whole K and V "
            f"([{seq_k}, {head_dim}] {jnp.dtype(dtype).name}) in VMEM: "
            f"{resident / 2 ** 20:.0f} MiB resident against a "
            f"{_SCOPED_VMEM_BYTES // 2 ** 20} MiB scoped limit")


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


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                block_q, block_k, seq_k):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [Bq, Dh]
    num_kb = seq_k // block_k
    if causal:
        # only key blocks at or before this q block's causal frontier
        num_kb = jnp.minimum(num_kb, ((iq + 1) * block_q + block_k - 1) // block_k)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(jk, carry):
        m, l, acc = carry
        kb = k_ref[0, 0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if causal:
            k_pos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    Dh = q_ref.shape[-1]
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, Dh), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    # lse carries a trailing unit lane dim: TPU lowering requires the last
    # two block dims be (8k, 128m) or equal to the array dims — (bq, 1)
    # satisfies that where a 3-D (1, bq) block would not
    lse_ref[0, 0] = m + jnp.log(l)


def _fwd(q, k, v, causal, scale, block_q, block_k):
    B, H, Tq, Dh = q.shape
    Tk = k.shape[2]
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        raise ValueError(f"seq lens ({Tq},{Tk}) must divide blocks "
                         f"({block_q},{block_k}); pad the sequence")
    interpret = _interpret()
    if not interpret:
        _check_resident_kv(Tk, Dh, k.dtype)
    grid = (B, H, Tq // block_q)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, seq_k=Tk)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tk, Dh), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tk, Dh), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, Dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, causal, scale, block_q, block_k, seq_k):
    iq = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]                                  # [Bq, 1]
    delta = delta_ref[0, 0]
    num_kb = seq_k // block_k
    if causal:
        num_kb = jnp.minimum(num_kb, ((iq + 1) * block_q + block_k - 1) // block_k)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(jk, dq):
        kb = k_ref[0, 0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(jk * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q * scale, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            k_pos = jk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(0, num_kb, body, dq0)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, causal, scale, block_q, block_k, seq_q):
    jk = pl.program_id(2)
    kb = k_ref[0, 0].astype(jnp.float32)                 # [Bk, Dh]
    vb = v_ref[0, 0].astype(jnp.float32)
    num_qb = seq_q // block_q
    start_qb = (jk * block_k) // block_q if causal else 0
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(iq, carry):
        dk, dv = carry
        qb = q_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        dob = do_ref[0, 0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(iq * block_q, block_q), :]   # [Bq, 1]
        delta = delta_ref[0, 0, pl.ds(iq * block_q, block_q), :]
        s = jax.lax.dot_general(qb * scale, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Bq, Bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dv_new = dv + jax.lax.dot_general(p, dob, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros_like(kb)
    dv0 = jnp.zeros_like(vb)
    dk, dv = jax.lax.fori_loop(start_qb, num_qb, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _bwd(causal, scale, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    B, H, Tq, Dh = q.shape
    Tk = k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    do = g
    # delta_i = rowsum(dO_i * O_i), the softmax-jacobian diagonal term
    # (kept 4-D [B, H, Tq, 1] for the same lane-tiling reason as lse)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq_kernel = functools.partial(_bwd_dq_kernel, causal=causal, scale=scale,
                                  block_q=bq, block_k=bk, seq_k=Tk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, Tq // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Tk, Dh), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tk, Dh), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, causal=causal, scale=scale,
                                   block_q=bq, block_k=bk, seq_q=Tq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, H, Tk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, Tq, Dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, Tq, Dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tq, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Tq, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """Fused causal attention. q,k,v: [B, H, T, Dh] -> [B, H, T, Dh]."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, _ = _fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    out, lse = _fwd(q, k, v, causal, scale, block_q, block_k)
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, scale, block_q, block_k, residuals, g):
    scale = scale if scale is not None else 1.0 / math.sqrt(residuals[0].shape[-1])
    return _bwd(causal, scale, block_q, block_k, residuals, g)


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
