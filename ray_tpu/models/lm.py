"""Shared language-model loss plumbing used by every model family."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def split_lm_batch(batch: dict):
    """{"tokens": [B,T+1]} or {"inputs","targets"} -> (inputs, targets)."""
    if "tokens" in batch:
        return batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    return batch["inputs"], batch["targets"]


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """logits [B,T,V], targets [B,T] -> each token's negative log
    likelihood [B,T] float32; logits upcast to f32 for the softmax."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy."""
    with jax.named_scope("unembed_loss"):
        return jnp.mean(token_nll(logits, targets))


def chunked_cross_entropy(x: jax.Array, head: jax.Array, targets: jax.Array,
                          chunk: int) -> jax.Array:
    """Fused unembedding + mean cross-entropy over sequence chunks: x
    [B,T,D] the final hidden state (already normed), head [D,V], both in
    the compute dtype. Peak logits memory drops from [B,T,V] to
    [B,chunk,V], forward AND backward (the chunk body is rematerialized).
    Numerically identical to `cross_entropy(x @ head, targets)` (float32
    reductions). The one copy every model family shares."""
    from jax import lax

    from ray_tpu.parallel.mesh import constrain

    B, T, D = x.shape
    if T % chunk:
        raise ValueError(f"seq len {T} not divisible by ce_chunk={chunk}")
    K = T // chunk
    with jax.named_scope("unembed_loss"):
        xc = x.reshape(B, K, chunk, D).swapaxes(0, 1)      # [K, B, C, D]
        tc = targets.reshape(B, K, chunk).swapaxes(0, 1)   # [K, B, C]

        def body(acc, xt):
            xcb, tcb = xt
            logits = constrain(xcb @ head, "batch", "seq", "vocab")
            return acc + jnp.sum(token_nll(logits, tcb)), None

        total, _ = lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                            (xc, tc))
        return total / (B * T)


# the shortest sequence at which the flash kernel beat XLA's dense attention
# on the chip (`resolve_attn_impl`)
FLASH_MIN_SEQ_LEN = 512


def resolve_attn_impl(attn_impl: str, seq_len: int) -> str:
    """Shared auto attention-implementation policy for all model families.

    auto → ring when the active mesh shards the sequence axis; else the
    Pallas flash kernel (`ops/flash_attention.py`) on the `tpu` backend
    from T=512 wherever its tiles divide the sequence (`tiles_divide`: T a
    multiple of 128); XLA's dense attention, which writes `[B, H, T, T]`
    scores to HBM, for shorter and other lengths and on the CPU test
    backend. What the rule reads is what the call can observe: backend,
    mesh, T.

    The crossover is measured (benchmarks/flash_crossover.py on a TPU v5e,
    PR 31; forward + backward of one layer's attention at GPT-2 small's
    heads and 20,480 tokens, ms dense / flash): T=128 1.20 / 4.56, T=256
    2.65 / 3.63, T=512 5.13 / 3.15, T=1024 10.03 / 3.68, T=2048 19.16 /
    5.13; at OLMoE's T=4096 and 128-wide heads the dense path does not fit
    the chip and the kernel takes 15.27. Below 512 a head is a single tile
    and a grid step costs more than the scores it keeps out of HBM.
    """
    if attn_impl != "auto":
        return attn_impl
    import jax

    from ray_tpu.ops.flash_attention import tiles_divide
    from ray_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return "ring"
    if (jax.default_backend() == "tpu" and seq_len >= FLASH_MIN_SEQ_LEN
            and tiles_divide(seq_len)):
        return "flash"
    return "dense"
