"""Shared language-model loss plumbing used by every model family."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def split_lm_batch(batch: dict):
    """{"tokens": [B,T+1]} or {"inputs","targets"} -> (inputs, targets)."""
    if "tokens" in batch:
        return batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    return batch["inputs"], batch["targets"]


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy; logits upcast to f32 for the softmax."""
    with jax.named_scope("unembed_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def resolve_attn_impl(attn_impl: str, seq_len: int) -> str:
    """Shared auto attention-implementation policy for all model families.

    auto → ring when the active mesh shards the sequence axis; else the
    Pallas flash kernel on the `tpu` backend from T >= 2048, where it no
    longer materializes T² scores; XLA's fused dense attention below that
    and on the CPU test backend. The crossover is a policy, not a current
    measurement: the benchmark re-measures it (ROADMAP S2).
    """
    if attn_impl != "auto":
        return attn_impl
    import jax

    from ray_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return "ring"
    if (jax.default_backend() == "tpu" and seq_len >= 2048
            and seq_len % 128 == 0):
        return "flash"
    return "dense"
