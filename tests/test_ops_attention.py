"""Flash / ring / Ulysses attention numerics + GPT-2 sequence parallelism.

Strategy mirrors the reference's fake-collective CI pattern (SURVEY §4.2
pattern 3): everything runs on the virtual 8-device CPU mesh; the pallas
kernels execute in interpret mode off-TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.flash_attention import flash_attention, mha_reference
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention
from ray_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh


def _qkv(B=2, H=4, T=256, D=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (B, H, T, D), dtype),
            jax.random.normal(kk, (B, H, T, D), dtype),
            jax.random.normal(kv, (B, H, T, D), dtype))


def test_flash_forward_matches_reference():
    q, k, v = _qkv()
    ref = mha_reference(q, k, v)
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_flash_non_causal():
    q, k, v = _qkv(T=128)
    ref = mha_reference(q, k, v, causal=False)
    out = flash_attention(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_flash_grads_match_reference():
    q, k, v = _qkv(T=128)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_flash_rejects_indivisible_seq():
    q, k, v = _qkv(T=130)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, v)


def test_flash_on_mesh_matches_reference(devices8):
    """Under a dp·tp mesh the kernel runs per (batch, heads) shard through
    shard_map (the TPU compiler cannot partition it); values and grads
    equal the dense reference."""
    from ray_tpu.ops.flash_attention import flash_attention_on_mesh

    q, k, v = _qkv(T=128)
    mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=devices8[:4])

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    ref = mha_reference(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    with use_mesh(mesh):
        out = jax.jit(flash_attention_on_mesh)(q, k, v)
        gf = jax.jit(jax.grad(loss(flash_attention_on_mesh),
                              argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_ring_attention_matches_dense(devices8):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v)
    mesh = build_mesh(MeshConfig(sp=8), devices=devices8)
    with use_mesh(mesh):
        out = jax.jit(lambda q, k, v: ring_attention(q, k, v))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_ring_attention_grads(devices8):
    q, k, v = _qkv(T=128)
    mesh = build_mesh(MeshConfig(dp=2, sp=4), devices=devices8)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    with use_mesh(mesh):
        gring = jax.jit(
            jax.grad(loss(ring_attention), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(gring, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=1e-3)


def test_ulysses_matches_dense(devices8):
    q, k, v = _qkv()  # H=4 divisible by sp=4
    ref = mha_reference(q, k, v)
    mesh = build_mesh(MeshConfig(dp=2, sp=4), devices=devices8)
    with use_mesh(mesh):
        out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)


def test_gpt2_sequence_parallel_train_step(devices8):
    """GPT-2 train step with an sp>1 mesh: loss matches the dense-impl loss
    (same params, same batch) and one step runs under ring attention."""
    from ray_tpu.models import gpt2
    from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, (8, 33), dtype=np.int32)

    losses = {}
    for name, axes in [("dense", dict(dp=8)),
                       ("ring", dict(dp=2, sp=2, tp=2))]:
        mesh = build_mesh(MeshConfig(**axes), devices=devices8)
        cfg = gpt2.GPT2Config.preset(
            "gpt2-tiny", vocab_size=256, max_seq_len=64,
            attn_impl="ring" if name == "ring" else "dense")
        prog = compile_gpt2_train(cfg, mesh,
                                  optimizer=default_optimizer(total_steps=4))
        state = prog.init_fn(jax.random.key(0))
        batch = {"tokens": jax.device_put(tokens, prog.batch_sharding)}
        state, metrics = prog.step_fn(state, batch)
        losses[name] = float(metrics["loss"])
        assert np.isfinite(losses[name])
    assert losses["ring"] == pytest.approx(losses["dense"], rel=2e-3)
