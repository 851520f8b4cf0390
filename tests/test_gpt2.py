"""Model correctness: shapes, loss decrease, sharded == single-device."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer

CFG = gpt2.GPT2Config.preset("gpt2-tiny", remat=False, dtype=jnp.float32)


def _batch(rng, b=4, t=32):
    return {"tokens": jnp.asarray(rng.integers(0, CFG.vocab_size, (b, t + 1)), jnp.int32)}


def test_forward_shapes():
    params = gpt2.init_params(jax.random.key(0), CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = gpt2.forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()


def test_causality():
    """Changing a future token must not affect earlier logits."""
    params = gpt2.init_params(jax.random.key(0), CFG)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, CFG.vocab_size, (1, 16)), jnp.int32)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % CFG.vocab_size)
    l1 = gpt2.forward(params, toks, CFG)
    l2 = gpt2.forward(params, toks2, CFG)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_loss_decreases_single_device():
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    train = compile_gpt2_train(CFG, mesh, optimizer=default_optimizer(
        lr=1e-2, warmup=2, total_steps=30))
    state = train.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = _batch(rng)
    first = None
    for _ in range(15):
        state, metrics = train.step_fn(state, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first - 0.5, (first, last)


@pytest.mark.parametrize("axes", [dict(dp=8), dict(dp=2, fsdp=2, tp=2),
                                  dict(fsdp=4, tp=2), dict(dp=2, tp=4)])
def test_sharded_matches_single(devices8, axes):
    """Train-step metrics must be identical (up to fp tolerance) under any mesh."""
    batch = _batch(np.random.default_rng(1), b=8, t=32)
    results = []
    for cfg_axes, devs in [(dict(), jax.devices()[:1]), (axes, devices8)]:
        mesh = build_mesh(MeshConfig(**cfg_axes), devices=devs)
        train = compile_gpt2_train(CFG, mesh, optimizer=default_optimizer(
            lr=1e-3, warmup=2, total_steps=10))
        state = train.init_fn(jax.random.key(0))
        bt = jax.device_put(batch["tokens"], train.batch_sharding)
        losses = []
        for _ in range(3):
            state, metrics = train.step_fn(state, {"tokens": bt})
            losses.append(float(metrics["loss"]))
        results.append(losses)
    np.testing.assert_allclose(results[0], results[1], rtol=2e-4, atol=2e-4)


def test_param_specs_structure():
    params = gpt2.init_params(jax.random.key(0), CFG)
    specs = gpt2.param_specs(CFG)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == \
        jax.tree.structure(jax.tree.map(lambda _: 0, specs,
                                        is_leaf=lambda x: not isinstance(x, dict)))


def test_num_params_matches():
    params = gpt2.init_params(jax.random.key(0), CFG)
    actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert actual == gpt2.num_params(CFG)
