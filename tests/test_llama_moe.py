"""LLaMA + MoE model families: shapes, causality, GQA decode parity,
expert-parallel sharding consistency, loss decrease."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh
from ray_tpu.train.spmd import compile_model_train, default_optimizer

LCFG = llama.LlamaConfig.preset("llama-tiny", remat=False, dtype=jnp.float32)
MCFG = moe.MoEConfig.preset("moe-tiny", remat=False, dtype=jnp.float32)


def _tokens(rng, vocab, b=2, t=16):
    return jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)


# ---------------------------------------------------------------------------
# LLaMA
# ---------------------------------------------------------------------------

def test_llama_forward_shapes():
    params = llama.init_params(jax.random.key(0), LCFG)
    logits = llama.forward(params, jnp.zeros((2, 16), jnp.int32), LCFG)
    assert logits.shape == (2, 16, LCFG.vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()


def test_llama_causality():
    params = llama.init_params(jax.random.key(1), LCFG)
    rng = np.random.default_rng(0)
    toks = _tokens(rng, LCFG.vocab_size, 1, 16)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % LCFG.vocab_size)
    l1 = llama.forward(params, toks, LCFG)
    l2 = llama.forward(params, toks2, LCFG)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               rtol=1e-5, atol=1e-5)


def test_llama_sharded_matches_single(devices8):
    params = llama.init_params(jax.random.key(0), LCFG)
    rng = np.random.default_rng(1)
    toks = _tokens(rng, LCFG.vocab_size, 4, 16)
    ref = np.asarray(llama.forward(params, toks, LCFG).astype(jnp.float32))

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=devices8)
    with use_mesh(mesh):
        fwd = jax.jit(lambda p, t: llama.forward(p, t, LCFG))
        out = np.asarray(fwd(params, toks).astype(jnp.float32))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_llama_loss_decreases():
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    train = compile_model_train(llama, LCFG, mesh, optimizer=default_optimizer(
        lr=1e-2, warmup=2, total_steps=30))
    state = train.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": _tokens(rng, LCFG.vocab_size, 4, 33)}
    losses = []
    for _ in range(12):
        state, m = train.step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9


def test_llama_num_params():
    params = llama.init_params(jax.random.key(0), LCFG)
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == llama.num_params(LCFG)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_forward_shapes_and_aux():
    params = moe.init_params(jax.random.key(0), MCFG)
    logits, aux = moe.forward(params, jnp.zeros((2, 16), jnp.int32), MCFG,
                              return_aux=True)
    assert logits.shape == (2, 16, MCFG.vocab_size)
    assert jnp.isfinite(logits.astype(jnp.float32)).all()
    # load-balance loss for near-uniform routing is ~1.0
    assert 0.5 < float(aux["aux_loss"]) < 4.0
    assert 0.0 <= float(aux["dropped_frac"]) < 0.5


def test_moe_causality():
    params = moe.init_params(jax.random.key(1), MCFG)
    rng = np.random.default_rng(0)
    toks = _tokens(rng, MCFG.vocab_size, 1, 16)
    toks2 = toks.at[0, -1].set((toks[0, -1] + 1) % MCFG.vocab_size)
    l1 = moe.forward(params, toks, MCFG)
    l2 = moe.forward(params, toks2, MCFG)
    np.testing.assert_allclose(np.asarray(l1[:, :-1]), np.asarray(l2[:, :-1]),
                               rtol=1e-4, atol=1e-4)


EP_MESHES = [dict(dp=2, ep=4), dict(ep=4, tp=2), dict(dp=4, ep=2)]
EP_IDS = ["dp2ep4", "ep4tp2", "dp4ep2"]


@pytest.mark.parametrize("axes", EP_MESHES, ids=EP_IDS)
def test_moe_drops_nothing_under_skew_on_an_ep_mesh(devices8, axes):
    """One token id filling the batch sends every token of a position to
    the same experts. There is no capacity to exceed, on any mesh: nothing
    is dropped and the output is the single device's."""
    cfg = moe.MoEConfig.preset("moe-tiny", remat=False, dtype=jnp.float32)
    params = moe.init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((4, 32), jnp.int32)
    ref, ref_aux = moe.forward(params, toks, cfg, return_aux=True)
    assert float(ref_aux["dropped_frac"]) == 0.0
    mesh = build_mesh(MeshConfig(**axes), devices=devices8)
    with use_mesh(mesh):
        out, aux = jax.jit(lambda p, t: moe.forward(
            p, t, cfg, return_aux=True))(params, toks)
    assert float(aux["dropped_frac"]) == 0.0
    assert float(aux["load_max_over_mean"]) == pytest.approx(
        float(ref_aux["load_max_over_mean"]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_moe_expert_parallel_matches_single(devices8):
    """The one routing path on a dp2·ep4 mesh (experts' weights sharded
    over `ep`) against one device: the forward pass and its aux, and a
    whole train step's loss, gradient norm and aux."""
    params = moe.init_params(jax.random.key(0), MCFG)
    rng = np.random.default_rng(1)
    toks = _tokens(rng, MCFG.vocab_size, 4, 17)
    ref, ref_aux = moe.forward(params, toks[:, :-1], MCFG, return_aux=True)

    mesh = build_mesh(MeshConfig(dp=2, ep=4), devices=devices8)
    with use_mesh(mesh):
        fwd = jax.jit(lambda p, t: moe.forward(p, t, MCFG, return_aux=True))
        out, aux = fwd(params, toks[:, :-1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    for k in moe.AUX_KEYS:
        assert float(aux[k]) == pytest.approx(float(ref_aux[k]), abs=1e-5)

    steps = []
    for m in (build_mesh(MeshConfig(), devices=devices8[:1]), mesh):
        train = compile_model_train(moe, MCFG, m,
                                    optimizer=default_optimizer(total_steps=10))
        state = train.init_fn(jax.random.key(0))
        if m is mesh:       # the experts' state really is split over ep
            wg = state.params["blocks"]["moe"]["wg"]
            assert wg.sharding.shard_shape(wg.shape)[1] \
                == MCFG.n_experts // 4
        steps.append(train.step_fn(state, {"tokens": toks})[1])
    for k in steps[0]:
        assert float(steps[1][k]) == pytest.approx(float(steps[0][k]),
                                                   rel=1e-4, abs=1e-5), k


@pytest.mark.parametrize("axes", [dict(dp=2, fsdp=2, tp=2), dict(fsdp=8),
                                  *EP_MESHES[1:]],
                         ids=["dp2fsdp2tp2", "fsdp8", *EP_IDS[1:]])
def test_moe_sharded_matches_single(devices8, axes):
    """The partitioner splits the sort and the grouped matmuls on every
    mesh, and the result is the single device's."""
    params = moe.init_params(jax.random.key(0), MCFG)
    rng = np.random.default_rng(1)
    toks = _tokens(rng, MCFG.vocab_size, 8, 16)
    ref, ref_aux = moe.forward(params, toks, MCFG, return_aux=True)
    mesh = build_mesh(MeshConfig(**axes), devices=devices8)
    with use_mesh(mesh):
        out, aux = jax.jit(lambda p, t: moe.forward(
            p, t, MCFG, return_aux=True))(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert float(aux["dropped_frac"]) == 0.0
    assert float(aux["load_max_over_mean"]) == pytest.approx(
        float(ref_aux["load_max_over_mean"]))


def test_moe_loss_decreases():
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    train = compile_model_train(moe, MCFG, mesh, optimizer=default_optimizer(
        lr=1e-2, warmup=2, total_steps=30))
    state = train.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": _tokens(rng, MCFG.vocab_size, 4, 33)}
    losses = []
    for _ in range(12):
        state, m = train.step_fn(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] * 0.9
