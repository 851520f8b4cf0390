"""OLMoE through `ray_tpu.models.moe`: the program against the benchmark's
plain reference (`benchmarks/chip/families/olmoe.py`, which imports nothing
from `ray_tpu.models`) on seeded weights at a tiny size, dropless routing
under skew, the layer against a dense sum over all experts, what float32
in the router and the loss is for and how the cell's check sees it, the
grouped matmul, and the step's `aux` metrics."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if CHIP_DIR not in sys.path:
    sys.path.insert(0, CHIP_DIR)

from families import olmoe as family  # noqa: E402

from ray_tpu.models import moe  # noqa: E402
from ray_tpu.ops.grouped_matmul import _tiling, grouped_matmul  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, build_mesh  # noqa: E402
from ray_tpu.train.spmd import (compile_model_train,  # noqa: E402
                                default_optimizer)

# OLMoE's shape at a tiny size, in the source's key names
MODEL = {"hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
         "num_hidden_layers": 2, "vocab_size": 256,
         "max_position_embeddings": 64, "norm_topk_prob": False,
         "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "tie_word_embeddings": False}
WEIGHTS = {"qk_norm": True, "load_balancing_weight": 0.01,
           "z_loss_weight": 0.001}
VARIANTS = [(False, True), (True, True), (False, False), (True, False)]
IDS = ["olmoe", "norm_topk", "no_qk_norm", "norm_topk_no_qk_norm"]


def setup(norm_topk_prob=False, qk_norm=True, dtype=jnp.float32, seed=0,
          **sizes):
    model = {**MODEL, "norm_topk_prob": norm_topk_prob, **sizes}
    weights = {**WEIGHTS, "qk_norm": qk_norm}
    cfg = family.program_config(model, weights, remat=False, dtype=dtype)
    params = moe.init_params(jax.random.key(seed), cfg)
    # norm scales away from 1, so that a norm left out shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a * (1.0 + 0.1 * jnp.sin(jnp.arange(a.size,
                                                            dtype=a.dtype)
                                                 ).reshape(a.shape))
        if "scale" in jax.tree_util.keystr(path) else a, params)
    tokens = jnp.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], (3, 33)), jnp.int32)
    return model, weights, cfg, params, tokens


def reference_loss(params, tokens, model, weights):
    return family.reference_loss(params, tokens, model, weights)["loss"]


@pytest.mark.parametrize("norm_topk_prob,qk_norm", VARIANTS, ids=IDS)
def test_logits_agree_with_the_reference_in_f32(norm_topk_prob, qk_norm):
    model, _, cfg, params, tokens = setup(norm_topk_prob, qk_norm)
    got = moe.forward(params, tokens[:, :-1], cfg)
    want, _, _ = family.reference_forward(params, tokens[:, :-1], model)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4,
                               rtol=0)


@pytest.mark.parametrize("norm_topk_prob,qk_norm", VARIANTS, ids=IDS)
def test_three_term_loss_and_aux_agree_with_the_reference(norm_topk_prob,
                                                          qk_norm):
    model, weights, cfg, params, tokens = setup(norm_topk_prob, qk_norm)
    loss, aux = moe.loss_fn(params, {"tokens": tokens}, cfg)
    want = family.reference_loss(params, tokens, model, weights)
    assert float(loss) == pytest.approx(float(want["loss"]), abs=2e-5)
    assert float(aux["router_aux_loss"]) == pytest.approx(
        float(want["load_balancing_loss"]), abs=1e-5)
    assert float(aux["router_z_loss"]) == pytest.approx(
        float(want["z_loss"]), abs=1e-5)
    assert float(aux["moe_load_max_over_mean"]) == pytest.approx(
        float(want["load_max_over_mean"]), abs=1e-6)
    assert float(aux["moe_dropped_frac"]) == 0.0
    # the three terms are all there: each weight moves the loss
    ce = float(want["cross_entropy"])
    assert float(loss) - ce == pytest.approx(
        0.01 * float(want["load_balancing_loss"])
        + 0.001 * float(want["z_loss"]), abs=2e-5)


@pytest.mark.parametrize("norm_topk_prob,qk_norm", VARIANTS, ids=IDS)
def test_gradients_of_every_leaf_agree_with_the_reference(norm_topk_prob,
                                                          qk_norm):
    model, weights, cfg, params, tokens = setup(norm_topk_prob, qk_norm)
    got = jax.grad(lambda p: moe.loss_fn(p, {"tokens": tokens}, cfg)[0])(
        params)
    want = jax.grad(reference_loss)(params, tokens, model, weights)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want))
    assert len(flat_got) == len(flat_want) == (15 if qk_norm else 13)
    for path, g in flat_got:
        w = np.asarray(flat_want[path])
        scale = max(float(np.abs(w).max()), 1e-6)
        assert float(np.abs(np.asarray(g) - w).max()) <= 2e-4 * scale + 1e-7, \
            jax.tree_util.keystr(path)
        assert float(np.abs(w).max()) > 0, jax.tree_util.keystr(path)


def test_qk_norm_and_norm_topk_prob_each_change_the_logits():
    """The four variants above are four models, not one."""
    outs = []
    for norm_topk_prob, qk_norm in VARIANTS:
        _, _, cfg, params, tokens = setup(norm_topk_prob, qk_norm)
        outs.append(np.asarray(moe.forward(params, tokens[:, :-1], cfg)))
    for i in range(len(outs)):
        for j in range(i):
            assert np.abs(outs[i] - outs[j]).max() > 1e-3


@pytest.mark.parametrize("batch", [2, 8])
def test_dropless_under_skew(batch):
    """One token id fills the batch: every token of a position goes to
    the same experts. Nothing is dropped, and each row comes out as it
    does alone in a batch (a capacity would cut the later rows)."""
    _, _, cfg, params, _ = setup()
    row = jnp.full((1, 32), 7, jnp.int32)
    alone, aux1 = moe.forward(params, row, cfg, return_aux=True)
    full, aux = moe.forward(params, jnp.tile(row, (batch, 1)), cfg,
                            return_aux=True)
    assert float(aux["dropped_frac"]) == 0.0
    assert float(aux["load_max_over_mean"]) == pytest.approx(
        float(aux1["load_max_over_mean"]))
    np.testing.assert_allclose(np.asarray(full),
                               np.tile(np.asarray(alone), (batch, 1, 1)),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_moe_layer_matches_a_dense_sum_over_all_experts(norm_topk_prob):
    """Every expert on every token, the gate zero outside a token's top
    k: what the sort and the grouped matmuls have to equal."""
    _, _, cfg, params, _ = setup(norm_topk_prob)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"])
    x = jax.random.normal(jax.random.key(3), (3, 16, cfg.d_model))
    out, aux = moe.moe_layer(x, p, cfg)
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    kept, chosen = jax.lax.top_k(probs, cfg.experts_per_token)
    if norm_topk_prob:
        kept = kept / kept.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(chosen, cfg.n_experts) * kept[..., None]).sum(-2)
    every = jnp.einsum("btef,efd->bted", jax.nn.silu(jnp.einsum(
        "btd,edf->btef", x, p["wg"])) * jnp.einsum("btd,edf->btef", x,
                                                    p["wu"]), p["wd"])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jnp.einsum("bted,bte->btd", every, gates)),
        atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(aux["routing"]["experts"]),
                                  np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(aux["routing"]["gates"]),
                               np.asarray(kept), atol=1e-7)


def test_routing_has_one_path_and_no_capacity():
    """No option the published config does not name: nothing to set a
    capacity with, and no second formulation to reach."""
    fields = {f.name for f in dataclasses.fields(moe.MoEConfig)}
    assert not {f for f in fields if "capacity" in f}
    assert not hasattr(moe, "_moe_onehot") and not hasattr(
        moe, "expert_capacity")


# bf16 activations against the float32 reference, d=64, one layer, 1,024
# tokens of seeded weights: measured here over three seeds, the logits
# differ by at most 0.0075 (bf16 keeps 8 bits: 0.4% of activations of order
# 1, through a layer and a 64-wide contraction) and 0.10-0.34% of the
# (token, slot) choices differ, near-ties that the bf16 input of the
# router tips. The router's softmax in bfloat16 tips 0.68-1.12%. The
# router's product in bfloat16 hides behind its bf16 input at this size,
# so `test_the_router_runs_in_float32` holds it to float32 directly.
BF16_LOGIT_TOLERANCE = 0.02
BF16_CHOICES_DIFFER_PCT = 0.5


def _low_route(what):
    from jax import lax

    def route(x2, router, cfg):
        if what == "product_bf16":
            logits = (x2 @ router.astype(jnp.bfloat16)).astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)
        else:
            logits = jnp.dot(x2.astype(jnp.float32), router,
                             precision=lax.Precision.HIGHEST)
            probs = jax.nn.softmax(logits.astype(jnp.bfloat16),
                                   axis=-1).astype(jnp.float32)
        gates, experts = lax.top_k(probs, cfg.experts_per_token)
        return logits, probs, gates, experts

    return route


def _bf16_against_reference(seed=0):
    model, _, cfg, params, _ = setup(dtype=jnp.bfloat16, seed=seed,
                                     num_hidden_layers=1)
    inputs = jnp.asarray(np.random.default_rng(seed).integers(
        0, model["vocab_size"], (16, 64)), jnp.int32)
    got = moe.forward(params, inputs, cfg).astype(jnp.float32)
    want, want_routing, _ = family.reference_forward(params, inputs, model)
    return (float(jnp.abs(got - want).max()), family.choices_differ_pct(
        moe.routing(params, inputs, cfg)["experts"], want_routing["experts"]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_program_is_inside_the_stated_tolerance(seed):
    gap, differ_pct = _bf16_against_reference(seed)
    assert gap <= BF16_LOGIT_TOLERANCE
    assert differ_pct <= BF16_CHOICES_DIFFER_PCT


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_router_softmax_below_float32_is_outside_it(monkeypatch, seed):
    monkeypatch.setattr(moe, "_route", _low_route("softmax_bf16"))
    _, differ_pct = _bf16_against_reference(seed)
    assert differ_pct > BF16_CHOICES_DIFFER_PCT


@pytest.mark.parametrize("what", ["program", "product_bf16", "softmax_bf16"])
def test_the_router_runs_in_float32(what):
    """bf16 activations in, float32 from there: the logits are the exact
    product to float32's last bits, the probabilities sum to one. A
    product or a softmax in bfloat16 misses both by a thousand times."""
    _, _, cfg, params, _ = setup(dtype=jnp.bfloat16)
    router = params["blocks"]["moe"]["router"][0]
    x2 = jax.random.normal(jax.random.key(1), (256, cfg.d_model),
                           jnp.bfloat16) * 4
    route = moe._route if what == "program" else _low_route(what)
    logits, probs, gates, experts = route(x2, router, cfg)
    exact = np.asarray(x2, np.float64) @ np.asarray(router, np.float64)
    logit_err = float(np.abs(np.asarray(logits) - exact).max())
    e = np.exp(exact - exact.max(-1, keepdims=True))
    prob_err = float(np.abs(np.asarray(probs) - e / e.sum(-1, keepdims=True)
                            ).max())
    if what == "program":
        assert logit_err < 1e-6 and prob_err < 1e-7
        assert logits.dtype == probs.dtype == gates.dtype == jnp.float32
    elif what == "product_bf16":
        assert logit_err > 1e-4
    else:
        assert prob_err > 1e-4


@pytest.mark.parametrize("sizes", [[5, 0, 11, 16], [32, 0, 0, 0],
                                   [8, 8, 8, 8]])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    """The Pallas kernel the chip runs (here interpreted) against
    `jax.lax.ragged_dot`, values and both gradients."""
    k1, k2 = jax.random.split(jax.random.key(0))
    lhs = jax.random.normal(k1, (32, 16), jnp.float32)
    rhs = jax.random.normal(k2, (4, 16, 24), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    def f(interpret):
        return lambda a, b: jnp.sum(grouped_matmul(
            a, b, gs, interpret=interpret) ** 2)

    np.testing.assert_allclose(
        np.asarray(grouped_matmul(lhs, rhs, gs, interpret=True)),
        np.asarray(grouped_matmul(lhs, rhs, gs)), atol=1e-4, rtol=1e-4)
    got = jax.grad(f(True), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(f(False), argnums=(0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-3,
                                   rtol=1e-3)


@pytest.mark.parametrize("first", [0, 1, 2])
def test_grouped_matmul_over_a_shard_of_the_groups(first):
    """`rhs` holds two of four groups, from `first` on: their rows are the
    whole product's rows, by the kernel (interpreted) and by `ragged_dot`,
    and so are the gradients once the other groups' rows are masked, as
    `moe._experts` masks them."""
    k1, k2 = jax.random.split(jax.random.key(1))
    lhs = jax.random.normal(k1, (32, 16), jnp.float32)
    rhs = jax.random.normal(k2, (4, 16, 24), jnp.float32)
    gs = jnp.asarray([5, 3, 11, 13], jnp.int32)
    group = jnp.repeat(jnp.arange(4), gs)
    own = ((group >= first) & (group < first + 2))[:, None]
    whole = jnp.where(own, grouped_matmul(lhs, rhs, gs), 0)

    def part(interpret):
        def f(a, b):
            out = grouped_matmul(jnp.where(own, a, 0), b, gs,
                                 jnp.int32(first), interpret=interpret)
            return jnp.where(own, out, 0)
        return f

    def whole_f(a, b):
        return jnp.where(own, grouped_matmul(a, b, gs), 0)

    want = jax.grad(lambda a, b: jnp.sum(whole_f(a, b) ** 2),
                    argnums=(0, 1))(lhs, rhs)
    for interpret in (False, True):
        f = part(interpret)
        np.testing.assert_allclose(np.asarray(f(lhs, rhs[first:first + 2])),
                                   np.asarray(whole), atol=1e-4, rtol=1e-4)
        d_lhs, d_rhs = jax.grad(lambda a, b: jnp.sum(f(a, b) ** 2),
                                argnums=(0, 1))(lhs, rhs[first:first + 2])
        np.testing.assert_allclose(np.asarray(d_lhs), np.asarray(want[0]),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(d_rhs),
                                   np.asarray(want[1][first:first + 2]),
                                   atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("m,k,n,groups,want", [
    (229376, 2048, 1024, 64, (512, 1024, 1024)),  # the cell's gate/up product
    (229376, 1024, 2048, 64, (512, 1024, 1024)),  # and its down product
    (229376, 2048, 1024, 16, (512, 1024, 1024)),  # a shard of the experts
    # few rows a group (serving): 128 rows by the whole contraction
    (192, 2048, 768, 128, (64, 2048, 768)),       # a Kanana decode step
    (192, 768, 2048, 128, (64, 768, 2048)),
    (24576, 2048, 768, 128, (128, 2048, 768)),    # and a chunk step
    (24576, 768, 2048, 128, (128, 768, 2048)),
    (192, 64, 32, 4, (64, 64, 32)), (8, 2048, 1024, 4, (8, 2048, 1024)),
    (192, 4096, 4096, 8, (64, 2048, 1024))])
def test_grouped_matmul_tiles(m, k, n, groups, want):
    assert _tiling(m, k, n, groups) == want


def test_a_loss_with_aux_puts_it_in_the_steps_metrics():
    _, _, cfg, _, tokens = setup()
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    train = compile_model_train(moe, cfg, mesh, optimizer=default_optimizer(
        lr=1e-2, warmup=2, total_steps=30))
    state = train.init_fn(jax.random.key(0))
    losses = []
    for _ in range(6):
        state, metrics = train.step_fn(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
    assert set(metrics) == {"loss", "grad_norm", "step", "router_aux_loss",
                            "router_z_loss", "moe_dropped_frac",
                            "moe_load_max_over_mean"}
    assert float(metrics["moe_dropped_frac"]) == 0.0
    assert 1.0 <= float(metrics["moe_load_max_over_mean"]) <= cfg.n_experts
    assert losses[-1] < losses[0]
    # the split programs take the loss alone
    loss, grads = train.grad_fn(state, {"tokens": tokens})
    assert loss.shape == () and jax.tree.structure(grads) \
        == jax.tree.structure(state.params)


def test_a_loss_without_aux_keeps_the_three_metrics():
    from ray_tpu.models import gpt2

    cfg = gpt2.GPT2Config.preset("gpt2-tiny", max_seq_len=32)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    train = compile_model_train(gpt2, cfg, mesh)
    state = train.init_fn(jax.random.key(0))
    _, metrics = train.step_fn(state, {"tokens": jnp.zeros((2, 17),
                                                           jnp.int32)})
    assert set(metrics) == {"loss", "grad_norm", "step"}


def test_param_counts_are_the_published_ones():
    cfg = moe.MoEConfig.preset("olmoe-1b-7b")
    assert (cfg.n_layer, cfg.qk_norm, cfg.norm_topk_prob) == (16, True, False)
    assert round(moe.num_params(cfg) / 1e9, 2) == 6.92        # "7B"
    assert round(moe.active_params(cfg) / 1e9, 1) == 1.3      # "1B"
    assert moe.MoEConfig.preset("mixtral-8x7b").norm_topk_prob is True
    for qk_norm in (True, False):
        _, _, tiny, params, _ = setup(qk_norm=qk_norm)
        assert sum(x.size for x in jax.tree.leaves(params)) \
            == moe.num_params(tiny)


def _bf16_nll(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.bfloat16), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


@pytest.mark.parametrize("what,outside", [
    ("program", ()), ("product_bf16", ("router_logit_gap",)),
    ("softmax_bf16", ("router_gate_gap",)), ("loss_bf16", ("token_nll_gap",))])
def test_the_cells_check_tells_float32_islands_from_bfloat16(
        monkeypatch, what, outside):
    """What the cell's `correct` holds the program to on the chip
    (`family.FLOAT32_ISLAND_LIMITS`): bf16 activations, and the router's
    product, its softmax and the loss's log-softmax in float32. Each of
    the three run in bfloat16 is outside its limit, token by token."""
    from ray_tpu.models import lm

    if what in ("product_bf16", "softmax_bf16"):
        monkeypatch.setattr(moe, "_route", _low_route(what))
    elif what == "loss_bf16":
        monkeypatch.setattr(lm, "token_nll", _bf16_nll)
    model, _, cfg, params, _ = setup(dtype=jnp.bfloat16, hidden_size=256,
                                     vocab_size=2048)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, model["vocab_size"], (4, 65)), jnp.int32)
    gaps = jax.jit(lambda seen: family.float32_island_gaps(seen, model))(
        jax.jit(lambda p, b: family.program_pass(p, b, cfg))(
            params, {"tokens": tokens}))
    over = {name for name, limit in family.FLOAT32_ISLAND_LIMITS.items()
            if float(gaps[name]) > limit}
    assert over >= set(outside) and (what != "program" or not over), gaps
