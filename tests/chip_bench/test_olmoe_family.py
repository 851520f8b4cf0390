"""The OLMoE family file on the CPU: its reference against a per-token
Python loop, its arithmetic against hand counts, its configuration against
the published one, and the readers of the MoE scopes and the attention
kernels on hand-made traces and on the recorded one (which has neither)."""

import math
import os

import numpy as np
import pytest

import trace_reduce as tr
from conftest import CHIP_DIR
from families import olmoe as family
from harness import spec
from metrics import _moe_scopes
from test_hot_path_metrics import DEVICE, ONE_CHIP, _msg, _plane

PUBLISHED = {      # allenai/OLMoE-1B-7B-0125-Instruct config.json
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "olmoe-1b-7b-train-1chip.json"))
CELL = "train-olmoe-4k"
TRACE_READERS = ["moe_router_time_pct", "moe_dispatch_time_pct",
                 "moe_experts_time_pct", "moe_experts_roofline_pct",
                 "flash_attn_roofline_pct"]
COUNTER_READERS = ["moe_load_max_over_mean", "moe_dropped_pct"]


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_published_one_less_depth():
    changed = {k for k in PUBLISHED if CONFIG["model"].get(k) != PUBLISHED[k]}
    assert changed == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert set(CONFIG["model"]) == set(PUBLISHED)
    assert {k: CONFIG[k] for k in PUBLISHED} == CONFIG["model"]
    job = CONFIG["job"]
    assert job["seq_len"] == PUBLISHED["max_position_embeddings"]
    assert job["mesh"] == {"dp": 1}
    assert job["router_losses"] == {"qk_norm": True,
                                    "load_balancing_weight": 0.01,
                                    "z_loss_weight": 0.001}


def test_the_compiled_step_fills_the_chip():
    memory = CONFIG["memory"]
    share = memory["step_program_bytes_compiled_for_v5e"] \
        / memory["chip_bytes_limit"]
    assert memory["chip_bytes_limit"] == 16_909_336_064
    assert 0.80 <= share < 1.0


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG["model"], CONFIG["job"]["router_losses"])
    assert (cfg.d_model, cfg.n_head, cfg.head_dim, cfg.n_kv_head) \
        == (2048, 16, 128, 16)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff) == (64, 8, 1024)
    assert (cfg.vocab_size, cfg.tie_embeddings, cfg.max_seq_len) \
        == (50304, False, 4096)
    assert (cfg.norm_topk_prob, cfg.qk_norm, cfg.n_layer) == (False, True, 1)
    assert (cfg.aux_loss_weight, cfg.z_loss_weight) == (0.01, 0.001)


def test_the_cell_reads_the_train_metrics_and_its_own():
    cell = spec.cell(spec.benchmark(), CELL)
    assert cell["chips"] == 1 and cell["traffic"]["dataset_batches"] == 384
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"train_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert set(TRACE_READERS + COUNTER_READERS) <= names
    assert {"mlp_time_pct", "attn_time_pct", "loss_time_pct",
            "optimizer_time_pct", "train_step_ms", "train_mfu_pct",
            "step_program_gb", "device_idle_pct.train"} <= names
    for name in TRACE_READERS + COUNTER_READERS:
        assert spec.metric_reader(name) is not None


# --------------------------------------------------------------- arithmetic

def test_train_flops_per_token_against_a_hand_count():
    model = {**PUBLISHED, "num_hidden_layers": 1}
    d, e, k, f, v, t = 2048, 64, 8, 1024, 50304, 4096
    layer = 4 * d * d + d * e + k * 3 * d * f       # 67,239,936 weights
    assert layer == 67_239_936
    want = 6 * (layer + v * d) + 12 * d * t
    assert family.train_flops_per_token(model, t) == want == 1_122_238_464
    # 1.122 GFLOP a token at depth 1; the 16-layer job's token
    full = family.train_flops_per_token(PUBLISHED, t)
    assert full == 6 * (16 * layer + v * d) + 12 * 16 * d * t
    assert round(full / 1e9, 2) == 8.68


def test_kernel_costs_against_hand_counts():
    model = {**PUBLISHED, "num_hidden_layers": 1}
    assert family.experts_flops_per_token(model) == 18 * 2048 * 1024 * 8
    cost = family.flash_attention_cost(model, batch=7, seq_len=4096)
    pairs = 7 * 16 * (32 * 33 // 2)          # visited block pairs, causal
    assert cost["flops"] == 18 * pairs * 128 ** 3
    rows = 7 * 16 * 4096
    assert cost["bytes"] == 15 * rows * 128 * 2 + 5 * rows * 4
    # a little over half of what the unmasked convention counts
    assert 0.5 < cost["flops"] / (7 * 12 * 2048 * 4096 * 4096 * 1.5) < 0.52
    which, seconds = _moe_scopes.bound_seconds(
        cost, spec.peaks()["TPU v5 lite"])
    assert which == "flops" and seconds == cost["flops"] / 197e12


# ---------------------------------------------------------------- reference

TOY = {"hidden_size": 16, "intermediate_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 2, "num_experts": 4, "num_experts_per_tok": 2,
       "num_hidden_layers": 2, "vocab_size": 32, "norm_topk_prob": False,
       "rms_norm_eps": 1e-5, "rope_theta": 10000,
       "tie_word_embeddings": False}


def _toy_params(rng, toy=TOY):
    d, f, e, v, layers = (toy[k] for k in (
        "hidden_size", "intermediate_size", "num_experts", "vocab_size",
        "num_hidden_layers"))
    n = lambda *shape: rng.normal(0, 0.3, shape).astype(np.float32)
    scale = lambda size: (1 + 0.2 * rng.normal(size=(layers, size))
                          ).astype(np.float32)
    return {"wte": n(v, d), "lm_head": n(d, v),
            "final_norm": {"scale": scale(d)[0]},
            "blocks": {
                "attn_norm": {"scale": scale(d)},
                "mlp_norm": {"scale": scale(d)},
                "attn": {"wq": n(layers, d, d), "wk": n(layers, d, d),
                         "wv": n(layers, d, d), "wo": n(layers, d, d),
                         "q_norm": {"scale": scale(d)},
                         "k_norm": {"scale": scale(d)}},
                "moe": {"router": n(layers, d, e), "wg": n(layers, e, d, f),
                        "wu": n(layers, e, d, f), "wd": n(layers, e, f, d)}}}


def _loop_forward(params, tokens):
    """OLMoE token by token, head by head, expert by expert, in float64
    numpy: the equations of the family file's docstring and nothing
    shared with its code."""
    p = params
    heads, head, top_k, eps = 2, 8, 2, 1e-5

    def norm(x, scale):
        return x / math.sqrt(float(np.mean(x * x)) + eps) * scale

    def rope(x, pos):                      # x [head]: rotate-half
        half = head // 2
        out = np.empty_like(x)
        for i in range(half):
            angle = pos / 10000 ** (2 * i / head)
            c, s = math.cos(angle), math.sin(angle)
            out[i] = x[i] * c - x[i + half] * s
            out[i + half] = x[i + half] * c + x[i] * s
        return out

    seq = len(tokens)
    xs = [p["wte"][t].astype(np.float64) for t in tokens]
    chosen = []
    for layer in range(2):
        blk = lambda group, name: np.asarray(
            p["blocks"][group][name][layer], np.float64)
        qs, ks, vs = [], [], []
        for pos, x in enumerate(xs):
            h = norm(x, np.asarray(p["blocks"]["attn_norm"]["scale"][layer]))
            q = norm(h @ blk("attn", "wq"),
                     np.asarray(p["blocks"]["attn"]["q_norm"]["scale"][layer]))
            k = norm(h @ blk("attn", "wk"),
                     np.asarray(p["blocks"]["attn"]["k_norm"]["scale"][layer]))
            v = h @ blk("attn", "wv")
            qs.append([rope(q[a * head:(a + 1) * head], pos)
                       for a in range(heads)])
            ks.append([rope(k[a * head:(a + 1) * head], pos)
                       for a in range(heads)])
            vs.append([v[a * head:(a + 1) * head] for a in range(heads)])
        new, picks = [], []
        for pos, x in enumerate(xs):
            mixed = []
            for a in range(heads):
                scores = np.array([qs[pos][a] @ ks[j][a] / math.sqrt(head)
                                   for j in range(pos + 1)])
                w = np.exp(scores - scores.max())
                w /= w.sum()
                mixed.append(sum(w[j] * vs[j][a] for j in range(pos + 1)))
            x = x + np.concatenate(mixed) @ blk("attn", "wo")
            h = norm(x, np.asarray(p["blocks"]["mlp_norm"]["scale"][layer]))
            logits = h @ blk("moe", "router")
            probs = np.exp(logits - logits.max())
            probs /= probs.sum()
            top = sorted(range(4), key=lambda e: -probs[e])[:top_k]
            for e in top:              # the gates as they are
                g = h @ blk("moe", "wg")[e]
                u = h @ blk("moe", "wu")[e]
                x = x + probs[e] * ((g / (1 + np.exp(-g)) * u)
                                    @ blk("moe", "wd")[e])
            new.append(x)
            picks.append(top)
        xs = new
        chosen.append(picks)
    final = np.asarray(p["final_norm"]["scale"], np.float64)
    return (np.stack([norm(x, final) @ p["lm_head"].astype(np.float64)
                      for x in xs]), np.asarray(chosen))


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_agrees_with_a_per_token_loop(seed):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    params = _toy_params(rng)
    tokens = rng.integers(0, 32, (2, 9))
    logits, routing, _ = family.reference_forward(
        params, jnp.asarray(tokens), TOY)
    chosen = routing["experts"]
    for b in range(2):
        want, want_chosen = _loop_forward(params, tokens[b])
        np.testing.assert_allclose(np.asarray(logits[b]), want, atol=2e-5)
        assert [sorted(c) for c in np.asarray(chosen[:, b]).reshape(-1, 2)] \
            == [sorted(c) for c in want_chosen.reshape(-1, 2)]


def test_reference_loss_is_the_three_terms_over_the_whole_batch():
    """Walking a batch in slices and adding the sums gives the loss of
    the whole batch (the load-balancing term is not a mean of slices)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    params = _toy_params(rng)
    tokens = jnp.asarray(rng.integers(0, 32, (4, 10)))
    weights = {"load_balancing_weight": 0.01, "z_loss_weight": 0.001}
    whole = family.reference_loss(params, tokens, TOY, weights)
    parts = [family.reference_sums(params, tokens[i:i + 2], TOY)
             for i in (0, 2)]
    added = family.loss_from_sums(
        {k: parts[0][k] + parts[1][k] for k in parts[0]}, TOY, weights)
    for k in whole:
        assert float(added[k]) == pytest.approx(float(whole[k]), rel=1e-6)
    logits, routing, _ = family.reference_forward(
        params, tokens[:, :-1], TOY)
    router_logits, chosen = routing["logits"], routing["experts"]
    logp = np.asarray(logits) - np.log(np.exp(np.asarray(logits)).sum(
        -1, keepdims=True))
    ce = -np.mean(np.take_along_axis(
        logp, np.asarray(tokens)[:, 1:, None], -1))
    assert float(whole["cross_entropy"]) == pytest.approx(ce, rel=1e-5)
    probs = np.exp(np.asarray(router_logits))
    probs /= probs.sum(-1, keepdims=True)                     # [L, B, T, E]
    load = np.stack([np.bincount(np.asarray(chosen[layer]).ravel(),
                                 minlength=4) for layer in range(2)]) / (
        chosen[0].size)
    balance = np.mean(4 * np.sum(load * probs.mean(axis=(1, 2)), -1))
    assert float(whole["load_balancing_loss"]) == pytest.approx(balance,
                                                                rel=1e-5)
    z = np.mean(np.log(np.exp(np.asarray(router_logits)).sum(-1)) ** 2)
    assert float(whole["z_loss"]) == pytest.approx(z, rel=1e-5)
    assert float(whole["loss"]) == pytest.approx(
        ce + 0.01 * balance + 0.001 * z, rel=1e-6)


def test_token_nll_is_the_log_softmax_at_the_target():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 5, 7)).astype(np.float32)
    targets = rng.integers(0, 7, (2, 5))
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    np.testing.assert_allclose(
        np.asarray(family.token_nll(jnp.asarray(logits), jnp.asarray(targets))),
        -np.take_along_axis(logp, targets[..., None], -1)[..., 0], atol=1e-6)
    assert family.token_nll(jnp.asarray(logits, jnp.bfloat16),
                            jnp.asarray(targets)).dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype,outside", [
    ("float32", set()),
    ("bfloat16", {"router_logit_gap", "router_gate_gap", "token_nll_gap"})])
def test_the_island_limits_tell_the_bfloat16_reference_from_float32(
        dtype, outside):
    """The reference's own pass has no gap; computed in bfloat16
    throughout, its router's product, its softmax and its loss are each
    outside the limit the cell holds the program to."""
    import jax
    import jax.numpy as jnp

    toy = {**TOY, "hidden_size": 128, "intermediate_size": 32,
           "vocab_size": 512, "num_experts": 8}
    rng = np.random.default_rng(5)
    params = _toy_params(rng, toy)
    tokens = jnp.asarray(rng.integers(0, toy["vocab_size"], (2, 65)))
    gaps = jax.jit(lambda seen: family.float32_island_gaps(seen, toy))(
        jax.jit(lambda p, t: family.reference_pass(p, t, toy, dtype))(
            params, tokens))
    assert set(gaps) == set(family.FLOAT32_ISLAND_LIMITS)
    over = {k for k, limit in family.FLOAT32_ISLAND_LIMITS.items()
            if float(gaps[k]) > limit}
    assert over >= outside and (outside or not over), gaps


def test_the_limits_are_the_ones_perf_md_gives_readings_for():
    assert family.TRAIN_LOSS_TOLERANCE == 1.5e-3
    assert family.ROUTING_DIFFER_TOLERANCE_PCT == 1.0
    assert family.FLOAT32_ISLAND_LIMITS == {
        "router_logit_gap": 1e-4, "router_gate_gap": 1e-4,
        "router_choices_differ_pct": 0.02, "token_nll_gap": 2e-3}


def test_choices_differ_pct():
    ref = np.array([[[[0, 1], [2, 3]]]])
    assert family.choices_differ_pct(ref[..., ::-1], ref) == 0.0
    assert family.choices_differ_pct(np.array([[[[0, 2], [2, 3]]]]), ref) \
        == 25.0


def test_the_reference_imports_nothing_from_the_program():
    """The reference's functions import jax, numpy and math; only what
    builds and drives the program (`program_config`, `seeded_params`,
    `TrainProgram`) imports `ray_tpu`."""
    import ast

    with open(os.path.join(CHIP_DIR, "families", "olmoe.py")) as f:
        tree = ast.parse(f.read())
    drives_the_program = {"program_config", "seeded_params", "TrainProgram",
                          "build_train", "program_pass"}
    for node in tree.body:
        name = getattr(node, "name", None)
        imported = {n.module if isinstance(n, ast.ImportFrom) else a.name
                    for n in ast.walk(node)
                    if isinstance(n, (ast.Import, ast.ImportFrom))
                    for a in n.names}
        if name not in drives_the_program:
            assert not any(m and m.startswith("ray_tpu") for m in imported), \
                (name, imported)
    reference = {n.name for n in tree.body if isinstance(n, ast.FunctionDef)}
    assert {"reference_forward", "reference_sums", "loss_from_sums",
            "reference_loss", "sums_of", "token_nll", "reference_pass",
            "float32_island_gaps"} <= reference - drives_the_program


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,moe_scope,flash", [
    ("jit(_step)/jvp(layers)/while/body/closed_call/mlp/moe_experts/"
     "jit(gmm)/pallas_call", "moe_experts", False),
    ("jit(_step)/transpose(jvp(layers))/while/body/closed_call/mlp/"
     "moe_experts/jit(tgmm)/pallas_call", "moe_experts", False),
    ("jit(_step)/jvp(layers)/while/body/mlp/moe_dispatch/sort:",
     "moe_dispatch", False),
    ("jit(_step)/jvp(layers)/while/body/mlp/moe_router/top_k:",
     "moe_router", False),
    ("jit(_step)/jvp(layers)/while/body/closed_call/attn/pallas_call",
     None, True),
    ("jit(_step)/transpose(jvp(layers))/while/body/attn/pallas_call:",
     None, True),
    ("jit(_step)/jvp(layers)/while/body/attn/dot_general:", None, False),
    ("jit(_step)/jvp(layers)/while/body/mlp/dot_general:", None, False),
    ("moe_experts", None, False), ("", None, False), (None, None, False)])
def test_where_an_operation_belongs(tf_op, moe_scope, flash):
    assert _moe_scopes.moe_scope_of(tf_op) == moe_scope
    assert _moe_scopes.is_flash_call(tf_op) is flash


MOE_OPS = {          # event -> tf_op; 10 ns each unless said
    "%gmm.1 = bf16[8,8]{1,0} custom-call()":
        "jit(_step)/jvp(layers)/while/body/mlp/moe_experts/jit(gmm)/"
        "pallas_call",
    "%fusion.2 = bf16[8]{0} fusion()":
        "jit(_step)/jvp(layers)/while/body/mlp/moe_experts/mul:",
    "%sort.3 = s32[8]{0} sort()":
        "jit(_step)/jvp(layers)/while/body/mlp/moe_dispatch/sort:",
    "%fusion.4 = f32[8]{0} fusion()":
        "jit(_step)/jvp(layers)/while/body/mlp/moe_router/top_k:",
    "%attn.5 = bf16[8]{0} custom-call()":
        "jit(_step)/jvp(layers)/while/body/attn/pallas_call",
    "%fusion.6 = f32[8]{0} fusion()": "jit(_step)/optimizer/add:",
    "%fusion.7 = f32[8]{0} fusion()":
        "jit(_step)/jvp(layers)/while/body/mlp/add:",
    "%fusion.8 = f32[8]{0} fusion()":
        "jit(_step)/jvp(layers)/while/body/attn/dot_general:"}


@pytest.fixture(scope="module")
def moe_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation
    of `MOE_OPS` for 10 ns, and a third cut off by the window's end."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(MOE_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, MOE_OPS)))
    d = tmp_path_factory.mktemp("moe_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    return {"trace_dir": str(d), "peaks": peaks, "loop": {"reference_check": {
        "program_routing": {"moe_dropped_frac": 0.0,
                            "moe_load_max_over_mean": 2.5},
        "costs": {"experts": {"flops": 0.5 * 20e-9 * peaks["bf16_flops_per_s"]},
                  "flash_attention": {
                      "flops": 0.1 * 10e-9 * peaks["bf16_flops_per_s"],
                      "bytes": 0.25 * 10e-9 * peaks["hbm_bytes_per_s"]}}}}}


@pytest.mark.parametrize("name,want", [
    ("moe_experts_time_pct", 25.0), ("moe_dispatch_time_pct", 12.5),
    ("moe_router_time_pct", 12.5),
    ("moe_experts_roofline_pct", 50.0),     # 20 ns a step under the scope
    ("flash_attn_roofline_pct", 25.0),      # 10 ns a step; bytes bound it
    ("moe_load_max_over_mean", 2.5), ("moe_dropped_pct", 0.0)])
def test_every_new_entry_reads_its_number(moe_record, name, want):
    assert spec.metric_reader(name).read(moe_record) == pytest.approx(want)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_a_trace_without_the_scopes_reads_as_nothing(name, tmp_path):
    """The recorded GPT-2 step has neither a MoE block nor a Pallas call:
    None, not 0 and not a crash; so does a run that was not traced."""
    d = tmp_path / "plugins" / "profile" / "t"
    os.makedirs(d)
    os.symlink(ONE_CHIP, d / "vm.xplane.pb")
    record = {"trace_dir": str(tmp_path), "peaks": spec.peaks()["TPU v5 lite"],
              "loop": {"reference_check": {}}}
    assert spec.metric_reader(name).read(record) is None
    assert spec.metric_reader(name).read({**record, "trace_dir": None}) is None


@pytest.mark.parametrize("name", COUNTER_READERS)
def test_a_check_without_routing_reads_as_nothing(name):
    """GPT-2's reference check records no routing."""
    record = {"loop": {"reference_check": {"program_loss": 1.0, "ok": True}}}
    assert spec.metric_reader(name).read(record) is None
