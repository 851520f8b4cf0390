"""The Nemotron family file on the CPU: its configuration against the
catalog's row, its `memory` against the arithmetic, its reference against a
second formulation (the Mamba-2 mixer by groups a head at a time in numpy
float64; the expert block a token at a time), its arithmetic against hand
counts, the traffic file, what the cell reads, the reader of the one new
entry on hand-made records, and the cell end to end at a tiny size."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
for _p in (REPO, CHIP_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from families import nemotron as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402

CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "nemotron-3-super-120b-a12b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "agent-reasoning-traces.json"))
CELL = "serve-nemotron-reasoning"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
# the lists the issue names beside every `.decode` reading and `engine_*`
OWN = {"setup_engine_build_s", "moe_router_time_pct.decode",
       "moe_dispatch_time_pct.decode", "moe_experts_time_pct.decode",
       "moe_shared_time_pct", "moe_experts_touched_per_layer",
       "moe_decode_load_max_over_mean", "moe_held_rows_pct",
       "moe_experts_decode_roofline_pct", "ssm_update_time_pct",
       "ssm_conv_time_pct", "ssm_project_time_pct", "ssm_chunk_time_pct",
       "ssm_update_roofline_pct", "gqa_attend_time_pct",
       "gqa_attend_roofline_pct", "gqa_rows_read_pct", "kv_bytes_per_token",
       "state_bytes_per_slot", "moe_latent_time_pct"}
TINY = {"vocab_size": 512, "num_hidden_layers": 3,
        "hybrid_override_pattern": "M*E", "hidden_size": 64,
        "mamba_num_heads": 4, "mamba_head_dim": 16, "ssm_state_size": 8,
        "n_groups": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "moe_intermediate_size": 40, "moe_latent_size": 24,
        "moe_shared_expert_intermediate_size": 48}
# the reference's model at the tiny size: 8 experts of which 4 are held
TINY_MODEL = {**CONFIG["model"], **TINY, "n_routed_experts": 4,
              "num_experts_per_tok": 3, "router_outputs": 8,
              "first_expert": 2, "rows": "float32"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_five_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows
              if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert {k: CONFIG[k] for k in REDUCED} == {
        k: CONFIG["model"][k] for k in REDUCED}
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    m = CONFIG["model"]
    assert (m["num_hidden_layers"], m["hybrid_override_pattern"],
            m["n_routed_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"]) == (11, "MEMEMEM*EME", 128, 32768,
                                               0)
    # the kept layers are the published model's first eleven: a period's
    # 5:5:1, and the published pattern has no dense MLP layer
    published = row["config"]["hybrid_override_pattern"]
    assert published[:11] == m["hybrid_override_pattern"]
    assert [published.count(c) for c in "ME*-"] == [40, 40, 8, 0]
    assert [m["hybrid_override_pattern"].count(c) for c in "ME*"] == [5, 5, 1]
    # every published width unchanged
    assert (m["hidden_size"], m["mamba_num_heads"], m["mamba_head_dim"],
            m["n_groups"], m["ssm_state_size"], m["conv_kernel"]) == (
        4096, 128, 64, 8, 128, 4)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (32, 2, 128)
    assert (m["num_experts_per_tok"], m["routed_scaling_factor"],
            m["moe_intermediate_size"], m["moe_latent_size"],
            m["moe_shared_expert_intermediate_size"]) == (22, 5, 2688, 1024,
                                                          5376)
    # the guide's floors: 8 experts or more, an eighth of the vocabulary
    assert m["n_routed_experts"] >= 8 and m["vocab_size"] * 4 == 131072
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "pipeline_stages", "router_outputs",
        "first_expert", "first_vocab_row")} == {
        "chips_sharing_a_layer": 4, "pipeline_stages": 8,
        "router_outputs": 512, "first_expert": 0, "first_vocab_row": 0}
    assert m["n_routed_experts"] * 4 == share["router_outputs"]
    assert share["pipeline_stages"] * m["num_hidden_layers"] == 88
    assert "5.5 rows a held expert" in share["experts_load"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "nemotron")
    assert CONFIG["deployment"] == {
        "preset": "nemotron-3-super-120b-a12b", "max_seq_len": 4608,
        "max_batch": 128, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 576, "kv_block_size": 128}
    assert {"latent_placement", "no_positions", "gated_norm",
            "time_step_limit", "mamba_init", "weights", "table_spread",
            "intermediate_size", "state_dtype", "state_layout",
            "float32_islands", "tokenizer", "deployment_sizes",
            "kv_blocks"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    # the count that bears the latent's placement, and the module left out
    assert "120.67 B" in CONFIG["assumed"]["latent_placement"]
    assert "12.77 B" in CONFIG["assumed"]["latent_placement"]
    assert "read by nothing" in CONFIG["assumed"]["intermediate_size"]
    assert any("multi-token prediction is not served" in d
               for d in CONFIG["departures"])
    assert any("head's quarter" in d for d in CONFIG["departures"])
    assert "four-chip" in CONFIG["stands_for"]
    assert "eight pipeline stages" in CONFIG["stands_for"]
    bench = spec.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == REDUCED and entry["source"] == CONFIG["source"]
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_memory_block_is_the_arithmetic():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    d = CONFIG["deployment"]
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(d["prefill_chunk_size"])]
    held = max(chunk, memory["decode_step_bytes"]) + memory[
        "prefix_pool_bytes"]
    assert 0.70 * chip <= held <= 0.95 * chip
    slot = memory["state_bytes_per_slot"]
    assert slot == 5 * (128 * 8192 + 3 * 10240) * 4 == 21_585_920
    assert memory["kv_bytes_per_token"] == 2 * 2 * 128 * 2 == 1024
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 16 == 2 * TRAFFIC["documents"]
    assert memory["prefix_pool_bytes"] == (
        snapshots * slot + d["kv_blocks"] * d["kv_block_size"] * 1024)
    assert family.state_bytes_per_slot(CONFIG["model"]) == slot
    assert family.kv_bytes_per_token(CONFIG["model"]) == 1024
    # 9.30 GB of weights, 0.60 of rows, 2.76 of state: the arguments of both
    rows = d["max_batch"] * d["max_seq_len"] * 1024
    state = d["max_batch"] * slot
    weights = memory["arguments_bytes"] - rows - state
    assert rows == 603_979_776 and state == 2_762_997_760
    # bf16 but the routers, W_in's dt columns and the small float32 leaves
    assert weights == pytest.approx(2 * 4_648_163_712, rel=4e-3)
    # neither program holds a copy of a leaf (the state alone is 2.76 GB):
    # the chunk program's temporaries are less than a third of it
    assert chunk - memory["arguments_bytes"] < state // 3


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (
        4096, 32, 2, 128)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state,
            cfg.ssm_conv) == (128, 64, 8, 128, 4)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert) == (512, 22, 128, 0)
    assert (cfg.d_ff_expert, cfg.d_latent, cfg.d_ff_shared) == (2688, 1024,
                                                                5376)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 5.0)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        11, 32768, 4608, 1e-5)
    assert cfg.pattern == "MEMEMEM*EME"
    assert family.CharTokenizer.eos_id == 32767 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 32766, 7])) == [1, 32766, 7]


def test_what_the_file_states_of_the_cache_is_what_the_program_holds():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import serving_family

    stated, d = CONFIG["stated"], CONFIG["deployment"]
    _, module, _ = serving_family(d["preset"])
    cache = jax.eval_shape(lambda: module.init_cache(
        family.program_config(CONFIG), d["max_batch"], d["max_seq_len"]))
    for leaf in ("k", "v"):
        assert list(cache[leaf].shape) == stated["rows_leaf"]
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        assert module.CACHE_TOKEN_AXIS[leaf] == stated[
            "rows_leaf_axes"].index("positions")
    assert list(cache["ssm"].shape) == stated["state_leaf"]
    assert list(cache["conv"].shape) == stated["window_leaf"]
    assert cache["ssm"].dtype == cache["conv"].dtype == jnp.dtype(
        stated["state"])
    assert module.CACHE_STATE == ("ssm", "conv")
    assert family.reference_model(CONFIG)["rows"] == stated["rows"]


def test_the_cell_reads_what_it_reads():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and OWN <= names
    assert names.isdisjoint({"mla_attend_time_pct", "kda_update_time_pct",
                             "mla_attend_roofline_pct"})
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    (own,) = [m for m in bench["per_layer"]
              if m["name"] == "moe_latent_time_pct"]
    assert own == {"name": "moe_latent_time_pct", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "engine programs", "moves": "serve_tokens_per_s",
                   "workloads": [CELL]}
    assert bench["per_layer"][-1] == own and len(bench["per_layer"]) <= 128
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG["name"]
    # the cell is on every list granite's is on but the one that asks for
    # rows pooled without a snapshot
    granites = {m["name"] for m in bench["per_layer"]
                if "serve-granite-docgen" in m.get("workloads", [])}
    assert granites - names == {"rows_without_snapshot_tokens"}
    assert "5.5 rows a held expert" in bench["workloads"][-1]["why"]
    assert len(bench["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 160,
        "requests_per_client": 4, "documents": 8,
        "document_uniform": [1024, 2048], "document_block": 128,
        "question_uniform": [64, 256], "output_uniform": [512, 2048],
        "schedule_seed": 53, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_preambles_the_tasks_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 160 * 4 and plan["clients"] == 160
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        assert 512 <= r["max_tokens"] <= 2048 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 32768
        assert 1024 + 64 <= n <= 2048 + 256
        documents.setdefault(r["document"], []).append(r["prompt_ids"])
    assert sorted(documents) == list(range(8))
    # equally often, and each a whole number of blocks of 128 shared by all
    # its requests, a task of 64-256 after it
    assert {len(v) for v in documents.values()} == {80}
    heads = {}
    for d, prompts in documents.items():
        shared = min(len(p) for p in prompts) - 64
        blocks = shared // 128
        while len({tuple(p[:blocks * 128]) for p in prompts}) > 1:
            blocks -= 1
        heads[d] = prompts[0][:blocks * 128]
        assert 1024 <= blocks * 128 <= 2048
        assert all(64 <= len(p) - blocks * 128 <= 256 for p in prompts)
    assert len(plan["warmup"]) == 9
    for w, d in zip(plan["warmup"], list(range(8)) + [0]):
        assert w["prompt_ids"][:len(heads[d])] == heads[d]
        assert w["max_tokens"] == 2
    other = closed_loop_documents.generate(TRAFFIC, CONFIG, seed + 1, 51.0)
    assert [(len(r["prompt_ids"]), r["max_tokens"], r["document"])
            for r in requests] == [
        (len(r["prompt_ids"]), r["max_tokens"], r["document"])
        for r in other["requests"]]
    assert requests[0]["prompt_ids"] != other["requests"][0]["prompt_ids"]


def test_roofline_costs_against_hand_counts():
    m = CONFIG["model"]
    one = family.ssm_update_cost(m, 1.0)
    # a slot and layer: 128 heads of S [64, 128] and the window [3, 10240],
    # float32, read and written
    assert one["bytes"] == (128 * 64 * 128 + 3 * 10240) * 4 * 2 == 8_634_368
    assert one["flops"] == 128 * 64 * 128 * 5
    row = family.gqa_attend_cost(m, 1.0)
    assert row["bytes"] == 2 * 2 * 128 * 2 == 1024
    assert row["flops"] == 2 * 32 * 128 * 2
    costs = family.roofline_costs(m)
    expert = costs["moe_experts_per_touched_expert"]
    assert expert == {"bytes": 2 * 1024 * 2688 * 2, "flops": 0.0}  # 11.01 MB
    assert costs["moe_experts_per_row"] == {
        "bytes": 2 * 1024 * 2, "flops": 4 * 1024 * 2688}
    peaks = spec.peaks()["TPU v5 lite"]
    assert _moe_scopes.bound_seconds(one, peaks)[0] == "bytes"
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    # a step's 128 slots, every held expert touched at 5.5 rows: the issue's
    # 1.41 GB a layer of experts and 5.37 GB of state over five layers
    layer = family.moe_experts_cost(m, 128 * 5.5, 128.0)
    assert round(layer["bytes"] / 1e9, 2) == 1.41
    assert _moe_scopes.bound_seconds(layer, peaks)[0] == "bytes"
    assert round(5 * family.ssm_update_cost(m, 128.0)["bytes"] / 1e9,
                 2) == 5.53
    assert costs == {
        "ssm_layers": 5, "ssm_update_per_slot": one, "gqa_layers": 1,
        "gqa_attend_per_position": row, "routed_experts": 128,
        "moe_experts_per_row": costs["moe_experts_per_row"],
        "moe_experts_per_touched_expert": expert}


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, kind: str) -> dict:
    rng = np.random.default_rng([seed, 0x4E4D])

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": (1 + 0.1 * rng.standard_normal(n)).astype(
            np.float32)}

    d, inner, heads, n, groups, f, c, e = 64, 64, 4, 8, 2, 40, 24, 8
    width = inner + 2 * groups * n
    if kind == "mamba":
        return {kind: {"norm": scale(d), "ssm": {
            "w_zx": w(d, inner + width), "w_dt": w(d, heads),
            "w_out": w(inner, d), "dt_bias": w(heads), "a_log": w(heads),
            "d": 1 + w(heads), "conv_w": w(4, width), "conv_b": w(width),
            "norm": scale(inner)}}}
    if kind == "attention":
        return {kind: {"norm": scale(d), "wq": w(d, 4 * 16),
                       "wk": w(d, 2 * 16), "wv": w(d, 2 * 16),
                       "wo": w(4 * 16, d)}}
    return {"moe": {"norm": scale(d), "router": w(d, e),
                    "bias": w(e, std=.1), "w_down": w(d, c),
                    "w_back": w(c, d),
                    "shared": {"w_in": w(d, 48), "w_out": w(48, d)}},
            "experts": {"wu": w(4, c, f), "wd": w(4, f, c)}}


def mamba_by_heads(x, p):
    """The Mamba-2 layer a head and a token at a time, float64: head h reads
    group h // 2's B and C, and the gated norm runs over its group's 32
    lanes."""
    m = {k: (v["scale"] if isinstance(v, dict) else v).astype(np.float64)
         for k, v in p["mamba"]["ssm"].items()}
    x = x.astype(np.float64)
    norm = p["mamba"]["norm"]["scale"].astype(np.float64)
    u = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * norm
    heads, lanes, n, groups, inner = 4, 16, 8, 2, 64
    proj = u @ m["w_zx"]
    z, xbc = proj[:, :inner], proj[:, inner:]
    padded = np.concatenate([np.zeros((3, xbc.shape[1])), xbc])
    conv = m["conv_b"] + sum(m["conv_w"][k] * padded[k:k + len(x)]
                             for k in range(4))
    xbc = conv / (1 + np.exp(-conv))
    xs = xbc[:, :inner].reshape(-1, heads, lanes)
    b = xbc[:, inner:inner + groups * n].reshape(-1, groups, n)
    c = xbc[:, inner + groups * n:].reshape(-1, groups, n)
    dt = np.log1p(np.exp(u @ m["w_dt"] + m["dt_bias"]))
    y = np.zeros((len(x), heads, lanes))
    for h in range(heads):
        g = h // (heads // groups)
        s = np.zeros((lanes, n))
        for t in range(len(x)):
            s = (np.exp(-dt[t, h] * np.exp(m["a_log"][h])) * s
                 + dt[t, h] * np.outer(xs[t, h], b[t, g]))
            y[t, h] = s @ c[t, g] + m["d"][h] * xs[t, h]
    y = y.reshape(len(x), inner) * (z / (1 + np.exp(-z)))
    y = y.reshape(len(x), groups, inner // groups)
    y = y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-5)
    y = y.reshape(len(x), inner) * m["norm"]
    return x + y @ m["w_out"]


@pytest.mark.parametrize("seed", [0, 1])
def test_the_mamba_layer_agrees_with_a_second_formulation(seed):
    p = tiny_layer(seed, "mamba")
    x = np.random.default_rng(seed).standard_normal((1, 19, 64)).astype(
        np.float32)
    got = np.asarray(family.reference_layer(x, p, TINY_MODEL))[0]
    np.testing.assert_allclose(got, mamba_by_heads(x[0], p), atol=3e-5)


def test_the_expert_block_agrees_with_a_token_at_a_time():
    """The router over all 8, the 3 largest of s + bias, the gates s / sum x
    5.0, the held experts 2..5 in the latent, W_back after the sum, the
    shared expert on the hidden size: a token at a time in float64."""
    p = tiny_layer(0, "moe")
    x = np.random.default_rng(0).standard_normal((1, 11, 64)).astype(
        np.float32)
    got = np.asarray(family.reference_layer(x, p, TINY_MODEL))[0]
    m = {k: (v if not isinstance(v, dict) else v) for k, v in p["moe"].items()}
    x64 = x[0].astype(np.float64)
    h = x64 / np.sqrt((x64 * x64).mean(-1, keepdims=True) + 1e-5) \
        * m["norm"]["scale"]
    want = np.zeros_like(x64)
    held_any = []
    for t in range(len(h)):
        s = 1 / (1 + np.exp(-(h[t] @ m["router"])))
        chosen = np.argsort(-(s + m["bias"]))[:3]
        gates = s[chosen] / (s[chosen].sum() + 1e-20) * 5.0
        c = h[t] @ m["w_down"]
        r = np.zeros(24)
        for e, g in zip(chosen, gates):
            if 2 <= e < 6:
                wu, wd = p["experts"]["wu"][e - 2], p["experts"]["wd"][e - 2]
                r += g * (np.maximum(c @ wu, 0) ** 2 @ wd)
        held_any.append(any(2 <= e < 6 for e in chosen))
        shared = np.maximum(h[t] @ m["shared"]["w_in"], 0) ** 2 \
            @ m["shared"]["w_out"]
        want[t] = x64[t] + r @ m["w_back"] + shared
    # relu^2 of weights of spread 0.3: the values reach hundreds
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    assert any(held_any)


@pytest.mark.parametrize("degrade,kind", [
    ("bfloat16_state", "mamba"), ("norm_over_all", "mamba"),
    ("one_group", "mamba"), ("bfloat16_scores", "attention"),
    ("bfloat16_latent", "moe")])
def test_a_degraded_reference_is_another_function(degrade, kind):
    p = tiny_layer(3, kind)
    x = np.random.default_rng(3).standard_normal((2, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, degrade))
    assert np.isfinite(off).all() and np.abs(exact - off).max() > 1e-6
    # and it touches only its own kind of layer
    for other in {"mamba", "attention", "moe"} - {kind}:
        q = tiny_layer(3, other)
        np.testing.assert_array_equal(
            np.asarray(family.reference_layer(x, q, TINY_MODEL)),
            np.asarray(family.reference_layer(x, q, TINY_MODEL, degrade)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, "float8_state")


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "nemotron.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_relu2", "_mamba", "_attention_row",
                 "_expert_block", "reference_layer", "reference_head",
                 "Reference", "reference_model", "ssm_update_cost",
                 "moe_experts_cost", "kv_bytes_per_token",
                 "state_bytes_per_slot", "_layers", "_ssm_inner",
                 "_conv_width"}
    seen = set()
    for node in tree.body:
        name = getattr(node, "name", None)
        if name in reference:
            seen.add(name)
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    module = getattr(sub, "module", None) or ""
                    names = [a.name for a in sub.names]
                    assert not module.startswith("ray_tpu"), (name, module)
                    assert not any(n.startswith("ray_tpu") for n in names)
    assert seen == reference


def test_each_limit_refuses_alone():
    passing = {"served_not_engine_top_share": 0.0,
               "engine_logit_mean_abs": 1e-4, "engine_logit_floor_abs": 1e-5}
    assert family.verdict(passing)["ok"] is True
    assert set(family.LIMITS) == set(passing)
    for name, limit in family.LIMITS.items():
        assert family.verdict({**passing, name: 2 * limit})["ok"] is False
    assert family.verdict({"error": "nothing served"})["ok"] is False
    limits = CONFIG["limits"]
    for name, limit in family.LIMITS.items():
        assert limits[name]["limit"] == limit
    # the floor holds the precision: above every reading of the program
    # with room, and under the nearest precision below what the file states
    floor = limits["engine_logit_floor_abs"]
    assert max(floor["program"] + floor["cell"]) * 2 <= floor["limit"]
    assert floor["limit"] * 2 <= min(floor["refused"]["bfloat16_state"])
    # the mean holds a fault in a minority of the positions and the other
    # mathematics: above the program's widest with room, far under those
    mean = limits["engine_logit_mean_abs"]
    assert max(mean["program"] + mean["cell"]) * 2 <= mean["limit"]
    for other in ("norm_over_all", "one_group"):
        assert mean["limit"] * 3 <= min(mean["refused"][other])


# ------------------------------------------------------------- the reader

def test_the_new_entry_reads_its_number_and_nothing_where_there_is_none():
    """`moe_latent_time_pct` on records with no trace (a parent's, an
    untraced run's): nothing, and no exception; its scope is the
    program's."""
    reader = spec.metric_reader("moe_latent_time_pct")
    assert reader.read({}) is None
    assert reader.read({"trace_dir": None, "counters": None}) is None
    assert reader.read({"trace_dir": "/nonexistent/trace"}) is None
    assert reader._scope_of(
        "jit(_step)/layers/while/body/mlp/moe_latent/dot_general") == \
        "moe_latent"
    assert reader._scope_of(
        "jit(_step)/layers/while/body/mlp/moe_shared/dot_general") is None
    assert reader._scope_of(None) is None
    with open(os.path.join(REPO, "ray_tpu", "models", "nemotron.py")) as f:
        assert 'jax.named_scope("moe_latent")' in f.read()


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_nemotron.py`: the generator, the warm-up, the pool
    hits of both kinds, the engine's counters and `check_served`, through
    the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_nemotron.py"),
         "--workload", CELL, "--seconds", "10", "--seed", "2530000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2       # beside five other workers' tests
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 40
    assert other["state_bytes_per_slot"]["value"] == 3 * (
        16 * 128 + 3 * 192) * 4
    assert other["kv_bytes_per_token"]["value"] == 2 * 2 * 16 * 2
    # 128 of 512 held: a quarter of the pairs, under the seed's skew
    assert 10 < other["moe_held_rows_pct"]["value"] < 45
    assert other["gqa_rows_read_pct"]["value"] >= 100
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("nemotron")
    try:
        with pytest.raises(ValueError, match="no serving family has the "
                                             "preset 'nemotron-3-super"):
            family.program_config(CONFIG)
    finally:
        models._SERVING.update(saved)
