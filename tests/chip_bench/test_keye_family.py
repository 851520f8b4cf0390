"""The Keye family file on the CPU: its configuration against the catalog's
row, its reference against a second formulation written here in numpy
(attention a query at a time over the rows a stable `numpy.argsort` of its
own scores leaves, the experts a token at a time), its arithmetic against
hand counts, the traffic file, the check of what was served (the window's
route, each limit alone), the readers of the new scopes and counters on
hand-made records, the cell end to end at a tiny size, and a later PR's cell
appended to the file as it stands with this one in it."""

import ast
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
for _p in (REPO, CHIP_DIR, os.path.join(CHIP_DIR, "rehearse")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import test_a_tenth_cell as tenth  # noqa: E402
import trace_reduce as tr  # noqa: E402
from cpu_cell_keye import TINY_DEPLOYMENT, TINY_MODEL as TINY  # noqa: E402
from families import keye as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _dsa_scopes, _moe_scopes, _scopes  # noqa: E402
from test_hot_path_metrics import DEVICE, _msg, _plane  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402

CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "keye-vl-2.0-30b-a3b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "long-document-qa.json"))
CELL = "serve-keye-longdoc"
REDUCED = ["num_hidden_layers"]
TINY_MODEL = {**CONFIG["model"], **TINY}
DSA = {"dsa_index_time_pct", "dsa_select_time_pct", "dsa_attend_time_pct",
       "dsa_index_roofline_pct", "dsa_select_roofline_pct",
       "dsa_attend_roofline_pct", "dsa_rows_read_pct"}
OWN = DSA | {"engine_attn_time_pct", "engine_mlp_time_pct",
             "engine_head_time_pct", "engine_prefix_pool_time_pct",
             "moe_router_time_pct.decode", "moe_dispatch_time_pct.decode",
             "moe_experts_time_pct.decode", "moe_experts_decode_roofline_pct",
             "moe_experts_touched_per_layer", "moe_decode_load_max_over_mean",
             "kv_bytes_per_token"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_twice_but_the_depth():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    # every key of the catalog's config at the top level and again under
    # `model`, nested groups whole
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert CONFIG["num_hidden_layers"] == CONFIG["model"][
        "num_hidden_layers"] == 6
    assert CONFIG["published"] == {"num_hidden_layers": 48} == {
        k: row["config"][k] for k in REDUCED}
    # the pattern's period is one layer and no layer is dense: six clear
    # the floor of four
    m = CONFIG["model"]
    assert m["decoder_sparse_step"] == 1 and m["mlp_only_layers"] == []
    assert m["num_hidden_layers"] >= 4
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "keye")
    assert CONFIG["deployment"] == {
        "preset": "keye-vl-2.0-30b-a3b", "max_seq_len": 13312,
        "max_batch": 32, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 416, "kv_block_size": 128}
    # every assumption the issue lists has its reason written down
    assert {"qk_norm", "indexer_input", "indexer_key_norm", "indexer_rotary",
            "indexer_weight_scale", "no_kept_tokens", "chunk_sizes", "mrope",
            "router", "weights", "q_norm_scale", "indexer_spread",
            "cache_dtype_layout", "float32_islands", "tokenizer",
            "routing_load", "deployment_sizes"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    assert "first of eight pipeline stages" in CONFIG["stands_for"]
    assert any("vision tower is absent" in d for d in CONFIG["departures"])
    bench = spec.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == REDUCED and entry["source"] == CONFIG["source"]
    assert entry["file"].endswith(CONFIG["name"] + ".json")


def test_the_compiled_programs_leave_room_on_the_chip():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(CONFIG["deployment"]["prefill_chunk_size"])]
    held = max(chunk, memory["decode_step_bytes"]) + memory[
        "prefix_pool_bytes"]
    # the issue's rule: fewer slots only past 95% with the pool
    assert 0.75 * chip <= held <= 0.95 * chip
    assert memory["decode_step_temp_bytes"] < 2 ** 27  # no leaf, no matrix
    assert memory["kv_bytes_per_token"] == 6 * (2048 + 128) == 13_056
    assert family.kv_bytes_per_token(CONFIG["model"]) == 13_056
    d = CONFIG["deployment"]
    assert memory["prefix_pool_bytes"] == (
        d["kv_blocks"] * d["kv_block_size"] * 13_056)
    rows = d["max_batch"] * d["max_seq_len"] * 13_056
    assert rows == 5_561_647_104 < memory["arguments_bytes"]
    # what is not rows is the weights, 2 bytes a parameter and the float32
    # router and scales
    assert 8.75e9 < memory["arguments_bytes"] - rows < 8.76e9


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.d_ff_expert) == (2048, 32, 4, 128, 768)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.router_scoring,
            cfg.norm_topk_prob) == (128, 8, "softmax", True)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps,
            cfg.rope_theta, cfg.mrope_section) == (
        6, 151936, 13312, 1e-6, 1e7, (16, 24, 24))
    from ray_tpu.models import keye

    assert round(keye.num_params(cfg) / 1e6) == 4375        # 8.75 GB held
    assert family.CharTokenizer.eos_id == 151643 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 151935, 7])) == [1, 151935, 7]


def the_cell_reads_what_it_reads(bench):
    """Holds the cell to what it reads, never to who else reads it: a
    later cell joins an entry's list (`test_a_tenth_cell.py`)."""
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names
    assert "setup_engine_build_s" in names
    # "contains", never "ends with": later PRs append too
    assert OWN <= names
    # no state a slot, no latent rows, no shared expert: their readers
    # would find nothing here
    assert names.isdisjoint({"state_bytes_per_slot", "mla_attend_time_pct",
                             "moe_shared_time_pct", "gqa_attend_time_pct"})
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
            assert spec.metric_reader(m["name"]) is not None
        if m["name"] in DSA:
            assert m["source"] == ("program_counter" if m["name"]
                                   == "dsa_rows_read_pct" else "device_trace")
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    # each new scope's share of the step beside its share of its roofline
    for scope in ("index", "select", "attend"):
        assert layers[f"dsa_{scope}_time_pct"] == layers[
            "moe_experts_time_pct.decode"]
        assert layers[f"dsa_{scope}_roofline_pct"] == layers[
            "moe_experts_decode_roofline_pct"]
    assert len(bench["per_layer"]) <= 128
    (workload,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert "what the choice of 2,048 rows leaves of it" in workload["why"]


def test_the_cell_reads_the_decode_metrics_that_exist_for_it_and_its_own():
    bench = spec.benchmark()
    the_cell_reads_what_it_reads(bench)
    # ISSUE 46's count: ten cells, the seven entries of its own at the end
    assert [w["name"] for w in bench["workloads"]][9] == CELL
    assert len(bench["per_layer"]) <= 115
    own = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in own} == DSA


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 40,
        "requests_per_client": 12, "documents": 4,
        "document_uniform": [8192, 12288], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [256, 512],
        "schedule_seed": 46, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert TRAFFIC["question_uniform"][1] <= d["prefill_chunk_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    # the pool holds every document's rows at their longest, and a
    # question's block each
    assert (TRAFFIC["documents"] * (TRAFFIC["document_uniform"][1]
                                    + d["kv_block_size"])
            <= d["kv_blocks"] * d["kv_block_size"])
    # every decode lane stands past the topk: the selection does its work
    assert TRAFFIC["document_uniform"][0] >= 4 * CONFIG["model"][
        "sa_config"]["topk"]


@pytest.mark.parametrize("seed", [1, 3_046_000_123])
def test_the_documents_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 40 * 12 and plan["clients"] == 40
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        blocks = max(b for b in range(64, 97) if b * 128 <= n - 16)
        assert 16 <= n - blocks * 128 <= 64
        assert 256 <= r["max_tokens"] <= 512 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 151936
        head = tuple(r["prompt_ids"][:blocks * 128])
        assert documents.setdefault(r["document"], head) == head
    assert sorted(documents) == list(range(4))
    assert 8192 <= min(map(len, documents.values()))
    assert max(map(len, documents.values())) <= 12288
    per = [sum(r["document"] == d for r in requests) for d in range(4)]
    assert max(per) - min(per) <= 1                          # stratified
    assert len(plan["warmup"]) == 5
    for w, d in zip(plan["warmup"], [0, 1, 2, 3, 0]):
        assert tuple(w["prompt_ids"][:len(documents[d])]) == documents[d]
        assert w["max_tokens"] == 2
    # the schedule is the file's, the tokens the seed's
    other = closed_loop_documents.generate(TRAFFIC, CONFIG, seed + 1, 51.0)
    assert [(len(r["prompt_ids"]), r["max_tokens"], r["document"])
            for r in requests] == [
        (len(r["prompt_ids"]), r["max_tokens"], r["document"])
        for r in other["requests"]]
    assert requests[0]["prompt_ids"] != other["requests"][0]["prompt_ids"]


def test_roofline_costs_against_hand_counts():
    m = CONFIG["model"]
    position = family.dsa_index_cost(m, 1.0)
    # one indexer key of 64 bf16 values; 16 heads' products over 64, then
    # the ReLU, the weight and the sum
    assert position == {"bytes": 128.0, "flops": 16 * (2 * 64 + 3)}
    score = family.dsa_select_cost(m, 1.0)
    assert score == {"bytes": 4.0, "flops": 1.0}
    row = family.dsa_attend_cost(m, 1.0)
    # a chosen row's key and value for the 4 heads; 32 query heads' scores
    # and weighted values over 128
    assert row == {"bytes": 2048.0, "flops": 4 * 32 * 128}
    # the issue's step: 32 lanes at ~10.5k: 0.26 GB of keys, 0.81 GB of
    # chosen rows where a dense attend would read 4.1 GB
    lanes, at = 32, 10_500
    assert 6 * family.dsa_index_cost(m, lanes * at)["bytes"] == pytest.approx(
        0.258e9, rel=1e-2)
    assert 6 * family.dsa_attend_cost(m, lanes * 2048)["bytes"] == \
        pytest.approx(0.805e9, rel=1e-2)
    assert 6 * family.dsa_attend_cost(m, lanes * at)["bytes"] == \
        pytest.approx(4.13e9, rel=1e-2)
    rows = family.moe_experts_decode_cost(m, 1.0, 0.0)
    expert = family.moe_experts_decode_cost(m, 0.0, 1.0)
    assert expert["bytes"] == 3 * 2048 * 768 * 2            # 9.4 MB
    assert rows["flops"] == 6 * 2048 * 768
    # all 128 experts of the six layers touched: the issue's 7.2 GB
    assert 6 * 128 * expert["bytes"] == pytest.approx(7.25e9, rel=2e-3)
    peaks = spec.peaks()["TPU v5 lite"]
    # all three are bound by the bytes on a v5e
    for cost in (position, score, row):
        assert _moe_scopes.bound_seconds(cost, peaks)[0] == "bytes"
    assert family.roofline_costs(m) == {
        "attention_layers": 6, "routed_experts": 128,
        "moe_experts_per_row": rows,
        "moe_experts_per_touched_expert": expert, "dsa_layers": 6,
        "dsa_index_per_position": position,
        "dsa_select_per_position": score, "dsa_attend_per_row": row}


# --------------------------------------------------------------- reference

def tiny_layer(seed: int) -> dict:
    """A layer of `TINY_MODEL` with every scale and the LayerNorm's bias
    away from their seeded 1 and 0."""
    rng = np.random.default_rng(seed)
    d, H, G, hd, J, e, E, F = 64, 4, 2, 16, 2, 8, 8, 32

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": 1 + w(n, std=0.1)}

    return {"layer": {
        "attn_norm": scale(d), "w_qkv": w(d, (H + 2 * G) * hd),
        "q_norm": {"scale": 2 + w(hd, std=0.1)}, "k_norm": scale(hd),
        "wo": w(H * hd, d),
        "w_index": np.concatenate([
            w(d, J * e), w(d, e), w(d, J),
            np.zeros((d, 128 - J * (e + 1) - e), np.float32)], axis=1),
        "ki_norm": {"scale": 1 + w(e, std=0.1), "bias": w(e, std=0.1)},
        "mlp_norm": scale(d), "router": w(d, E)},
        "experts": {"wg": w(E, d, F), "wu": w(E, d, F), "wd": w(E, F, d)}}


def _f64(tree):
    if isinstance(tree, dict):
        return {k: _f64(v) for k, v in tree.items()}
    return np.asarray(tree, np.float64)


def _norm(v, scale, eps=1e-6):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale


def _turn(v, angle):
    """v [..., p] by angle [p/2]: value i turns with value i + p/2."""
    half = v.shape[-1] // 2
    a, b = v[..., :half], v[..., half:]
    return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle)], -1)


def layer_by_queries(x, p, model, positions=None, topk=None):
    """The layer in float64 numpy, a token at a time: each query's scores
    over the rows before it, the set by `numpy.argsort(kind="stable")` of
    this function's own scores, attention a head at a time over the set,
    the experts a token at a time. None of the reference's code, no block
    of queries, no mask."""
    x, p = np.asarray(x, np.float64), _f64(p)
    layer, sa = p["layer"], model["sa_config"]
    H, G, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    J, e = sa["indexer_num_heads"], sa["indexer_head_dim"]
    topk = topk or sa["topk"]
    theta, seq = float(model["rope_theta"]), len(x)
    if positions is None:
        positions = np.broadcast_to(np.arange(seq), (3, seq))
    stream = np.repeat(np.arange(3), model["rope_scaling"]["mrope_section"])
    inv = theta ** -(np.arange(0, hd, 2) / hd)
    inv_i = theta ** -(np.arange(0, e, 2) / e)
    u = _norm(x, layer["attn_norm"]["scale"])
    qkv, iq = u @ layer["w_qkv"], u @ layer["w_index"]
    q, k, v, qi, ki = [], [], [], [], []
    for t in range(seq):
        angle = positions[stream, t] * inv
        q.append(_turn(_norm(qkv[t, :H * hd].reshape(H, hd),
                             layer["q_norm"]["scale"]), angle))
        k.append(_turn(_norm(qkv[t, H * hd:(H + G) * hd].reshape(G, hd),
                             layer["k_norm"]["scale"]), angle))
        v.append(qkv[t, (H + G) * hd:].reshape(G, hd))
        angle_i = positions[0, t] * inv_i
        qi.append(_turn(iq[t, :J * e].reshape(J, e), angle_i))
        key = iq[t, J * e:(J + 1) * e]
        key = (key - key.mean()) / np.sqrt(key.var() + 1e-6)
        ki.append(_turn(key * layer["ki_norm"]["scale"]
                        + layer["ki_norm"]["bias"], angle_i))
    k, v, ki = np.stack(k), np.stack(v), np.stack(ki)
    weight = iq[:, (J + 1) * e:(J + 1) * e + J] / np.sqrt(J * e)
    out = np.zeros((seq, H, hd))
    for t in range(seq):
        index = np.maximum(ki[:t + 1] @ qi[t].T, 0.0) @ weight[t]
        chosen = np.sort(np.argsort(-index, kind="stable")[:topk])
        for h in range(H):
            g = h // (H // G)
            s = k[chosen, g] @ q[t][h] / np.sqrt(hd)
            a = np.exp(s - s.max())
            out[t, h] = (a / a.sum()) @ v[chosen, g]
    x = x + out.reshape(seq, -1) @ layer["wo"]
    e_, routed = p["experts"], np.zeros_like(x)
    for t, h in enumerate(_norm(x, layer["mlp_norm"]["scale"])):
        logits = h @ layer["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        best = np.argsort(-probs, kind="stable")[:model[
            "num_experts_per_tok"]]
        for gate, i in zip(probs[best] / probs[best].sum(), best):
            g_, u_ = h @ e_["wg"][i], h @ e_["wu"][i]
            routed[t] += gate * ((g_ / (1 + np.exp(-g_)) * u_) @ e_["wd"][i])
    return x + routed


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("streams", ["text", "image"])
def test_reference_agrees_with_a_second_formulation(streams, seed):
    """40 tokens under a topk of 16: queries below, at and past it; with an
    image's unequal position streams too."""
    p = tiny_layer(seed)
    rng = np.random.default_rng(seed + 10)
    x = rng.standard_normal((40, 64)).astype(np.float32)
    positions = None
    if streams == "image":
        positions = np.stack([np.arange(40), rng.integers(0, 9, 40),
                              rng.integers(0, 9, 40)])
    got = np.asarray(family.reference_layer(
        x, p, TINY_MODEL, None if positions is None else
        __import__("jax").numpy.asarray(positions)))
    want = layer_by_queries(x, p, TINY_MODEL, positions)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("degrade", family.DEGRADE[1:])
def test_a_degraded_reference_is_another_function(degrade):
    p = tiny_layer(3)
    x = np.random.default_rng(4).standard_normal((40, 64)).astype(np.float32)
    plain = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL,
                                            degrade=degrade))
    if degrade == "bfloat16_scores":
        # another set only where two scores within 0.4% of each other lie
        # across the boundary (`tests/test_keye_serving.py` plants that)
        assert off.shape == plain.shape
    else:
        assert np.abs(off - plain).max() > 1e-3
    if degrade == "half_topk":
        np.testing.assert_allclose(
            off, layer_by_queries(x, p, TINY_MODEL, topk=8), rtol=2e-4,
            atol=2e-4)
    # below the topk (the window's, and the half's) nothing is left out
    if degrade in ("dense_attend", "window", "half_topk"):
        np.testing.assert_array_equal(off[:8], plain[:8])
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, degrade="approx_max_k")


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "keye.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_layer_norm", "_rotate", "_angles",
                 "_mrope_angles", "_selected", "_attention", "_expert_block",
                 "reference_layer", "reference_head", "Reference",
                 "dsa_index_cost", "dsa_select_cost", "dsa_attend_cost",
                 "kv_bytes_per_token"}
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


# ------------------------------------------------------------ what decides

def tiny_config() -> dict:
    config = json.loads(json.dumps(CONFIG))
    config["model"].update(TINY)
    config["deployment"].update(TINY_DEPLOYMENT)
    return config


@pytest.fixture(scope="module")
def served():
    """What a busy engine served: four greedy replies, prompts of 52-61
    tokens sharing two documents of 48 (three times the tiny topk), through
    `LLMEngine.generate`."""
    from ray_tpu.serve.llm import LLMEngine

    config = tiny_config()
    rng = np.random.default_rng(7)
    heads = [rng.integers(1, 512, 48).tolist() for _ in range(2)]
    prompts = [heads[i % 2] + rng.integers(1, 512, 4 + 3 * i).tolist()
               for i in range(4)]
    eng = LLMEngine(**family.engine_options(config, 11))
    try:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(4) as pool:
            replies = list(pool.map(lambda p: eng.generate(
                prompt_ids=p, max_tokens=14)["token_ids"], prompts))
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    # what the indexer scored and what attention then read, counted on the
    # device: every lane of these stood past the topk of 16
    assert 0 < stats["rows_selected"] < stats["positions_indexed"]
    assert stats["moe_expert_rows"] % 3 == 0
    return config, [{"id": i, "prompt_ids": p, "token_ids": r}
                    for i, (p, r) in enumerate(zip(prompts, replies))]


def test_check_served_passes_what_a_busy_engine_served_and_refuses_others(
        served):
    config, replies = served
    good = family.check_served(config, 11, replies)
    assert good["ok"] is True
    assert good["tokens_checked"] == sum(len(r["token_ids"]) for r in replies)
    assert good["served_not_engine_top_share"] == 0.0
    assert good["engine_logit_mean_abs"] <= family.ENGINE_LOGIT_MEAN_ABS_LIMIT
    assert family.check_served(config, 11, [])["ok"] is False
    # another seed's weights did not choose these tokens
    assert family.check_served(config, 12, replies)["ok"] is False
    # nor did this engine choose another reply's
    swapped = [{**a, "token_ids": b["token_ids"][:len(a["token_ids"])]}
               for a, b in zip(replies, replies[1:] + replies[:1])]
    assert family.check_served(config, 11, swapped)["ok"] is False


def test_the_checks_engine_takes_the_windows_route(served):
    """Prefill of the whole blocks in one slot, the three leaves' rows
    pooled, a hit copied into another slot, the rest as a chunk, then
    decode: the pool's counters say so, and the logits choose what was
    served."""
    config, replies = served
    eng = family.stopped_engine(config, 11)
    by_route = family.engine_logits(eng, replies[:2])
    stats = eng.kv.stats()
    assert stats["prefix_hits"] == 2 and stats["tokens_reused"] == 2 * 48
    assert stats["blocks_used"] == 2 * 6
    for reply, got in zip(replies[:2], by_route):
        assert got.shape == (len(reply["token_ids"]), 512)
        assert got.argmax(axis=-1).tolist() == reply["token_ids"]


def test_each_limit_refuses_alone():
    ok = {"served_not_engine_top_share":
          0.5 * family.SERVED_NOT_ENGINE_TOP_LIMIT,
          "engine_logit_mean_abs": 0.5 * family.ENGINE_LOGIT_MEAN_ABS_LIMIT}
    assert family.verdict(ok)["ok"] is True
    assert family.verdict({**ok, "served_not_engine_top_share": 1.01
                           * family.SERVED_NOT_ENGINE_TOP_LIMIT})[
        "ok"] is False
    assert family.verdict({**ok, "engine_logit_mean_abs": 1.01
                           * family.ENGINE_LOGIT_MEAN_ABS_LIMIT})[
        "ok"] is False
    assert family.verdict({"error": "non-finite logits"})["ok"] is False


def test_the_limits_stand_between_the_readings_the_file_gives():
    """The configuration file's `limits`: the program's widest reading on
    the chip under each limit, every degradation the limit refuses above
    it, and what no limit can tell from the program said to be so."""
    limits = CONFIG["limits"]
    second = limits["engine_logit_mean_abs"]
    assert second["limit"] == family.ENGINE_LOGIT_MEAN_ABS_LIMIT
    assert limits["served_not_engine_top_share"]["limit"] == \
        family.SERVED_NOT_ENGINE_TOP_LIMIT
    assert max(second["program"]) < second["limit"]
    assert max(limits["served_not_engine_top_share"]["cell"]) < limits[
        "served_not_engine_top_share"]["limit"]
    assert set(second["degraded"]) == set(family.DEGRADE[1:])
    refused = {d for d, r in second["degraded"].items()
               if min(r) > second["limit"]}
    assert refused | set(second["not_told_apart"]) == set(family.DEGRADE[1:])
    assert {"dense_attend", "window", "half_topk"} <= refused
    for d in second["not_told_apart"]:
        assert min(second["degraded"][d]) <= second["limit"]


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,own,old", [
    ("jit(_step)/layers/while/body/attn/dsa_index/dot_general:", "dsa_index",
     "attn"),
    ("jit(_step)/layers/while/body/attn/dsa_index/ln/mul:", "dsa_index",
     "ln"),
    ("jit(_step)/layers/while/body/attn/dsa_select/top_k:", "dsa_select",
     "attn"),
    ("jit(_step)/layers/while/body/attn/dsa_attend/gather:", "dsa_attend",
     "attn"),
    ("jit(_chunk)/layers/while/body/closed_call/while/body/attn/while/body/"
     "dsa_attend/gqt,tgd->gqd/dot_general:", "dsa_attend", "attn"),
    ("jit(_step)/layers/while/body/attn/gqa_project/dot_general:", None,
     "attn"),
    ("jit(_step)/layers/while/body/attn/kv_update/scatter:", None,
     "kv_update"),
    ("jit(_copy_in)/prefix_pool/while/body/dynamic_update_slice:", None,
     "prefix_pool"),
    ("jit(_step)/layers/while/body/mlp/moe_experts/expert_mlp/pallas_call",
     None, "mlp"),
    ("dsa_select", None, "unscoped"), (None, None, "unscoped")])
def test_where_an_operation_belongs(tf_op, own, old):
    """The three new scopes are `attn` (or the inner `ln`) to `_scopes.py`,
    whose shares still sum to 100."""
    assert _dsa_scopes.dsa_scope_of(tf_op) == own
    assert _scopes.scope_of(tf_op) == old
    assert not set(_dsa_scopes.DSA_SCOPES) & _scopes.SCOPES


STEP_OPS = {         # event -> tf_op; 10 ns each
    "%fusion.1 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/dsa_index/dot_general:",
    "%fusion.2 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/dsa_index/ln/mul:",
    "%sort.3 = f32[8]{0} sort()":
        "jit(_step)/layers/while/body/attn/dsa_select/top_k:",
    "%fusion.4 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/dsa_attend/gather:",
    "%fusion.5 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/dsa_attend/gather:",
    "%fusion.6 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/dsa_attend/dot_general:",
    "%fusion.7 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/gqa_project/dot_general:",
    "%expert_mlp.8 = f32[8]{0} custom-call()":
        "jit(_step)/layers/while/body/mlp/moe_experts/expert_mlp/pallas_call",
    "%fusion.9 = f32[8]{0} fusion()": "jit(_step)/unembed_loss/dot_general:",
    "%fusion.10 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kv_update/scatter:"}


@pytest.fixture(scope="module")
def served_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation of
    `STEP_OPS` for 10 ns, and the counters of a window of 10 decode steps of
    32 lanes at 10,000 positions."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(STEP_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, STEP_OPS)))
    d = tmp_path_factory.mktemp("keye_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    positions, rows = 32 * 10_000, 32 * 2048
    # so that a step's least time comes out at 8 ns under `dsa_index` (of
    # 20), at 2 under `dsa_select` (of 10), at 6 under `dsa_attend` (of 30)
    # and at 5 under `moe_experts` (of 10: 50 touched experts a step)
    costs = {"dsa_layers": 6,
             "dsa_index_per_position": {
                 "bytes": 8e-9 * peaks["hbm_bytes_per_s"] / (positions * 6),
                 "flops": 0.0},
             "dsa_select_per_position": {
                 "bytes": 2e-9 * peaks["hbm_bytes_per_s"] / (positions * 6),
                 "flops": 0.0},
             "dsa_attend_per_row": {
                 "bytes": 0.0,
                 "flops": 6e-9 * peaks["bf16_flops_per_s"] / (rows * 6)},
             "attention_layers": 6, "routed_experts": 128,
             "moe_experts_per_row": {"bytes": 0.0, "flops": 0.0},
             "moe_experts_per_touched_expert": {
                 "bytes": 5e-9 * peaks["hbm_bytes_per_s"] / 50,
                 "flops": 0.0}}

    def counts(expert_rows, touched, busiest, indexed, selected, steps):
        return {"decode": {"expert_rows": expert_rows,
                           "experts_touched": touched,
                           "busiest_expert_rows": busiest,
                           "expert_layer_steps": 6 * steps,
                           "positions_indexed": indexed,
                           "rows_selected": selected},
                "chunk": {k: 0 for k in (
                    "expert_rows", "experts_touched", "busiest_expert_rows",
                    "expert_layer_steps", "positions_indexed",
                    "rows_selected")}}

    return {"trace_dir": str(d), "peaks": peaks, "counters": {
        "before": {"engine_steps": 100, "chunk_steps": 0,
                   "total_generated": 1000,
                   "step_counts": counts(100, 10, 3, 2 ** 32 - 8, 7, 0)},
        "after": {"engine_steps": 110, "chunk_steps": 0,
                  "total_generated": 1320,
                  # the uint32 wrapped: 3,200,000 positions more
                  "step_counts": counts(
                      100 + 10 * 6 * 256, 510, 3 + 10 * 6 * 5,
                      10 * positions - 8, 7 + 10 * rows, 10),
                  "kv_bytes_per_token": 13_056, "roofline_costs": costs}}}


@pytest.mark.parametrize("name,want", [
    ("dsa_index_time_pct", 20.0), ("dsa_select_time_pct", 10.0),
    ("dsa_attend_time_pct", 30.0),
    ("moe_experts_time_pct.decode", 10.0),
    ("engine_attn_time_pct", 60.0),       # the three and the projections';
                                          # the indexer's norm is `ln` there
    ("engine_mlp_time_pct", 10.0), ("engine_head_time_pct", 10.0),
    ("kv_update_time_pct.decode", 10.0),
    ("kv_bytes_per_token", 13_056),
    ("dsa_rows_read_pct", 20.48),
    ("dsa_index_roofline_pct", 40.0), ("dsa_select_roofline_pct", 20.0),
    ("dsa_attend_roofline_pct", 20.0),
    ("moe_experts_decode_roofline_pct", 50.0),
    ("moe_experts_touched_per_layer", 500 / 60),
    ("moe_decode_load_max_over_mean", 5 * 128 / 256)])
def test_every_new_entry_reads_its_number(served_record, name, want):
    assert spec.metric_reader(name).read(served_record) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(DSA))
def test_a_program_without_the_scopes_and_counters_reads_as_nothing(
        name, served_record):
    """The parent's engine has neither: None, not 0 and not a crash."""
    parent = {"trace_dir": None, "peaks": served_record["peaks"],
              "counters": {"before": {"engine_steps": 1, "chunk_steps": 0,
                                      "total_generated": 0},
                           "after": {"engine_steps": 9, "chunk_steps": 2,
                                     "total_generated": 90}}}
    read = spec.metric_reader(name).read
    assert read(parent) is None
    assert read({"counters": None}) is None
    assert read({}) is None
    # a traced program that has the scopes and no counters or costs
    if not name.endswith("_time_pct"):
        assert read({**parent,
                     "trace_dir": served_record["trace_dir"]}) is None
        # Kanana's program counts its experts' rows and no position: nothing
        kanana = json.loads(json.dumps(served_record["counters"]))
        for side in kanana.values():
            for program in side["step_counts"].values():
                del program["positions_indexed"], program["rows_selected"]
        assert read({**served_record, "counters": kanana}) is None


def test_a_trace_without_the_scopes_reads_as_nothing(tmp_path):
    """Every other family's programs have none of the three."""
    ops = {"%fusion.1 = f32[8]{0} fusion()":
           "jit(_step)/layers/while/body/attn/dot_general:"}
    space = _msg((1, _plane(DEVICE, {
        tr.OPS_LINE: [(0, 10, next(iter(ops)))],
        tr.MODULES_LINE: [(0, 10, "jit__step(1)")]}, ops)))
    os.makedirs(tmp_path / "plugins" / "profile" / "t")
    (tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(
        space)
    record = {"trace_dir": str(tmp_path)}
    for scope in _dsa_scopes.DSA_SCOPES:
        assert _dsa_scopes.share(record, scope) is None
        assert _dsa_scopes.step_seconds(record, scope) is None


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_keye.py`: the generator, the warm-up, the pool
    hits of three leaves, the engine's counters and `check_served`, through
    the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_keye.py"),
         "--workload", CELL, "--seconds", "6", "--seed", "3046000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # how many replies end in six seconds is the host's to say; the check
    # needs one
    assert line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 80
    assert other["kv_bytes_per_token"]["value"] == 3 * (2 * 2 * 16 + 8) * 2
    # a topk of 16 under lanes at 50-110 positions
    assert 14 < other["dsa_rows_read_pct"]["value"] < 33
    assert 1 <= other["moe_experts_touched_per_layer"]["value"] <= 8
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark has no `ray_tpu.models.keye`:
    `build_app` raises in the phase's own process (`program_config`), so
    the command ends at once with an error; and an engine asked for the
    preset says which preset it does not know."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("keye")
    try:
        with pytest.raises(ValueError, match="keye-vl-2.0-30b-a3b"):
            importlib.import_module("ray_tpu.serve.llm").LLMEngine(
                **family.engine_options(CONFIG, 1))
    finally:
        models._SERVING.update(saved)


# ------------------------------------------------ the cell after this one

# `test_a_tenth_cell.py` holds its copy of the file to ten cells in the same
# test that holds the appended cell to what it reads; with this PR's cell in
# the file the copy has eleven, and `tests/conftest.py` marks that one test
# as expected to fail. These hold what it held, with the count read from the
# file, and this family's cell beside the other four.

@pytest.fixture(scope="module")
def one_more():
    return tenth.with_a_tenth_cell(spec.benchmark())


@pytest.mark.parametrize("name", ["kanana", "brumby", "granite", "kimi",
                                  "keye"])
def test_every_familys_cell_still_reads_what_it_reads(name, one_more):
    importlib.import_module(
        f"test_{name}_family").the_cell_reads_what_it_reads(one_more)


def test_a_later_cell_still_reads_what_the_cell_it_is_like_reads(one_more):
    bench = spec.benchmark()
    assert len(one_more["workloads"]) == len(bench["workloads"]) + 1
    names = [m["name"] for m in spec.cell(one_more,
                                          tenth.TENTH)["per_layer"]]
    like = [m["name"] for m in spec.cell(one_more, tenth.LIKE)["per_layer"]]
    # what the cell it is like reads (less the one entry of the first
    # decode cell alone), then the two of its own and every decode cell's
    assert names[:-3] == like[:-1]
    assert names[-3:] == tenth.APPENDED[:3]
    assert [m["name"] for m in one_more["per_layer"][-4:]] == tenth.APPENDED
    assert len(one_more["per_layer"]) == len(bench["per_layer"]) + 4
    # and none of this PR's seven: the appended cell is like Kimi's
    assert DSA.isdisjoint(names)
    tenth.test_the_files_own_rules_hold_with_the_tenth_cell(one_more)
