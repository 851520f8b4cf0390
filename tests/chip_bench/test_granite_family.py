"""The Granite family file on the CPU: its configuration against the catalog's
row, its reference against the chunked (SSD) form written here in numpy, its
arithmetic against hand counts, the traffic file, the check of what was
served (the window's route, each limit alone), the readers of the new scopes
and counters on hand-made records, and the cell end to end at a tiny size."""

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

import trace_reduce as tr  # noqa: E402
from families import granite as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes, _scopes, _ssm_scopes  # noqa: E402
from test_hot_path_metrics import DEVICE, _msg, _plane  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402

CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "granite-4.0-h-micro-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "doc-grounded-generation.json"))
CELL = "serve-granite-docgen"
TINY = {"vocab_size": 512, "num_hidden_layers": 6,
        "layer_types": ["mamba", "mamba", "attention"] * 2,
        "hidden_size": 64, "shared_intermediate_size": 128,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "mamba_n_heads": 4, "mamba_d_head": 32,
        "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "embedding_multiplier": 12, "residual_multiplier": 0.22,
        "attention_multiplier": 0.015625, "logits_scaling": 8,
        "rms_norm_eps": 1e-5, "hidden_act": "silu", "attention_bias": False,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "normalization_function": "rmsnorm", "num_local_experts": 0}
OWN = {"ssm_update_time_pct", "ssm_conv_time_pct", "ssm_project_time_pct",
       "ssm_chunk_time_pct", "gqa_attend_time_pct", "ssm_update_roofline_pct",
       "gqa_attend_roofline_pct", "rows_without_snapshot_tokens",
       "engine_attn_time_pct", "engine_mlp_time_pct",
       "engine_head_time_pct", "engine_prefix_pool_time_pct",
       "kv_bytes_per_token"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_whole():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["source_url"] == CONFIG["source"]
    # every key of the catalog's config, at the top level and under `model`
    assert CONFIG["model"] == row["config"]
    assert {k: CONFIG[k] for k in row["config"]} == row["config"]
    assert CONFIG["reduced"] == [] and CONFIG["published"] == {}
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "granite")
    assert CONFIG["deployment"] == {
        "preset": "granite-4.0-h-micro", "max_seq_len": 8192,
        "max_batch": 48, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 64,
        "kv_blocks": 384, "kv_block_size": 128}
    # every assumption the issue lists has its reason written down
    assert {"ssm_init", "state_dtype", "state_layout", "float32_islands",
            "weights", "tokenizer", "deployment_sizes", "kv_blocks"} <= set(
        CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    assert any("W_in" in d for d in CONFIG["departures"])
    assert "whole" in CONFIG["stands_for"]
    bench = spec.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]


def test_the_compiled_programs_leave_room_on_the_chip():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(CONFIG["deployment"]["prefill_chunk_size"])]
    held = max(chunk, memory["decode_step_bytes"]) + memory[
        "prefix_pool_bytes"]
    assert 0.75 * chip <= held <= 0.95 * chip
    assert memory["decode_step_temp_bytes"] < 2 ** 27  # no copy of the state
    slot = memory["state_bytes_per_slot"]
    assert slot == 36 * (64 * 64 * 128 + 3 * 4352) * 4 == 77_377_536
    assert memory["kv_bytes_per_token"] == 4 * 2 * 8 * 64 * 2 == 8192
    d = CONFIG["deployment"]
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 6 == TRAFFIC["documents"]
    assert memory["prefix_pool_bytes"] == (
        snapshots * slot + d["kv_blocks"] * d["kv_block_size"] * 8192)
    assert family.state_bytes_per_slot(CONFIG["model"]) == slot
    assert family.kv_bytes_per_token(CONFIG["model"]) == 8192


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff,
            cfg.queries_per_kv) == (2048, 32, 8, 64, 8192, 4)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv,
            cfg.ssm_inner, cfg.conv_width) == (64, 64, 128, 4, 4096, 4352)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len) == (40, 100352,
                                                              8192)
    assert cfg.layer_types == tuple(CONFIG["model"]["layer_types"])
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling, cfg.norm_eps) == (
        12.0, 0.22, 0.015625, 8.0, 1e-5)
    from ray_tpu.models import granite

    assert round(granite.num_params(cfg) / 1e6) == 3191     # whole, 6.38 GB
    assert family.CharTokenizer.eos_id == 100257 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 100351, 7])) == [1, 100351, 7]


def the_cell_reads_what_it_reads(bench):
    """Holds the cell to what it reads, never to who else reads it: a
    later cell joins an entry's list (`test_a_tenth_cell.py`)."""
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names
    # the experts' three can only read null here: the model has none
    assert names.isdisjoint({"moe_router_time_pct.decode",
                             "moe_dispatch_time_pct.decode",
                             "moe_experts_time_pct.decode"})
    # both gauges, for the first time in one cell
    assert {"kv_bytes_per_token", "state_bytes_per_slot",
            "setup_engine_build_s"} <= names
    # "contains", never "ends with": later PRs append too
    assert OWN <= names
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
            assert spec.metric_reader(m["name"]) is not None
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["ssm_update_roofline_pct"] == layers[
        "gqa_attend_roofline_pct"] == layers["mla_attend_roofline_pct"]
    assert layers["rows_without_snapshot_tokens"] == layers[
        "prefix_reuse_pct.decode"]
    assert layers["ssm_update_time_pct"] == layers["mla_attend_time_pct"]
    assert len(bench["per_layer"]) <= 128


def test_the_cell_reads_the_decode_metrics_that_exist_for_it_and_its_own():
    the_cell_reads_what_it_reads(spec.benchmark())


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 64,
        "requests_per_client": 8, "documents": 6,
        "document_uniform": [3072, 6144], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [512, 1024],
        "schedule_seed": 38, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert TRAFFIC["question_uniform"][1] <= d["prefill_chunk_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    # the pool holds every document's rows at their longest
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_documents_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 64 * 8 and plan["clients"] == 64
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        blocks = max(b for b in range(24, 49) if b * 128 <= n - 16)
        assert 16 <= n - blocks * 128 <= 64
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 100352
        head = tuple(r["prompt_ids"][:blocks * 128])
        assert documents.setdefault(r["document"], head) == head
    assert sorted(documents) == list(range(6))
    assert 3072 <= min(map(len, documents.values()))
    assert max(map(len, documents.values())) <= 6144
    per = [sum(r["document"] == d for r in requests) for d in range(6)]
    assert max(per) - min(per) <= 1                          # stratified
    assert len(plan["warmup"]) == 7
    for w, d in zip(plan["warmup"], [0, 1, 2, 3, 4, 5, 0]):
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
    one = family.ssm_update_cost(m, 1.0)
    # a slot and layer: 64 heads of S [64, 128] and the window [3, 4352],
    # float32, read and written
    assert one["bytes"] == (64 * 64 * 128 + 3 * 4352) * 4 * 2 == 4_298_752
    assert one["flops"] == 64 * 64 * 128 * 5
    step = family.ssm_update_cost(m, 48.0)
    assert 36 * step["bytes"] == pytest.approx(7.43e9, rel=2e-3)  # the issue's
    row = family.gqa_attend_cost(m, 1.0)
    assert row["bytes"] == 2 * 8 * 64 * 2 == 2048
    assert row["flops"] == 2 * 32 * 64 * 2
    # 48 slots at ~5,400 positions over the four layers: the issue's 2.1 GB
    assert 4 * 48 * 5400 * row["bytes"] == pytest.approx(2.12e9, rel=2e-3)
    # bound by the bytes on a v5e, both
    peaks = spec.peaks()["TPU v5 lite"]
    assert _moe_scopes.bound_seconds(one, peaks)[0] == "bytes"
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    assert family.roofline_costs(m) == {
        "ssm_layers": 36, "ssm_update_per_slot": one, "gqa_layers": 4,
        "gqa_attend_per_position": row}


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, kind: str) -> dict:
    rng = np.random.default_rng(seed)
    d, ff = 64, 128

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    out = {"mixer_norm": {"scale": 1 + w(d, std=0.1)},
           "mlp_norm": {"scale": 1 + w(d, std=0.1)},
           "mlp": {"w_in": w(d, 2 * ff), "w_out": w(ff, d)}}
    if kind == "attention":
        out["attn"] = {"wq": w(d, 64), "wk": w(d, 32), "wv": w(d, 32),
                       "wo": w(64, d)}
    else:
        out["ssm"] = {"w_zx": w(d, 128 + 160), "w_dt": w(d, 4),
                      "w_out": w(128, d),
                      "dt_bias": rng.uniform(-4.0, -1.0, 4).astype(np.float32),
                      "a_log": np.log(rng.uniform(1, 16, 4)).astype(
                          np.float32),
                      "d": 1 + w(4, std=0.1), "conv_w": w(4, 160),
                      "conv_b": w(160), "norm": {"scale": 1 + w(128, std=0.1)}}
    return out


def mamba_layer_by_chunks(x, p, m, chunk=5):
    """The Mamba-2 layer and its MLP in float64 numpy by the chunked (SSD)
    form: the state in `[H, P, N]` carried from chunk to chunk, the
    quadratic form within one, the convolution by a sliding window. None of
    the reference's code, and not its formulation (a recurrence a token)."""
    x = np.asarray(x, np.float64)
    p = json.loads(json.dumps(p, default=lambda a: np.asarray(a).tolist()))
    s = {k: (np.asarray(v, np.float64) if not isinstance(v, dict)
             else np.asarray(v["scale"], np.float64))
         for k, v in p["ssm"].items()}
    H, P, N = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    K, eps, by = m["mamba_d_conv"], m["rms_norm_eps"], m[
        "residual_multiplier"]
    inner = H * P

    def norm(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale

    def silu(v):
        return v / (1 + np.exp(-v))

    u = norm(x, np.asarray(p["mixer_norm"]["scale"], np.float64))
    zx = u @ s["w_zx"]
    z, xbc = zx[:, :inner], zx[:, inner:]
    dt = np.log1p(np.exp(u @ s["w_dt"] + s["dt_bias"]))          # [T, H]
    seq = len(x)
    conv = np.stack([sum(s["conv_w"][k] * (xbc[t - (K - 1 - k)]
                                           if t - (K - 1 - k) >= 0 else 0.0)
                         for k in range(K)) + s["conv_b"]
                     for t in range(seq)])
    conv = silu(conv)
    xs = conv[:, :inner].reshape(seq, H, P)
    b, c = conv[:, inner:inner + N], conv[:, inner + N:]
    a = -np.exp(s["a_log"])
    state = np.zeros((H, P, N))
    y = np.zeros((seq, H, P))
    for t0 in range(0, seq, chunk):
        t1 = min(seq, t0 + chunk)
        cum = np.cumsum(dt[t0:t1] * a, axis=0)                   # [C, H]
        for i in range(t1 - t0):
            y[t0 + i] = np.exp(cum[i])[:, None] * (state @ c[t0 + i])
            for j in range(i + 1):
                y[t0 + i] += (np.exp(cum[i] - cum[j]) * dt[t0 + j])[:, None] \
                    * xs[t0 + j] * (c[t0 + i] @ b[t0 + j])
        new = np.exp(cum[-1])[:, None, None] * state
        for j in range(t1 - t0):
            new += (np.exp(cum[-1] - cum[j]) * dt[t0 + j])[:, None, None] \
                * xs[t0 + j][:, :, None] * b[t0 + j][None, None, :]
        state = new
    y = y + s["d"][:, None] * xs
    out = norm(y.reshape(seq, inner) * silu(z), s["norm"]) @ s["w_out"]
    x = x + by * out
    h = norm(x, np.asarray(p["mlp_norm"]["scale"], np.float64))
    ab = h @ np.asarray(p["mlp"]["w_in"], np.float64)
    half = ab.shape[-1] // 2
    return x + by * ((silu(ab[:, :half]) * ab[:, half:])
                     @ np.asarray(p["mlp"]["w_out"], np.float64))


def attention_layer_by_rows(x, p, m):
    """The attention layer and its MLP in float64 numpy, a query at a time
    over broadcast heads: no blocks, no grouping of the einsum."""
    x = np.asarray(x, np.float64)
    a = {k: np.asarray(v, np.float64) for k, v in p["attn"].items()}
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    lanes, eps, by = m["hidden_size"] // heads, m["rms_norm_eps"], m[
        "residual_multiplier"]

    def norm(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale

    u = norm(x, np.asarray(p["mixer_norm"]["scale"], np.float64))
    q = (u @ a["wq"]).reshape(-1, heads, lanes)
    k = np.repeat((u @ a["wk"]).reshape(-1, kv, lanes), heads // kv, axis=1)
    v = np.repeat((u @ a["wv"]).reshape(-1, kv, lanes), heads // kv, axis=1)
    out = np.zeros_like(q)
    for t in range(len(x)):
        for h in range(heads):
            s = k[:t + 1, h] @ q[t, h] * m["attention_multiplier"]
            w = np.exp(s - s.max())
            out[t, h] = (w / w.sum()) @ v[:t + 1, h]
    x = x + by * (out.reshape(len(x), -1) @ a["wo"])
    h = norm(x, np.asarray(p["mlp_norm"]["scale"], np.float64))
    ab = h @ np.asarray(p["mlp"]["w_in"], np.float64)
    half = ab.shape[-1] // 2
    return x + by * ((ab[:, :half] / (1 + np.exp(-ab[:, :half]))
                      * ab[:, half:])
                     @ np.asarray(p["mlp"]["w_out"], np.float64))


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_reference_agrees_with_a_second_formulation(kind, seed):
    p = tiny_layer(seed, kind)
    x = np.random.default_rng(seed + 10).standard_normal((2, 19, 64)).astype(
        np.float32)
    got = np.asarray(family.reference_layer(x, p, TINY, kind))
    other = (mamba_layer_by_chunks if kind == "mamba"
             else attention_layer_by_rows)
    for row, want in zip(got, (other(x[0], p, TINY), other(x[1], p, TINY))):
        np.testing.assert_allclose(row, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("degrade,kind", [
    ("bfloat16_state", "mamba"), ("float8_rows", "attention"),
    ("sqrt_scale", "attention")])
def test_a_degraded_reference_is_another_function(degrade, kind):
    p = tiny_layer(3, kind)
    x = np.random.default_rng(4).standard_normal((1, 40, 64)).astype(
        np.float32)
    plain = np.asarray(family.reference_layer(x, p, TINY, kind))
    off = np.asarray(family.reference_layer(x, p, TINY, kind, degrade))
    assert np.abs(off - plain).max() > 1e-3
    # and moves nothing of the other kind of layer
    other = "attention" if kind == "mamba" else "mamba"
    q = tiny_layer(5, other)
    np.testing.assert_array_equal(
        np.asarray(family.reference_layer(x, q, TINY, other)),
        np.asarray(family.reference_layer(x, q, TINY, other, degrade)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY, kind, "float8_state")


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "granite.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_mamba", "_attention", "reference_layer",
                 "reference_head", "Reference", "ssm_update_cost",
                 "gqa_attend_cost", "kv_bytes_per_token",
                 "state_bytes_per_slot", "_layers"}
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
    config["model"].update({k: v for k, v in TINY.items()
                            if k in config["model"]})
    config["deployment"].update({
        "preset": "granite-tiny", "max_seq_len": 128, "max_batch": 4,
        "prefill_chunk_size": 16, "kv_blocks": 48, "kv_block_size": 8})
    return config


@pytest.fixture(scope="module")
def served():
    """What a busy engine served: four greedy replies, prompts of 36-45
    tokens sharing two documents, through `LLMEngine.generate`."""
    from ray_tpu.serve.llm import LLMEngine

    config = tiny_config()
    rng = np.random.default_rng(7)
    heads = [rng.integers(1, 512, 32).tolist() for _ in range(2)]
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
    assert stats["snapshots_pooled"] >= 2
    assert stats["rows_without_snapshot_tokens"] == 0
    return config, [{"id": i, "prompt_ids": p, "token_ids": r}
                    for i, (p, r) in enumerate(zip(prompts, replies))]


def test_check_served_passes_what_a_busy_engine_served_and_refuses_others(
        served):
    config, replies = served
    good = family.check_served(config, 11, replies)
    assert good["ok"] is True and good["tokens_checked"] == 4 * 14
    assert good["served_not_engine_top_share"] == 0.0
    assert good["engine_logit_mean_abs"] <= family.ENGINE_LOGIT_MEAN_ABS_LIMIT
    assert family.check_served(config, 11, [])["ok"] is False
    # another seed's weights did not choose these tokens
    assert family.check_served(config, 12, replies)["ok"] is False
    # nor did this engine choose another reply's
    swapped = [{**a, "token_ids": b["token_ids"]}
               for a, b in zip(replies, replies[1:] + replies[:1])]
    assert family.check_served(config, 11, swapped)["ok"] is False


def test_the_checks_engine_takes_the_windows_route(served):
    """Prefill of the whole blocks in one slot, rows and state pooled
    between two chunk steps, a hit copied into another slot, the rest as a
    chunk, then decode through the kernel's program: the pool's counters say
    so, and the logits choose what was served."""
    config, replies = served
    eng = family.stopped_engine(config, 11)
    by_route = family.engine_logits(eng, replies[:2])
    stats = eng.kv.stats()
    assert stats["prefix_hits"] == 2 and stats["tokens_reused"] == 2 * 32
    assert stats["blocks_used"] == 2 * 4 and stats["snapshots_used"] == 2
    assert stats["rows_without_snapshot_tokens"] == 0
    for reply, got in zip(replies[:2], by_route):
        assert got.shape == (14, 512)
        assert got.argmax(axis=-1).tolist() == reply["token_ids"]


def test_each_limit_refuses_alone():
    ok = {"served_not_engine_top_share": 0.01,
          "engine_logit_mean_abs": 0.5 * family.ENGINE_LOGIT_MEAN_ABS_LIMIT}
    assert family.verdict(ok)["ok"] is True
    assert family.verdict({**ok, "served_not_engine_top_share": 0.02})[
        "ok"] is False
    assert family.verdict({**ok, "engine_logit_mean_abs": 1.01
                           * family.ENGINE_LOGIT_MEAN_ABS_LIMIT})[
        "ok"] is False
    assert family.verdict({"error": "non-finite logits"})["ok"] is False


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,own,old", [
    ("jit(_step)/layers/while/body/closed_call/while/body/closed_call/attn/"
     "ssm_update/jit(ssm_update)/pallas_call", "ssm_update", "attn"),
    ("jit(_step)/layers/while/body/attn/ssm_update/mul:", "ssm_update",
     "attn"),
    ("jit(_step)/layers/while/body/attn/ssm_conv/jit(silu)/mul:", "ssm_conv",
     "attn"),
    ("jit(_chunk)/layers/while/body/attn/ssm_chunk/bjn,bjf->bnf/dot_general",
     "ssm_chunk", "attn"),
    ("jit(_step)/layers/while/body/attn/ssm_project/ln/mul:", "ssm_project",
     "ln"),
    ("jit(_step)/layers/while/body/attn/gqa_project/weights_cast/"
     "convert_element_type:", "gqa_project", "weights_cast"),
    ("jit(_chunk)/layers/while/body/attn/gqa_attend/while/body/cond/"
     "branch_1_fun/gqd,gtd->gqt/dot_general", "gqa_attend", "attn"),
    ("jit(_step)/layers/while/body/attn/kv_update/dynamic_update_slice:",
     None, "kv_update"),
    ("jit(_reset)/kv_update/dynamic_update_slice:", None, "kv_update"),
    ("jit(_copy_in)/prefix_pool/while/body/dynamic_update_slice:", None,
     "prefix_pool"),
    ("jit(_step)/layers/while/body/mlp/dot_general:", None, "mlp"),
    ("ssm_update", None, "unscoped"), (None, None, "unscoped")])
def test_where_an_operation_belongs(tf_op, own, old):
    """The six new scopes are `attn` (or the inner `ln`, `weights_cast`) to
    `_scopes.py`, whose shares still sum to 100."""
    assert _ssm_scopes.ssm_scope_of(tf_op) == own
    assert _scopes.scope_of(tf_op) == old
    assert not set(_ssm_scopes.SSM_SCOPES) & _scopes.SCOPES


STEP_OPS = {         # event -> tf_op; 10 ns each
    "%ssm_update.1 = f32[8]{0} custom-call()":
        "jit(_step)/layers/while/body/attn/ssm_update/jit(ssm_update)/"
        "pallas_call",
    "%fusion.2 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/ssm_update/mul:",
    "%fusion.3 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/ssm_conv/mul:",
    "%fusion.4 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/ssm_project/dot_general:",
    "%fusion.5 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/gqa_attend/dot_general:",
    "%fusion.6 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/dot_general:",
    "%fusion.7 = f32[8]{0} fusion()": "jit(_step)/unembed_loss/dot_general:",
    "%fusion.8 = f32[8]{0} fusion()":
        "jit(_chunk)/layers/while/body/attn/ssm_chunk/dot_general:",
    "%fusion.9 = f32[8]{0} fusion()":
        "jit(_copy_in)/prefix_pool/dynamic_update_slice:",
    "%fusion.10 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kv_update/dynamic_update_slice:"}


@pytest.fixture(scope="module")
def served_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation of
    `STEP_OPS` for 10 ns, and the counters of a window of 10 steps that
    generated 480 tokens over 2,000 positions a step."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(STEP_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, STEP_OPS)))
    d = tmp_path_factory.mktemp("granite_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    # so that a step's least time comes out at 8 ns under `ssm_update` (48
    # slots x 36 layers) and at 4 ns under `gqa_attend` (2,000 positions x 4)
    costs = {"ssm_layers": 36, "ssm_update_per_slot": {
                 "bytes": 8e-9 * peaks["hbm_bytes_per_s"] / (48 * 36),
                 "flops": 1.0},
             "gqa_layers": 4, "gqa_attend_per_position": {
                 "bytes": 0.0,
                 "flops": 4e-9 * peaks["bf16_flops_per_s"] / 8000}}
    return {"trace_dir": str(d), "peaks": peaks, "counters": {
        "before": {"engine_steps": 100, "total_generated": 1000,
                   "positions_attended": 50_000,
                   "rows_without_snapshot_tokens": 128},
        "after": {"engine_steps": 110, "total_generated": 1480,
                  "positions_attended": 70_000,
                  "rows_without_snapshot_tokens": 128,
                  "state_bytes_per_slot": 77_377_536,
                  "kv_bytes_per_token": 8192, "roofline_costs": costs}}}


@pytest.mark.parametrize("name,want", [
    ("ssm_update_time_pct", 20.0), ("ssm_conv_time_pct", 10.0),
    ("ssm_project_time_pct", 10.0), ("ssm_chunk_time_pct", 10.0),
    ("gqa_attend_time_pct", 10.0),
    ("engine_attn_time_pct", 60.0),
    ("engine_mlp_time_pct", 10.0),
    ("engine_head_time_pct", 10.0),
    ("engine_prefix_pool_time_pct", 10.0),
    ("kv_update_time_pct.decode", 10.0),
    ("state_bytes_per_slot", 77_377_536),
    ("kv_bytes_per_token", 8192),
    ("rows_without_snapshot_tokens", 0),
    # 8 ns of the 20 a step spends under ssm_update; 4 of gqa_attend's 10
    ("ssm_update_roofline_pct", 40.0), ("gqa_attend_roofline_pct", 40.0)])
def test_every_new_entry_reads_its_number(served_record, name, want):
    assert spec.metric_reader(name).read(served_record) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(
    OWN - {"engine_attn_time_pct", "engine_mlp_time_pct",
           "engine_head_time_pct", "kv_bytes_per_token",
           "engine_prefix_pool_time_pct"}))
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
    if name.endswith("roofline_pct") or name.startswith("rows_"):
        assert read({**parent,
                     "trace_dir": served_record["trace_dir"]}) is None


def test_a_trace_without_the_scopes_reads_as_nothing(tmp_path):
    """GPT-2's, Kanana's and Brumby's programs have none of the six."""
    ops = {"%fusion.1 = f32[8]{0} fusion()":
           "jit(_step)/layers/while/body/attn/dot_general:"}
    space = _msg((1, _plane(DEVICE, {
        tr.OPS_LINE: [(0, 10, next(iter(ops)))],
        tr.MODULES_LINE: [(0, 10, "jit__step(1)")]}, ops)))
    os.makedirs(tmp_path / "plugins" / "profile" / "t")
    (tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(
        space)
    record = {"trace_dir": str(tmp_path)}
    for scope in _ssm_scopes.SSM_SCOPES:
        assert _ssm_scopes.share(record, scope) is None
        assert _ssm_scopes.step_seconds(record, scope) is None


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_granite.py`: the generator, the warm-up, the pool
    hits of both kinds, the engine's counters and `check_served`, through
    the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_granite.py"),
         "--workload", CELL, "--seconds", "6", "--seed", "2400000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 80
    assert other["state_bytes_per_slot"]["value"] == 4 * (16 * 128 + 480) * 4
    assert other["kv_bytes_per_token"]["value"] == 2 * 2 * 2 * 16 * 2
    assert other["rows_without_snapshot_tokens"]["value"] == 0
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import importlib

    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("granite")
    try:
        with pytest.raises(ValueError, match="granite-4.0-h-micro"):
            importlib.import_module("ray_tpu.serve.llm").LLMEngine(
                **family.engine_options(CONFIG, 1))
    finally:
        models._SERVING.update(saved)
