"""The Kanana family file on the CPU: its configuration against the
published one, its reference against a per-token loop, its arithmetic
against hand counts, the document generator, the readers of the new scopes
and counters on hand-made records, and the cell end to end at a tiny size."""

import ast
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

import trace_reduce as tr
from conftest import CHIP_DIR, REPO
from families import kanana as family
from generators import closed_loop_documents
from harness import spec
from metrics import _mla_scopes, _moe_scopes, _scopes
from test_hot_path_metrics import DEVICE, _msg, _plane

PUBLISHED = {   # kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "kanana-2-30b-a3b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "doc-qa-decode.json"))
CELL = "serve-kanana-docqa"
TINY = {"vocab_size": 512, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "num_attention_heads": 4,
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_experts_per_tok": 3, "n_shared_experts": 2,
        "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "routed_scaling_factor": 2.448, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 1000000, "rms_norm_eps": 1e-6}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_published_one_less_depth():
    changed = {k for k in PUBLISHED if CONFIG["model"].get(k) != PUBLISHED[k]}
    assert changed == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert set(CONFIG["model"]) == set(PUBLISHED)
    assert {k: CONFIG[k] for k in PUBLISHED} == CONFIG["model"]
    assert CONFIG["model"]["num_hidden_layers"] == 8
    assert CONFIG["published"] == {"num_hidden_layers": 48}
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "kanana")
    assert CONFIG["deployment"] == {
        "preset": "kanana-2-30b-a3b", "max_seq_len": 4096, "max_batch": 32,
        "scheduler": "continuous", "enable_prefix_caching": True,
        "prefill_chunk_size": 128, "kv_blocks": 256, "kv_block_size": 128}


def test_the_compiled_programs_fill_the_chip_and_leave_a_twentieth():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(CONFIG["deployment"]["prefill_chunk_size"])]
    held = chunk + memory["prefix_pool_bytes"]
    assert 0.60 <= held / chip <= 0.95
    assert memory["arguments_bytes"] / chip >= 0.60
    assert memory["decode_step_bytes"] < chunk
    assert memory["prefix_pool_bytes"] == 256 * 128 * 8 * 576 * 2


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.n_head, cfg.qk_head_dim, cfg.v_head_dim,
            cfg.kv_lora_rank, cfg.cache_width) == (2048, 32, 192, 128, 512,
                                                   576)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.d_ff_expert,
            cfg.n_shared_experts, cfg.d_ff) == (128, 6, 768, 2, 6144)
    assert (cfg.n_layer, cfg.n_dense_layer, cfg.vocab_size,
            cfg.max_seq_len) == (8, 1, 128256, 4096)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 2.448)
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    assert family.CharTokenizer.eos_id < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([0, 128255, 7])) == [0, 128255, 7]


# What every document cell reads of the first decode cell's readings, by
# name and never by count: a later PR may append a `.decode` reading for any
# cell, these or `serve-xl-decode` alone (`test_a_tenth_cell.py`).
DECODE = {"stream_open_ms.decode", "engine_step_ms.decode",
          "tokens_per_step.decode", "decode_device_ms.decode",
          "prefill_device_ms.decode", "prefix_reuse_pct.decode",
          "device_idle_pct.decode", "gen_late_p99_ms.decode",
          "engine_host_ms.decode", "queue_wait_mean_ms.decode",
          "idle_in_fetch_pct.decode", "idle_in_sample_pct.decode",
          "idle_in_loop_pct.decode", "kv_update_time_pct.decode",
          "unscoped_time_pct.decode", "step_hbm_gb.decode",
          "layers_time_pct.decode"}
OWN = {"engine_attn_time_pct", "engine_mlp_time_pct", "engine_head_time_pct",
       "engine_prefix_pool_time_pct", "moe_router_time_pct.decode",
       "moe_dispatch_time_pct.decode", "moe_experts_time_pct.decode",
       "mla_attend_time_pct", "mla_project_time_pct", "moe_shared_time_pct",
       "moe_experts_touched_per_layer", "moe_decode_load_max_over_mean",
       "kv_bytes_per_token", "moe_experts_decode_roofline_pct",
       "mla_attend_roofline_pct"}


def the_cell_reads_what_it_reads(bench):
    """Holds the cell to what it reads, never to who else reads it: a
    later cell joins an entry's list (`test_a_tenth_cell.py`)."""
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and OWN <= names
    for m in cell["per_layer"]:
        assert spec.metric_reader(m["name"]) is not None, m["name"]
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"


def test_the_cell_reads_the_decode_metrics_and_its_own():
    the_cell_reads_what_it_reads(spec.benchmark())


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in TRAFFIC
            if k not in ("what", "schedule_seed", "schedule_seed_why")} == {
        "generator": "closed_loop_documents", "clients": 64,
        "requests_per_client": 24, "documents": 8,
        "document_uniform": [2048, 3072], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [256, 512],
        "ramp_s": 10.0, "reference_sample": 4, "trace_at": 0.4,
        "trace_seconds": 5.0}
    deploy = CONFIG["deployment"]
    assert TRAFFIC["clients"] == 2 * deploy["max_batch"]
    assert TRAFFIC["document_block"] == deploy["kv_block_size"]


# --------------------------------------------------------------- arithmetic

def test_roofline_costs_against_hand_counts():
    model = CONFIG["model"]
    attend = family.mla_attend_cost(model, 1000.0)
    assert attend == {"bytes": 1000 * 576 * 2.0,
                      "flops": 1000 * 2.0 * 32 * (576 + 512)}
    experts = family.moe_experts_decode_cost(model, 192.0, 100.0)
    assert experts["bytes"] == 100 * 3 * 2048 * 768 * 2 + 192 * 2 * 2048 * 2
    assert experts["flops"] == 192 * 6.0 * 2048 * 768
    unit = family.roofline_costs(model)
    assert unit["attention_layers"] == 8 and unit["routed_experts"] == 128
    assert unit["mla_attend_per_position"]["bytes"] == 1152.0
    assert unit["moe_experts_per_touched_expert"] == {
        "bytes": 3 * 2048 * 768 * 2.0, "flops": 0.0}
    # a decode step of the cell: the touched experts' weights bound it
    peaks = spec.peaks()["TPU v5 lite"]
    assert _moe_scopes.bound_seconds(experts, peaks)[0] == "bytes"
    assert _moe_scopes.bound_seconds(attend, peaks)[0] == "bytes"


# ---------------------------------------------------------------- reference

def tiny_layer(seed: int, dense: bool) -> dict:
    rng = np.random.default_rng(seed)
    m = TINY
    d, heads = m["hidden_size"], m["num_attention_heads"]
    n, p, v, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"], m["kv_lora_rank"])

    def w(*shape, std=0.2):
        return rng.normal(size=shape).astype(np.float32) * std

    layer = {"attn_norm": {"scale": 1 + w(d)},
             "attn": {"wq": w(d, heads, n + p), "wkva": w(d, r + p),
                      "kv_norm": {"scale": 1 + w(r)},
                      "wkvb": w(r, heads, n + v), "wo": w(heads * v, d)},
             "mlp_norm": {"scale": 1 + w(d)}}
    f, e = m["moe_intermediate_size"], m["n_routed_experts"]
    if dense:
        layer["mlp"] = {"wg": w(d, 2 * f), "wu": w(d, 2 * f),
                        "wd": w(2 * f, d)}
    else:
        layer["moe"] = {"router": w(d, e), "bias": w(e, std=0.3),
                        "wg": w(e, d, f), "wu": w(e, d, f), "wd": w(e, f, d)}
        layer["shared"] = {"wg": w(d, 2 * f), "wu": w(d, 2 * f),
                           "wd": w(2 * f, d)}
    return layer


def layer_by_a_loop(x, p, m):
    """One token at a time, one head at a time, in float64 numpy: the
    published equations read literally."""
    x = np.asarray(x, np.float64)
    p = {k: ({kk: np.asarray(vv, np.float64) if not isinstance(vv, dict)
              else {k3: np.asarray(v3, np.float64) for k3, v3 in vv.items()}
              for kk, vv in v.items()})
         for k, v in p.items()}
    seq, d = x.shape
    heads, n, rope, v_dim, r = (m["num_attention_heads"],
                                m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                                m["v_head_dim"], m["kv_lora_rank"])

    def norm(a, scale):
        return a / np.sqrt(np.mean(a * a) + m["rms_norm_eps"]) * scale

    def turn(a, t):
        half = rope // 2
        inv = 1.0 / m["rope_theta"] ** (np.arange(0, rope, 2) / rope)
        cos, sin = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a[:half] * cos - a[half:] * sin,
                               a[half:] * cos + a[:half] * sin])

    a = p["attn"]
    keys, values, out = [], [], np.zeros_like(x)
    for t in range(seq):
        h = norm(x[t], p["attn_norm"]["scale"])
        ckr = h @ a["wkva"]
        c = norm(ckr[:r], a["kv_norm"]["scale"])
        kv = np.einsum("r,rhk->hk", c, a["wkvb"])
        k_r = turn(ckr[r:], t)
        keys.append(np.concatenate([kv[:, :n], np.tile(k_r, (heads, 1))], 1))
        values.append(kv[:, n:])
        q = np.einsum("d,dhk->hk", h, a["wq"])
        o = np.zeros((heads, v_dim))
        for head in range(heads):
            qh = np.concatenate([q[head, :n], turn(q[head, n:], t)])
            s = np.array([qh @ keys[u][head] for u in range(t + 1)]) \
                / math.sqrt(n + rope)
            w = np.exp(s - s.max())
            w /= w.sum()
            o[head] = sum(w[u] * values[u][head] for u in range(t + 1))
        out[t] = x[t] + o.reshape(-1) @ a["wo"]

    def swiglu(h, q):
        g = h @ q["wg"]
        return (g / (1 + np.exp(-g)) * (h @ q["wu"])) @ q["wd"]

    final = np.zeros_like(out)
    for t in range(seq):
        h = norm(out[t], p["mlp_norm"]["scale"])
        if "mlp" in p:
            final[t] = out[t] + swiglu(h, p["mlp"])
            continue
        e = p["moe"]
        s = 1 / (1 + np.exp(-(h @ e["router"])))
        chosen = np.argsort(-(s + e["bias"]))[:m["num_experts_per_tok"]]
        g = s[chosen] / (s[chosen].sum() + 1e-20) * m["routed_scaling_factor"]
        final[t] = out[t] + swiglu(h, p["shared"]) + sum(
            gk * swiglu(h, {k: e[k][ek] for k in ("wg", "wu", "wd")})
            for gk, ek in zip(g, chosen))
    return final


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "experts"])
@pytest.mark.parametrize("seed", [0, 1])
def test_reference_agrees_with_a_per_token_loop(seed, dense):
    import jax.numpy as jnp

    layer = tiny_layer(seed, dense)
    x = np.random.default_rng(seed + 10).normal(size=(9, 64)).astype(
        np.float32)
    got, chosen = family.reference_layer(jnp.asarray(x), layer, TINY)
    np.testing.assert_allclose(np.asarray(got), layer_by_a_loop(x, layer,
                                                                TINY),
                               atol=2e-4, rtol=2e-4)
    assert (chosen is None) == dense


@pytest.mark.parametrize("degrade", family.DEGRADE[1:])
def test_a_degraded_reference_is_another_function(degrade):
    import jax.numpy as jnp

    layer = tiny_layer(3, False)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(9, 64)),
                    jnp.float32)
    want, _ = family.reference_layer(x, layer, TINY)
    low, _ = family.reference_layer(x, layer, TINY, degrade)
    worst = float(jnp.abs(low - want).max())
    assert 1e-3 < worst < 1.0


def test_float8_keeps_three_bits_of_mantissa():
    import jax.numpy as jnp

    a = jnp.asarray([1.0, 1.06, 1.07, 0.53, -0.27, 3.9], jnp.float32)
    got = np.asarray(family._through_float8(a))
    assert np.abs(got / np.asarray(a) - 1).max() <= 2 ** -4 + 1e-6
    assert len({float(x) for x in np.asarray(family._through_float8(
        jnp.linspace(1.0, 2.0, 101)))}) == 9           # 8 steps an octave


def test_the_reference_imports_nothing_from_the_program():
    with open(os.path.join(CHIP_DIR, "families", "kanana.py")) as f:
        tree = ast.parse(f.read())
    drives_the_program = {"program_config", "seeded_weights", "build_app",
                          "stopped_engine"}
    for node in tree.body:
        name = getattr(node, "name", None)
        imported = {n.module if isinstance(n, ast.ImportFrom) else a.name
                    for n in ast.walk(node)
                    if isinstance(n, (ast.Import, ast.ImportFrom))
                    for a in n.names}
        if name not in drives_the_program:
            assert not any(m and m.startswith("ray_tpu") for m in imported), \
                (name, imported)
    defined = {n.name for n in tree.body
               if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    assert {"reference_layer", "reference_head", "Reference", "compare_served", "verdict",
            "check_served", "mla_attend_cost",
            "moe_experts_decode_cost"} <= defined - drives_the_program
    with open(os.path.join(CHIP_DIR, "families", "kanana.py")) as f:
        assert '"highest"' in f.read()


TINY_DEPLOYMENT = {"preset": "deepseek-tiny", "max_seq_len": 96,
                   "max_batch": 8, "scheduler": "continuous",
                   "enable_prefix_caching": True, "prefill_chunk_size": 4,
                   "kv_blocks": 24, "kv_block_size": 8}


def test_check_served_passes_what_a_busy_engine_served_and_refuses_others():
    """What an engine serves with every slot taken, the pool hit and the
    loop a step ahead is, token for token, what the check's engine computes
    for it alone: a step's row depends on no other row."""
    from concurrent.futures import ThreadPoolExecutor

    from ray_tpu.models import deepseek
    from ray_tpu.serve.llm import LLMEngine

    config = {"model": dict(TINY), "deployment": dict(TINY_DEPLOYMENT)}
    assert family.program_config(config) == deepseek.DeepseekConfig.preset(
        "deepseek-tiny", max_seq_len=96)
    document = list(range(5, 45))               # five blocks of the pool
    prompts = [document + [100 + 3 * i + j for j in range(2 + i % 4)]
               for i in range(12)]
    eng = LLMEngine(**family.engine_options(config, 9))
    try:
        eng.generate(prompt_ids=prompts[0], max_tokens=2)    # pools it
        with ThreadPoolExecutor(12) as pool:
            replies = list(pool.map(
                lambda p: eng.generate(prompt_ids=p, max_tokens=6 + len(p) % 5),
                prompts))
        assert eng.kv.stats()["tokens_reused"] >= 12 * 40
        assert eng.engine_stats()["steps_dispatched_ahead"] > 0
    finally:
        eng.shutdown()
    served = [{"prompt_ids": p, "token_ids": r["token_ids"]}
              for p, r in zip(prompts, replies)][2:6]
    good = family.check_served(config, 9, served)
    assert good["ok"] and good["served_not_engine_top_share"] == 0.0
    assert good["engine_logit_mean_abs"] < 5e-3            # bf16, tiny
    assert good["tokens_checked"] == sum(len(s["token_ids"]) for s in served)
    assert good["replies"] == 4 and set(good["seconds"]) == {"engine",
                                                             "reference"}
    assert set(good["limits"]) == {"served_not_engine_top_share",
                                   "engine_logit_mean_abs"}
    # the engine's logits are the reference's whatever was served; the
    # served tokens have to be the engine's
    wrong = [{**s, "token_ids": [(t + 1) % 512 for t in s["token_ids"]]}
             for s in served]
    bad = family.check_served(config, 9, wrong)
    assert not bad["ok"] and bad["served_not_engine_top_share"] > 0.5
    assert bad["served_below_reference_top_mean"] > 0.01
    other_seed = family.check_served(config, 10, served)
    assert not other_seed["ok"]
    assert not family.check_served(config, 9, [])["ok"]


def test_the_checks_engine_takes_the_windows_route():
    """`engine_logits`: whole blocks prefilled and pooled, a pool hit into
    another slot, the rest as a chunk, the served tokens a step each, and
    every sequence live at once: each sequence's logits are those it gets
    alone, and one row a generated position."""
    config = {"model": dict(TINY), "deployment": dict(TINY_DEPLOYMENT)}
    served = [{"prompt_ids": list(range(3, 3 + n)), "token_ids": list(t)}
              for n, t in ((41, [7, 8, 9, 10]), (5, [1]), (27, [4, 5, 6]),
                           (38, [2, 3]))]
    eng = family.stopped_engine(config, 4)
    together = family.engine_logits(eng, served)
    # 38: four blocks from the pool and two chunk steps for the rest
    assert [r.shape for r in together] == [(4, 512), (1, 512), (3, 512),
                                           (2, 512)]
    for s, rows in zip(served, together):
        alone = family.engine_logits(eng, [s])[0]       # slots used before
        assert np.array_equal(alone, rows)
    layer_weights, ends = family.seeded_weights(config, 4)
    rows, at = family._rows_and_positions(served)
    want = family.Reference(config["model"], layer_weights, ends).logits(
        rows, at)
    assert max(np.abs(g - w).max() for g, w in zip(together, want)) < 5e-3


def test_each_limit_refuses_alone():
    served = [{"prompt_ids": [1], "token_ids": [2, 0, 1]}]
    top = np.eye(3, dtype=np.float32)[[2, 0, 1]]
    fine = family.verdict(family.compare_served(served, [top], [top]))
    assert fine["ok"] and fine["engine_logit_mean_abs"] == 0.0
    far = family.verdict(family.compare_served(
        served, [top], [top + 2 * family.ENGINE_LOGIT_MEAN_ABS_LIMIT]))
    assert not far["ok"] and far["served_not_engine_top_share"] == 0.0
    other = family.verdict(family.compare_served(
        served, [top[::-1]], [top[::-1]]))
    assert not other["ok"] and other["engine_logit_mean_abs"] == 0.0
    assert not family.verdict(family.compare_served(
        served, [top * np.nan], [top]))["ok"]
    # between what the program reads and the least a float8 part does
    assert 0.025 < family.SERVED_NOT_ENGINE_TOP_LIMIT < 0.069
    assert 0.0105 < family.ENGINE_LOGIT_MEAN_ABS_LIMIT < 0.0151


# ---------------------------------------------------------------- generator

GEN_CONFIG = {"model": {"vocab_size": 128256}}


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_documents_the_questions_and_the_clips(seed):
    plan = closed_loop_documents.generate(TRAFFIC, GEN_CONFIG, seed, 51)
    again = closed_loop_documents.generate(TRAFFIC, GEN_CONFIG, seed, 51)
    assert plan == again
    requests = plan["requests"]
    assert plan["loop"] == "closed" and plan["clients"] == 64
    assert len(requests) == 64 * 24
    assert [r["client"] for r in requests[:128]] == list(range(64)) * 2
    documents = {}
    for r in requests:
        documents.setdefault(r["document"], []).append(r)
    assert len(documents) == 8
    assert {len(v) for v in documents.values()} == {len(requests) // 8}
    blocks = 0
    for asked in documents.values():
        lengths = [len(r["prompt_ids"]) for r in asked]
        doc_len = min(lengths) // 128 * 128
        assert 2048 <= doc_len <= 3072
        blocks += doc_len // 128
        first = asked[0]["prompt_ids"][:doc_len]
        questions = set()
        for r in asked:
            assert r["prompt_ids"][:doc_len] == first
            question = r["prompt_ids"][doc_len:]
            assert 16 <= len(question) <= 64
            questions.add(tuple(question))
        assert len(questions) == len(asked)              # all distinct
    assert blocks <= 192 < 256
    outs = [r["max_tokens"] for r in requests]
    assert min(outs) == 256 and max(outs) == 512
    assert abs(np.mean(outs) - 384) < 2
    assert all(r["temperature"] == 0.0 and r["top_p"] == 1.0
               for r in requests)
    assert max(len(r["prompt_ids"]) + r["max_tokens"] for r in requests) \
        <= 4096 - 2
    ids = np.concatenate([r["prompt_ids"] for r in requests[:64]])
    assert ids.min() >= 0 and ids.max() < 128256 and ids.max() > 120000
    # the warm-up pools every document, then finds the first again
    warm = plan["warmup"]
    assert len(warm) == 9 and all(w["max_tokens"] == 2 for w in warm)
    assert warm[0]["prompt_ids"][:2048] == warm[8]["prompt_ids"][:2048]
    assert warm[0]["prompt_ids"] != warm[8]["prompt_ids"]
    starts = {tuple(w["prompt_ids"][:128]) for w in warm}
    assert starts == {tuple(v[0]["prompt_ids"][:128])
                      for v in documents.values()}


def test_other_seed_other_tokens_on_the_same_schedule():
    a = closed_loop_documents.generate(TRAFFIC, GEN_CONFIG, 7, 51)
    b = closed_loop_documents.generate(TRAFFIC, GEN_CONFIG, 8, 51)
    assert a["requests"][0]["prompt_ids"][:64] != \
        b["requests"][0]["prompt_ids"][:64]
    shape = lambda plan: [(r["document"], len(r["prompt_ids"]),
                           r["max_tokens"]) for r in plan["requests"]]
    assert shape(a) == shape(b)
    other = closed_loop_documents.generate(
        {**TRAFFIC, "schedule_seed": 30}, GEN_CONFIG, 7, 51)
    assert shape(other) != shape(a)


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,mla,old,moe", [
    ("jit(_step)/layers/while/body/closed_call/attn/mla_attend/"
     "bchr,btr->bhct/dot_general", "mla_attend", "attn", None),
    ("jit(_step)/layers/while/body/closed_call/attn/mla_project/ln/mul:",
     "mla_project", "ln", None),
    ("jit(_step)/attn/mla_project/weights_cast/convert_element_type:",
     "mla_project", "weights_cast", None),
    ("jit(_step)/layers/while/body/attn/kv_update/dynamic_update_slice:",
     None, "kv_update", None),
    ("jit(_step)/layers/while/body/mlp/moe_shared/dot_general:",
     "moe_shared", "mlp", None),
    ("jit(_step)/layers/while/body/mlp/moe_experts/jit(gmm)/pallas_call",
     None, "mlp", "moe_experts"),
    ("jit(_step)/layers/while/body/mlp/moe_router/top_k:", None, "mlp",
     "moe_router"),
    ("jit(_step)/unembed_loss/dot_general:", None, "unembed_loss", None),
    ("mla_attend", None, "unscoped", None), (None, None, "unscoped", None)])
def test_where_an_operation_belongs(tf_op, mla, old, moe):
    """The new readers see the new scopes; to the readers that were there
    an operation under them is `attn`, `ln` or `mlp`, as it should be."""
    assert _mla_scopes.mla_scope_of(tf_op) == mla
    assert _scopes.scope_of(tf_op) == old
    assert _moe_scopes.moe_scope_of(tf_op) == moe


STEP_OPS = {         # event -> tf_op; 10 ns each
    "%conv.1 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/mla_attend/dot_general:",
    "%fusion.2 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/mla_attend/softmax/exp:",
    "%fusion.3 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/mla_project/dot_general:",
    "%gmm.4 = bf16[8,8]{1,0} custom-call()":
        "jit(_step)/layers/while/body/mlp/moe_experts/jit(gmm)/pallas_call",
    "%fusion.5 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/moe_shared/dot_general:",
    "%fusion.6 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/moe_router/top_k:",
    "%fusion.7 = f32[8]{0} fusion()": "jit(_step)/unembed_loss/dot_general:",
    "%fusion.8 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kv_update/select_n:"}


@pytest.fixture(scope="module")
def served_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation
    of `STEP_OPS` for 10 ns, and the counters of a window of 10 decode
    steps and 2 chunk steps."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(STEP_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, STEP_OPS)))
    d = tmp_path_factory.mktemp("kanana_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    per_s = peaks["hbm_bytes_per_s"]
    costs = {"attention_layers": 8, "routed_experts": 128,
             # so that a step's least times come out at 5 ns and 4 ns
             "mla_attend_per_position": {"bytes": 5e-9 * per_s / 8 / 1000,
                                         "flops": 1.0},
             "moe_experts_per_row": {"bytes": 0.0, "flops": 1.0},
             "moe_experts_per_touched_expert": {"bytes": 4e-9 * per_s / 700,
                                                "flops": 0.0}}
    zero = {"expert_rows": 2 ** 32 - 100, "experts_touched": 50,
            "busiest_expert_rows": 5, "expert_layer_steps": 7,
            "attended_positions": 11}
    after = {"expert_rows": 13340, "experts_touched": 7050,
             "busiest_expert_rows": 215, "expert_layer_steps": 77,
             "attended_positions": 10011}
    chunk = dict.fromkeys(zero, 0)
    return {"trace_dir": str(d), "peaks": peaks, "counters": {
        "before": {"engine_steps": 100, "chunk_steps": 10,
                   "kv_bytes_per_token": 9216,
                   "step_counts": {"decode": zero, "chunk": chunk}},
        "after": {"engine_steps": 112, "chunk_steps": 12,
                  "kv_bytes_per_token": 9216, "roofline_costs": costs,
                  "step_counts": {"decode": after, "chunk": chunk}}}}


@pytest.mark.parametrize("name,want", [
    ("mla_attend_time_pct", 25.0), ("mla_project_time_pct", 12.5),
    ("moe_shared_time_pct", 12.5),
    ("engine_attn_time_pct", 37.5), ("engine_mlp_time_pct", 37.5),
    ("engine_head_time_pct", 12.5), ("kv_update_time_pct.decode", 12.5),
    ("moe_experts_time_pct.decode", 12.5),
    ("moe_router_time_pct.decode", 12.5),
    ("moe_dispatch_time_pct.decode", 0.0),
    ("engine_prefix_pool_time_pct", 0.0),
    ("kv_bytes_per_token", 9216),
    # 70 layer-steps in 10 decode steps: 7000 touched / 70
    ("moe_experts_touched_per_layer", 100.0),
    # 210 busiest x 128 / 13440 rows (the counter wrapped on the way)
    ("moe_decode_load_max_over_mean", 2.0),
    # 700 touched a step: 4 ns of 10 under moe_experts
    ("moe_experts_decode_roofline_pct", 40.0),
    # 1000 positions a step x 8 layers: 5 ns of 20 under mla_attend
    ("mla_attend_roofline_pct", 25.0)])
def test_every_new_entry_reads_its_number(served_record, name, want):
    assert spec.metric_reader(name).read(served_record) == pytest.approx(want)


OWN_READERS = ["mla_attend_time_pct", "mla_project_time_pct",
               "moe_shared_time_pct", "moe_experts_touched_per_layer",
               "moe_decode_load_max_over_mean", "kv_bytes_per_token",
               "moe_experts_decode_roofline_pct", "mla_attend_roofline_pct"]


@pytest.mark.parametrize("name", OWN_READERS)
def test_a_program_without_the_scopes_and_counters_reads_as_nothing(
        name, served_record):
    """The parent's engine has neither: None, not 0 and not a crash."""
    parent = {"trace_dir": None, "peaks": served_record["peaks"],
              "counters": {"before": {"engine_steps": 1, "chunk_steps": 0},
                           "after": {"engine_steps": 9, "chunk_steps": 2}}}
    assert spec.metric_reader(name).read(parent) is None
    assert spec.metric_reader(name).read({"counters": None}) is None
    assert spec.metric_reader(name).read({}) is None


def test_the_training_cells_reader_cannot_read_a_serving_record(
        served_record):
    """Why the cell's load metric has a name of its own: the reader that
    was there reads a training cell's reference check."""
    with pytest.raises((KeyError, TypeError)):
        spec.metric_reader("moe_load_max_over_mean").read(
            {**served_record, "loop": "closed"})


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_kanana.py`: the generator, the warm-up, the pool
    hits, the engine's counters and `check_served`, through the harness's
    own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_kanana.py"),
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
    assert other["kv_bytes_per_token"]["value"] == 3 * 40 * 2
    assert 1 <= other["moe_experts_touched_per_layer"]["value"] <= 8
    assert other["moe_decode_load_max_over_mean"]["value"] >= 1
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_the_on_chip_comparison_runs_on_the_cpu_at_a_tiny_size(tmp_path):
    """`rehearse/kanana_on_chip.py --tiny`: both routes through the engine's
    programs against the reference, and both degraded references."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "kanana_on_chip.py"),
         "--tiny", "--prompt", "60", "--decode", "8", "--seeds", "3",
         "--chunk-every", "3"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["decoded"] == 8
    # the check's route (56 of 60 tokens from the pool) gives what the
    # plain prefill gave
    assert got["pool_route_against_plain_max_abs"] < 1e-2
    assert got["program"]["ok"] and got["program"]["tokens_checked"] == 8
    # on the CPU the chunk program at one token rounds as the decode program
    assert got["program"]["served_not_engine_top_share"] == 0.0
    assert got["mixed_against_pool_route_mean_abs"] < 1e-2
    assert got["program"]["engine_logit_mean_abs"] < 5e-3       # bf16, tiny
    for degrade in family.DEGRADE[1:]:
        for as_if in ("as_if_served", "as_if_the_engines"):
            assert got[degrade][as_if]["tokens_checked"] == 8
        assert got[degrade]["as_if_the_engines"]["engine_logit_mean_abs"] \
            > 1e-5
    # 7 decode steps by the plain and the pool route and 5 of the mixed
    # route's, 2 expert layers
    assert got["counters"]["step_counts"]["decode"]["expert_layer_steps"] \
        == 2 * (7 + 7 + 5)
