"""The Kimi family file on the CPU: its configuration against the catalog's
row, its reference against a second formulation written here in numpy (the
chunked form of the delta rule with its triangular solve, attention a query
at a time, the experts a token at a time), its arithmetic against hand
counts, the traffic file, the check of what was served (the window's route,
each limit alone), the readers of the new scopes and counters on hand-made
records, and the cell end to end at a tiny size."""

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
from families import kimi as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _kda_scopes, _moe_scopes, _scopes  # noqa: E402
from test_hot_path_metrics import DEVICE, _msg, _plane  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402

CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "kimi-linear-48b-a3b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "hot-context-long-generation.json"))
CELL = "serve-kimi-longgen"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "linear_attn_config"]
TINY = {"vocab_size": 512, "num_hidden_layers": 5,
        "linear_attn_config": {
            "full_attn_layers": [3, 5], "kda_layers": [1, 2, 4],
            "head_dim": 16, "num_heads": 2, "short_conv_kernel_size": 4},
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16}
# the reference's model at the tiny size: 8 experts of which 4 are held
TINY_MODEL = {**CONFIG["model"], **TINY, "num_experts": 4,
              "num_experts_per_token": 3, "router_outputs": 8,
              "first_expert": 2}
OWN = {"kda_update_time_pct", "kda_chunk_time_pct", "kda_project_time_pct",
       "kda_update_roofline_pct", "mla_attend_time_pct",
       "mla_attend_roofline_pct", "moe_experts_time_pct.decode",
       "moe_experts_decode_roofline_pct", "moe_held_rows_pct",
       "engine_attn_time_pct", "engine_mlp_time_pct",
       "engine_head_time_pct", "engine_prefix_pool_time_pct",
       "kv_bytes_per_token"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_four_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    # every key of the catalog's config but the four, at the top level and
    # under `model`, and the two agree on the four as well
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert {k: CONFIG[k] for k in REDUCED} == {
        k: CONFIG["model"][k] for k in REDUCED}
    # the four: the published value beside what is held here
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED}
    m = CONFIG["model"]
    assert (m["num_hidden_layers"], m["num_experts"], m["vocab_size"]) == (
        9, 64, 40960)
    lin, published = m["linear_attn_config"], row["config"][
        "linear_attn_config"]
    assert lin["full_attn_layers"] == [4, 8]
    assert lin["kda_layers"] == [1, 2, 3, 5, 6, 7, 9]
    assert {k: v for k, v in lin.items() if not k.endswith("_layers")} == {
        k: v for k, v in published.items() if not k.endswith("_layers")}
    # the nine kept layers are the published model's first nine
    assert lin["full_attn_layers"] == [
        l for l in published["full_attn_layers"] if l <= 9]
    assert lin["kda_layers"] == [l for l in published["kda_layers"] if l <= 9]
    # the floors: a whole period and four layers after the dense one, 8
    # experts a layer, an eighth of the vocabulary
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4 + 4
    assert m["num_experts"] >= 8 and m["vocab_size"] * 8 >= 163840
    assert CONFIG["share"] == {
        "chips_sharing_a_layer": 4, "pipeline_stages": 3,
        "router_outputs": 256, "first_expert": 0, "first_vocab_row": 0}
    assert m["num_experts"] * 4 == CONFIG["share"]["router_outputs"]
    assert m["vocab_size"] * 4 == CONFIG["published"]["vocab_size"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "kimi")
    assert CONFIG["deployment"] == {
        "preset": "kimi-linear-48b-a3b", "max_seq_len": 10240,
        "max_batch": 128, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 480, "kv_block_size": 128}
    # every assumption the issue lists has its reason written down
    assert {"kda_gate_rank", "kda_init", "selection_bias", "state_dtype",
            "state_layout", "float32_islands", "weights", "no_rotation",
            "head_dim", "tokenizer", "routing_load", "deployment_sizes",
            "kv_blocks"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    assert "four-chip" in CONFIG["stands_for"]
    assert "three pipeline stages" in CONFIG["stands_for"]
    bench = spec.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == REDUCED and entry["source"] == CONFIG["source"]
    # no width is reduced
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]


def test_the_compiled_programs_leave_room_on_the_chip():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(CONFIG["deployment"]["prefill_chunk_size"])]
    held = max(chunk, memory["decode_step_bytes"]) + memory[
        "prefix_pool_bytes"]
    assert 0.75 * chip <= held <= 0.95 * chip
    assert memory["decode_step_temp_bytes"] < 2 ** 28  # no copy of the state
    slot = memory["state_bytes_per_slot"]
    assert slot == 7 * (32 * 128 * 128 + 3 * 12288) * 4 == 15_712_256
    assert memory["kv_bytes_per_token"] == 2 * 576 * 2 == 2304
    d = CONFIG["deployment"]
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 6 == TRAFFIC["documents"]
    assert memory["prefix_pool_bytes"] == (
        snapshots * slot + d["kv_blocks"] * d["kv_block_size"] * 2304)
    assert family.state_bytes_per_slot(CONFIG["model"]) == slot
    assert family.kv_bytes_per_token(CONFIG["model"]) == 2304


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert, cfg.n_head,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (2304, 9216, 1024, 32, 512, 128, 64, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_rank,
            cfg.kda_inner) == (32, 128, 4, 128, 4096)
    # the router at its published width and 8 a token; 64 experts held
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert, cfg.n_shared_experts) == (256, 8, 64, 0, 1)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 2.446)
    assert (cfg.n_layer, cfg.n_dense_layer, cfg.vocab_size,
            cfg.max_seq_len, cfg.norm_eps) == (9, 1, 40960, 10240, 1e-5)
    assert cfg.layer_types == ("kda", "kda", "kda", "mla") * 2 + ("kda",)
    from ray_tpu.models import kimi

    assert round(kimi.num_params(cfg) / 1e6) == 4274        # 8.55 GB held
    assert family.CharTokenizer.eos_id == 40959 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 40958, 7])) == [1, 40958, 7]


def the_cell_reads_what_it_reads(bench):
    """Holds the cell to what it reads, never to who else reads it: a
    later cell joins an entry's list (`test_a_tenth_cell.py`)."""
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names
    # of the experts' three it reads the experts' own (Kanana's entry, one
    # reading for both cells since PR 45); the router's and the dispatch's
    # shares were never listed here
    assert names.isdisjoint({"moe_router_time_pct.decode",
                             "moe_dispatch_time_pct.decode"})
    assert {"state_bytes_per_slot", "kv_bytes_per_token",
            "setup_engine_build_s"} <= names
    # "contains", never "ends with": later PRs append too
    assert OWN <= names
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
            assert spec.metric_reader(m["name"]) is not None
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["kda_update_roofline_pct"] == layers[
        "moe_experts_decode_roofline_pct"] == layers[
        "mla_attend_roofline_pct"]
    assert layers["kda_update_time_pct"] == layers["mla_attend_time_pct"]
    assert layers["engine_prefix_pool_time_pct"] == layers[
        "prefix_reuse_pct.decode"]
    assert len(bench["per_layer"]) <= 128
    (workload,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert "4x their share" in workload["why"]   # attention sees more


def test_the_cell_reads_the_decode_metrics_that_exist_for_it_and_its_own():
    the_cell_reads_what_it_reads(spec.benchmark())


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 160,
        "requests_per_client": 6, "documents": 6,
        "document_uniform": [4096, 8192], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [512, 1024],
        "schedule_seed": 40, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert TRAFFIC["question_uniform"][1] <= d["prefill_chunk_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    # the pool holds every context's rows at their longest
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_contexts_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 160 * 6 and plan["clients"] == 160
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        blocks = max(b for b in range(32, 65) if b * 128 <= n - 16)
        assert 16 <= n - blocks * 128 <= 64
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        # a sliced vocabulary is a smaller vocabulary: ids from the slice
        assert max(r["prompt_ids"]) < 40960
        head = tuple(r["prompt_ids"][:blocks * 128])
        assert documents.setdefault(r["document"], head) == head
    assert sorted(documents) == list(range(6))
    assert 4096 <= min(map(len, documents.values()))
    assert max(map(len, documents.values())) <= 8192
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
    one = family.kda_update_cost(m, 1.0)
    # a slot and layer: 32 heads of S [128, 128] and the window [3, 12288],
    # float32, read and written
    assert one["bytes"] == (32 * 128 * 128 + 3 * 12288) * 4 * 2 == 4_489_216
    assert one["flops"] == 32 * 128 * 128 * 7
    step = family.kda_update_cost(m, 128.0)
    # the issue's 3.8 GB of state, and the windows' 0.26
    assert 7 * step["bytes"] == pytest.approx(4.02e9, rel=2e-3)
    row = family.mla_attend_cost(m, 1.0)
    assert row["bytes"] == 576 * 2 and row["flops"] == 2 * 32 * (1024 + 64)
    rows = family.moe_experts_decode_cost(m, 1.0, 0.0)
    expert = family.moe_experts_decode_cost(m, 0.0, 1.0)
    assert expert["bytes"] == 3 * 2304 * 1024 * 2           # 14.2 MB
    assert rows["flops"] == 6 * 2304 * 1024
    # all 64 held experts of the 8 layers: the issue's 7.25 GB
    assert 8 * 64 * expert["bytes"] == pytest.approx(7.25e9, rel=2e-3)
    peaks = spec.peaks()["TPU v5 lite"]
    assert _moe_scopes.bound_seconds(one, peaks)[0] == "bytes"
    assert family.roofline_costs(m) == {
        "attention_layers": 2, "routed_experts": 64,
        "mla_attend_per_position": row, "moe_experts_per_row": rows,
        "moe_experts_per_touched_expert": expert, "kda_layers": 7,
        "kda_update_per_slot": one}


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, kind: str, dense: bool = False) -> dict:
    rng = np.random.default_rng(seed)
    d, inner, rank = 64, 32, 8

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": 1 + w(n, std=0.1)}

    if kind == "kda":
        out = {"kda": {
            "norm": scale(d), "w_qkv": w(d, 3 * inner),
            "conv_w": w(4, 3 * inner),
            "w_fgb": np.concatenate([w(d, rank), w(d, rank), w(d, 2),
                                     np.zeros((d, 126), np.float32)], axis=1),
            "w_f2": w(rank, inner),
            "dt_bias": rng.uniform(-4.0, 0.0, inner).astype(np.float32),
            "a_log": np.log(rng.uniform(1, 16, 2)).astype(np.float32),
            "w_g2": w(rank, inner),
            "g_bias": w(inner), "o_norm": scale(16), "w_o": w(inner, d)}}
    else:
        out = {"mla": {"norm": scale(d), "wq": w(d, 4 * 24), "wkva": w(d, 40),
                       "kv_norm": scale(32), "w_uk": w(4, 16, 32),
                       "w_uv": w(4, 32, 16),
                       "wo": w(64, d)}}
    if dense:
        out["dense"] = {"norm": scale(d), "w_in": w(d, 256), "w_out": w(128, d)}
    else:
        out["moe"] = {"norm": scale(d), "router": w(d, 8), "bias": w(8),
                      "shared": {"w_in": w(d, 64), "w_out": w(32, d)}}
        out["experts"] = {"wg": w(4, d, 32), "wu": w(4, d, 32),
                          "wd": w(4, 32, d)}
    return out


def _f64(tree):
    if isinstance(tree, dict):
        return {k: _f64(v) for k, v in tree.items()}
    return np.asarray(tree, np.float64)


def _norm(v, scale, eps=1e-5):
    return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale


def _silu(v):
    return v / (1 + np.exp(-v))


def _sigmoid(v):
    return 1 / (1 + np.exp(-v))


def kda_by_chunks(x, p, chunk=5):
    """The KDA mixer in float64 numpy by the chunked form: the state carried
    from chunk to chunk, within one the strictly lower triangular system
    solved by `numpy.linalg.solve`, the convolution by a sliding window.
    None of the reference's code, and not its formulation (a recurrence a
    token)."""
    x, p = np.asarray(x, np.float64), _f64(p)
    H, P, K = 2, 16, 4
    seq = len(x)
    u = _norm(x, p["norm"]["scale"])
    qkv = u @ p["w_qkv"]
    qkv = _silu(np.stack([sum(
        p["conv_w"][k] * (qkv[t - (K - 1 - k)] if t - (K - 1 - k) >= 0
                          else 0.0) for k in range(K)) for t in range(seq)]))
    q, k, v = (t.reshape(seq, H, P) for t in np.split(qkv, 3, axis=-1))
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(P)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    w_f1, w_g1, w_b = (p["w_fgb"][:, :8], p["w_fgb"][:, 8:16],
                       p["w_fgb"][:, 16:18])
    rate = np.log1p(np.exp((u @ w_f1) @ p["w_f2"] + p["dt_bias"]))
    log_a = -np.exp(p["a_log"])[:, None] * rate.reshape(seq, H, P)
    b = _sigmoid(u @ w_b)                                      # [T, H]
    o = np.zeros((seq, H, P))
    for h in range(H):
        s = np.zeros((P, P))
        for t0 in range(0, seq, chunk):
            t1 = min(seq, t0 + chunk)
            m = t1 - t0
            g = np.cumsum(log_a[t0:t1, h], axis=0)             # [m, N]
            kc, qc, vc, bc = k[t0:t1, h], q[t0:t1, h], v[t0:t1, h], b[t0:t1,
                                                                      h]
            a_mat, qk = np.zeros((m, m)), np.zeros((m, m))
            for i in range(m):
                for j in range(i + 1):
                    decay = np.exp(g[i] - g[j])
                    qk[i, j] = (qc[i] * kc[j] * decay).sum()
                    if j < i:
                        a_mat[i, j] = bc[i] * (kc[i] * kc[j] * decay).sum()
            rhs = bc[:, None] * (vc - (kc * np.exp(g)) @ s)
            us = np.linalg.solve(np.eye(m) + a_mat, rhs)
            o[t0:t1, h] = (qc * np.exp(g)) @ s + qk @ us
            s = np.exp(g[-1])[:, None] * s + (kc * np.exp(g[-1] - g)).T @ us
    gate = _sigmoid((u @ w_g1) @ p["w_g2"] + p["g_bias"])
    y = _norm(o, p["o_norm"]["scale"]).reshape(seq, H * P) * gate
    return x + y @ p["w_o"]


def mla_by_rows(x, p):
    """The MLA mixer in float64 numpy, a query at a time and a head at a
    time, keys and values by head: no blocks, no absorbed products, no
    rotation."""
    x, p = np.asarray(x, np.float64), _f64(p)
    heads, n, shared, r = 4, 16, 8, 32
    u = _norm(x, p["norm"]["scale"])
    q = (u @ p["wq"]).reshape(-1, heads, n + shared)
    ckr = u @ p["wkva"]
    c, k_r = _norm(ckr[:, :r], p["kv_norm"]["scale"]), ckr[:, r:]
    kv = np.concatenate([np.einsum("tr,hnr->thn", c, p["w_uk"]),
                         np.einsum("tr,hrv->thv", c, p["w_uv"])], axis=-1)
    out = np.zeros((len(x), heads, 16))
    for t in range(len(x)):
        for h in range(heads):
            s = (kv[:t + 1, h, :n] @ q[t, h, :n]
                 + k_r[:t + 1] @ q[t, h, n:]) / np.sqrt(n + shared)
            w = np.exp(s - s.max())
            out[t, h] = (w / w.sum()) @ kv[:t + 1, h, n:]
    return x + out.reshape(len(x), -1) @ p["wo"]


def mlp_by_tokens(x, p, model):
    """The layer's second half in float64 numpy, a token at a time: its
    eight... its K largest of s + bias, the held ones among them."""
    x, p = np.asarray(x, np.float64), _f64(p)

    def swiglu(h, m):
        ab = h @ m["w_in"]
        half = ab.shape[-1] // 2
        return (_silu(ab[..., :half]) * ab[..., half:]) @ m["w_out"]

    if "dense" in p:
        return x + swiglu(_norm(x, p["dense"]["norm"]["scale"]), p["dense"])
    m, e = p["moe"], p["experts"]
    first, held = model["first_expert"], len(e["wg"])
    out = np.zeros_like(x)
    for t, h in enumerate(_norm(x, m["norm"]["scale"])):
        s = _sigmoid(h @ m["router"])
        chosen = np.argsort(-(s + m["bias"]), kind="stable")[
            :model["num_experts_per_token"]]
        kept = s[chosen] / (s[chosen].sum() + 1e-20) * model[
            "routed_scaling_factor"]
        for gate, expert in zip(kept, chosen):
            if first <= expert < first + held:
                i = expert - first
                out[t] += gate * ((_silu(h @ e["wg"][i]) * (h @ e["wu"][i]))
                                  @ e["wd"][i])
        out[t] += swiglu(h, m["shared"])
    return x + out


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("kind,dense", [("kda", True), ("kda", False),
                                        ("mla", False)])
def test_reference_agrees_with_a_second_formulation(kind, dense, seed):
    p = tiny_layer(seed, kind, dense)
    x = np.random.default_rng(seed + 10).standard_normal((2, 19, 64)).astype(
        np.float32)
    got = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    mixer = kda_by_chunks if kind == "kda" else mla_by_rows
    for row, x_row in zip(got, x):
        want = mlp_by_tokens(mixer(x_row, p[kind]), p, TINY_MODEL)
        np.testing.assert_allclose(row, want, rtol=2e-4, atol=2e-4)


def test_the_reference_leaves_out_what_the_absent_experts_would_add():
    p = tiny_layer(2, "mla")
    x = np.random.default_rng(12).standard_normal((1, 9, 64)).astype(
        np.float32)
    part = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    # the same four experts held as experts 0..3: other tokens reach them
    moved = np.asarray(family.reference_layer(
        x, p, {**TINY_MODEL, "first_expert": 0}))
    assert np.abs(part - moved).max() > 1e-3


@pytest.mark.parametrize("degrade,kind", [
    ("bfloat16_state", "kda"), ("scalar_decay", "kda"), ("no_delta", "kda"),
    ("float8_rows", "mla")])
def test_a_degraded_reference_is_another_function(degrade, kind):
    p = tiny_layer(3, kind)
    x = np.random.default_rng(4).standard_normal((1, 40, 64)).astype(
        np.float32)
    plain = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, degrade))
    assert np.abs(off - plain).max() > 1e-3
    # and moves nothing of the other kind of layer
    q = tiny_layer(5, "mla" if kind == "kda" else "kda")
    np.testing.assert_array_equal(
        np.asarray(family.reference_layer(x, q, TINY_MODEL)),
        np.asarray(family.reference_layer(x, q, TINY_MODEL, degrade)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, "float8_state")


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "kimi.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_kda", "_mla", "_swiglu", "_expert_block",
                 "reference_layer", "reference_head", "Reference",
                 "reference_model", "kda_update_cost", "kv_bytes_per_token",
                 "state_bytes_per_slot", "_kda_layers", "_mla_layers"}
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
    config["deployment"].update({
        "preset": "kimi-tiny", "max_seq_len": 128, "max_batch": 4,
        "prefill_chunk_size": 16, "kv_blocks": 48, "kv_block_size": 8})
    return config


@pytest.fixture(scope="module")
def served():
    """What a busy engine served: four greedy replies, prompts of 36-45
    tokens sharing two contexts, through `LLMEngine.generate`; the router
    scores 256 experts, 8 a token, of which the first 64 are held."""
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
    # held and all pairs counted apart, on the device
    assert 0 < stats["moe_expert_rows"] < stats["moe_expert_rows_all"]
    assert stats["moe_expert_rows_all"] % 8 == 0
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


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,own,old", [
    ("jit(_step)/layers/while/body/closed_call/while/body/closed_call/attn/"
     "kda_update/jit(kda_update)/pallas_call", "kda_update", "attn"),
    ("jit(_step)/layers/while/body/attn/kda_update/exp:", "kda_update",
     "attn"),
    ("jit(_step)/attn/kda_project/jit(silu)/mul:", "kda_project", "attn"),
    ("jit(_chunk)/layers/while/body/attn/kda_chunk/while/body/"
     "hij,jhp->ihp/dot_general", "kda_chunk", "attn"),
    ("jit(_step)/layers/while/body/attn/kda_project/ln/mul:", "kda_project",
     "ln"),
    ("jit(_step)/layers/while/body/attn/mla_attend/dot_general:", None,
     "attn"),
    ("jit(_step)/layers/while/body/attn/kv_update/dynamic_update_slice:",
     None, "kv_update"),
    ("jit(_reset)/kv_update/dynamic_update_slice:", None, "kv_update"),
    ("jit(_copy_in)/prefix_pool/while/body/dynamic_update_slice:", None,
     "prefix_pool"),
    ("jit(_step)/layers/while/body/mlp/moe_experts/pallas_call", None,
     "mlp"),
    ("kda_update", None, "unscoped"), (None, None, "unscoped")])
def test_where_an_operation_belongs(tf_op, own, old):
    """The three new scopes are `attn` (or the inner `ln`) to `_scopes.py`,
    whose shares still sum to 100."""
    assert _kda_scopes.kda_scope_of(tf_op) == own
    assert _scopes.scope_of(tf_op) == old
    assert not set(_kda_scopes.KDA_SCOPES) & _scopes.SCOPES


STEP_OPS = {         # event -> tf_op; 10 ns each
    "%kda_update.1 = f32[8]{0} custom-call()":
        "jit(_step)/layers/while/body/attn/kda_update/jit(kda_update)/"
        "pallas_call",
    "%fusion.2 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kda_update/exp:",
    "%fusion.3 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kda_project/dot_general:",
    "%fusion.4 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/mla_attend/dot_general:",
    "%gmm.5 = f32[8]{0} custom-call()":
        "jit(_step)/layers/while/body/mlp/moe_experts/jit(gmm)/pallas_call",
    "%fusion.6 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/moe_shared/dot_general:",
    "%fusion.7 = f32[8]{0} fusion()": "jit(_step)/unembed_loss/dot_general:",
    "%fusion.8 = f32[8]{0} fusion()":
        "jit(_chunk)/layers/while/body/attn/kda_chunk/dot_general:",
    "%fusion.9 = f32[8]{0} fusion()":
        "jit(_copy_in)/prefix_pool/dynamic_update_slice:",
    "%fusion.10 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/kv_update/dynamic_update_slice:"}


@pytest.fixture(scope="module")
def served_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation of
    `STEP_OPS` for 10 ns, and the counters of a window of 10 decode steps
    that generated 1,280 tokens."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(STEP_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, STEP_OPS)))
    d = tmp_path_factory.mktemp("kimi_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    # so that a step's least time comes out at 8 ns under `kda_update` (128
    # slots x 7 layers), at 4 ns under `mla_attend` (a step attends 5,000
    # positions x 2 layers) and at 5 ns under `moe_experts` (50 touched
    # experts a step)
    costs = {"kda_layers": 7, "kda_update_per_slot": {
                 "bytes": 8e-9 * peaks["hbm_bytes_per_s"] / (128 * 7),
                 "flops": 1.0},
             "attention_layers": 2, "routed_experts": 64,
             "mla_attend_per_position": {
                 "bytes": 0.0,
                 "flops": 4e-9 * peaks["bf16_flops_per_s"] / 10_000},
             "moe_experts_per_row": {"bytes": 0.0, "flops": 0.0},
             "moe_experts_per_touched_expert": {
                 "bytes": 5e-9 * peaks["hbm_bytes_per_s"] / 50,
                 "flops": 0.0}}

    def counts(rows, held, touched, positions, steps):
        return {"decode": {"expert_rows": held, "experts_touched": touched,
                           "busiest_expert_rows": 0,
                           "expert_layer_steps": 8 * steps,
                           "attended_positions": positions,
                           "expert_rows_all": rows},
                "chunk": {k: 0 for k in (
                    "expert_rows", "experts_touched", "busiest_expert_rows",
                    "expert_layer_steps", "attended_positions",
                    "expert_rows_all")}}

    return {"trace_dir": str(d), "peaks": peaks, "counters": {
        "before": {"engine_steps": 100, "chunk_steps": 0,
                   "total_generated": 1000,
                   "step_counts": counts(2 ** 32 - 8, 100, 10, 7, 0)},
        "after": {"engine_steps": 110, "chunk_steps": 0,
                  "total_generated": 2280,
                  # the uint32 wrapped: 81,920 pairs more, 22,938 of them held
                  "step_counts": counts(81_912, 23_038, 510, 50_007, 10),
                  "state_bytes_per_slot": 15_712_256,
                  "kv_bytes_per_token": 2304, "roofline_costs": costs}}}


@pytest.mark.parametrize("name,want", [
    ("kda_update_time_pct", 20.0), ("kda_project_time_pct", 10.0),
    ("kda_chunk_time_pct", 10.0), ("mla_attend_time_pct", 10.0),
    ("moe_experts_time_pct.decode", 10.0),
    ("engine_attn_time_pct", 50.0),
    ("engine_mlp_time_pct", 20.0),
    ("engine_head_time_pct", 10.0),
    ("engine_prefix_pool_time_pct", 10.0),
    ("kv_update_time_pct.decode", 10.0),
    ("state_bytes_per_slot", 15_712_256),
    ("kv_bytes_per_token", 2304),
    ("moe_held_rows_pct", 100 * 22_938 / 81_920),
    # 8 ns of the 20 a step spends under kda_update; 4 of mla_attend's 10; 5
    # of moe_experts' 10
    ("kda_update_roofline_pct", 40.0),
    ("mla_attend_roofline_pct", 40.0),
    ("moe_experts_decode_roofline_pct", 50.0)])
def test_every_new_entry_reads_its_number(served_record, name, want):
    assert spec.metric_reader(name).read(served_record) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "kda_update_time_pct", "kda_chunk_time_pct", "kda_project_time_pct",
    "kda_update_roofline_pct", "moe_held_rows_pct"])
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
    if name in ("kda_update_roofline_pct", "moe_held_rows_pct"):
        assert read({**parent,
                     "trace_dir": served_record["trace_dir"]}) is None
    # Kanana's program counts no `expert_rows_all`: nothing, not 100
    if name == "moe_held_rows_pct":
        kanana = json.loads(json.dumps(served_record["counters"]))
        for side in kanana.values():
            for program in side["step_counts"].values():
                del program["expert_rows_all"]
        assert read({"counters": kanana}) is None


def test_a_trace_without_the_scopes_reads_as_nothing(tmp_path):
    """GPT-2's, Kanana's, Brumby's and Granite's programs have none of the
    three."""
    ops = {"%fusion.1 = f32[8]{0} fusion()":
           "jit(_step)/layers/while/body/attn/dot_general:"}
    space = _msg((1, _plane(DEVICE, {
        tr.OPS_LINE: [(0, 10, next(iter(ops)))],
        tr.MODULES_LINE: [(0, 10, "jit__step(1)")]}, ops)))
    os.makedirs(tmp_path / "plugins" / "profile" / "t")
    (tmp_path / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(
        space)
    record = {"trace_dir": str(tmp_path)}
    for scope in _kda_scopes.KDA_SCOPES:
        assert _kda_scopes.share(record, scope) is None
        assert _kda_scopes.step_seconds(record, scope) is None


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_kimi.py`: the generator, the warm-up, the pool
    hits of both kinds, the engine's counters and `check_served`, through
    the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_kimi.py"),
         "--workload", CELL, "--seconds", "6", "--seed", "2400000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    # how many replies end in six seconds is the host's to say (13 beside
    # five other test workers, 84 alone); the check needs one
    assert line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 80
    assert other["state_bytes_per_slot"]["value"] == 3 * (
        2 * 16 * 16 + 3 * 3 * 32) * 4
    assert other["kv_bytes_per_token"]["value"] == 2 * 40 * 2
    # 64 of 256 held: a quarter of the pairs, under the seed's skew
    assert 10 < other["moe_held_rows_pct"]["value"] < 45
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import importlib

    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("kimi")
    try:
        with pytest.raises(ValueError, match="kimi-linear-48b-a3b"):
            importlib.import_module("ray_tpu.serve.llm").LLMEngine(
                **family.engine_options(CONFIG, 1))
    finally:
        models._SERVING.update(saved)
