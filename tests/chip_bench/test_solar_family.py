"""The Solar family file on the CPU: its configuration against the catalog's
row, its `memory` against the arithmetic, its reference against a second
formulation (the softmax layer a query at a time in numpy float64; the delta
rule against Kimi's reference, whose layer it is but for b's factor), its
arithmetic against hand counts, the traffic file, what the cell reads, the
reader of the one new entry on hand-made records, and the cell end to end at
a tiny size."""

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

from families import kimi as kimi_family  # noqa: E402
from families import solar as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402

CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "solar-open2-250b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "long-context-agent-turns.json"))
CELL = "serve-solar-longctx"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "gqa_layers"]
# the lists the issue names: Kimi's 31 but latent attention's two, granite's
# two of grouped-head attention, and the entry of its own
KIMIS = {"kda_update_time_pct", "kda_chunk_time_pct", "kda_project_time_pct",
         "kda_update_roofline_pct", "moe_experts_time_pct.decode",
         "moe_experts_decode_roofline_pct", "moe_held_rows_pct",
         "engine_attn_time_pct", "engine_mlp_time_pct",
         "engine_head_time_pct", "engine_prefix_pool_time_pct",
         "kv_bytes_per_token", "state_bytes_per_slot",
         "setup_engine_build_s"}
OWN = KIMIS | {"gqa_attend_time_pct", "gqa_attend_roofline_pct",
               "gqa_rows_read_pct"}
TINY = {"vocab_size": 512, "num_hidden_layers": 4, "gqa_layers": [0],
        "linear_attn_config": {"head_dim": 16, "num_heads": 2,
                               "num_kv_heads": None,
                               "short_conv_kernel_size": 4},
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 40, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16}
# the reference's model at the tiny size: 8 experts of which 4 are held
TINY_MODEL = {**CONFIG["model"], **TINY, "n_routed_experts": 4,
              "num_experts_per_tok": 3, "router_outputs": 8,
              "first_expert": 2, "rows": "float32"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_four_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Solar-Open2-250B"]
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
    assert (m["num_hidden_layers"], m["n_routed_experts"], m["vocab_size"],
            m["gqa_layers"]) == (4, 40, 24576, [0])
    # the kept layers are the published model's first period
    assert m["gqa_layers"] == [l for l in row["config"]["gqa_layers"]
                               if l < 4]
    # every published width unchanged
    assert (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            m["moe_intermediate_size"], m["num_experts_per_tok"]) == (
        4096, 64, 8, 128, 1280, 8)
    assert m["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    # the guide's floors exactly: a whole period and four layers, 8 experts
    # or more, an eighth of the vocabulary
    assert m["num_hidden_layers"] == 4 == m["gqa_interval"] + 1
    assert m["n_routed_experts"] >= 8 and m["vocab_size"] * 8 == 196608
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "pipeline_stages", "router_outputs",
        "first_expert", "first_vocab_row")} == {
        "chips_sharing_a_layer": 8, "pipeline_stages": 12,
        "router_outputs": 320, "first_expert": 0, "first_vocab_row": 0}
    assert m["n_routed_experts"] * 8 == share["router_outputs"]
    assert "1.0 row a held expert" in share["experts_load"]
    assert "47%" in share["experts_load"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "solar")
    assert CONFIG["deployment"] == {
        "preset": "solar-open2-250b", "max_seq_len": 25600,
        "max_batch": 40, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 800, "kv_block_size": 128}
    # the three sizes the source has no key for, each with its reason
    assert CONFIG["assumed_sizes"] == {"kda_gate_rank": 128,
                                       "router_scoring": "sigmoid"}
    assert {"gqa_gate", "kda_gate_rank", "router", "intermediate_size",
            "state_dtype", "state_layout", "float32_islands", "weights",
            "no_rotation", "tokenizer", "routing_load", "deployment_sizes",
            "kv_blocks"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    assert "used by no layer" in CONFIG["assumed"]["intermediate_size"]
    assert "eight-chip" in CONFIG["stands_for"]
    assert "twelve pipeline stages" in CONFIG["stands_for"]
    assert len(CONFIG["departures"]) >= 6
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
    assert slot == 3 * (64 * 128 * 128 + 3 * 3 * 8192) * 4 == 13_467_648
    assert memory["kv_bytes_per_token"] == 2 * 8 * 128 * 2 == 4096
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 4 == TRAFFIC["documents"]
    assert memory["prefix_pool_bytes"] == (
        snapshots * slot + d["kv_blocks"] * d["kv_block_size"] * 4096)
    assert family.state_bytes_per_slot(CONFIG["model"]) == slot
    assert family.kv_bytes_per_token(CONFIG["model"]) == 4096
    # 6.62 GB of weights (with the padding beside W_b), 4.19 of rows, 0.54
    # of state: the arguments of both programs
    rows = d["max_batch"] * d["max_seq_len"] * 4096
    state = d["max_batch"] * slot
    weights = memory["arguments_bytes"] - rows - state
    assert rows == 4_194_304_000 and state == 538_705_920
    assert weights == pytest.approx(2 * 3_309_164_352, rel=2e-3)
    # neither program holds a copy of a leaf, nor the chunk program a slot's
    # scores over all positions (8 x 2 x 1,024 rows x 25,600 floats, 1.7 GB):
    # its temporaries are less than those alone would be
    assert chunk - memory["arguments_bytes"] < 8 * 2048 * 25600 * 4


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.d_ff_expert, cfg.n_head, cfg.n_kv_head,
            cfg.gqa_head_dim) == (4096, 1280, 64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_rank,
            cfg.kda_inner, cfg.kda_neg_eigval) == (64, 128, 4, 128, 8192,
                                                   True)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert, cfg.n_shared_experts) == (320, 8, 40, 0, 1)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 1.0)
    assert (cfg.n_layer, cfg.n_dense_layer, cfg.vocab_size,
            cfg.max_seq_len, cfg.norm_eps) == (4, 0, 24576, 25600, 1e-5)
    assert cfg.layer_types == ("gqa", "kda", "kda", "kda")
    assert family.CharTokenizer.eos_id == 24575 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 24574, 7])) == [1, 24574, 7]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_what_the_file_states_of_the_cache_is_what_the_program_holds():
    """`stated` against the program: the leaves' shape and dtypes at the
    cell's sizes, and, in the decode program at the tiny size, the dtype q
    is projected in and the bf16 rows (pieces x queries a key-value head)
    that meet the cached keys and values."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import serving_family

    stated, d = CONFIG["stated"], CONFIG["deployment"]
    _, module, config_cls = serving_family(d["preset"])
    cache = jax.eval_shape(lambda: module.init_cache(
        family.program_config(CONFIG), d["max_batch"], d["max_seq_len"]))
    for leaf in ("k", "v"):
        assert list(cache[leaf].shape) == stated["rows_leaf"]
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        assert module.CACHE_TOKEN_AXIS[leaf] == stated[
            "rows_leaf_axes"].index("positions")
    assert cache["kda"].dtype == cache["conv"].dtype == jnp.dtype(
        stated["state"])
    assert stated["rows"] in CONFIG["assumed"]["state_dtype"]
    assert "[8, T, 128]" in CONFIG["assumed"]["state_layout"]
    assert family.reference_model(CONFIG)["rows"] == stated["rows"]

    cfg = config_cls.preset("solar-tiny")
    B, T = 3, 64
    G, R, lanes = cfg.n_kv_head, cfg.queries_per_kv, cfg.gqa_head_dim
    params = jax.eval_shape(lambda: module.resident_params(
        module.init_params(jax.random.key(0), cfg), cfg))
    tiny = jax.eval_shape(lambda: module.init_cache(cfg, B, T))
    jaxpr = jax.make_jaxpr(lambda p, c, t, pos, on: module.decode_step(
        p, c, t, pos, on, cfg))(
        params, tiny, jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.bool_))
    rows = jnp.dtype(stated["rows"])
    met = [tuple(v.aval for v in eqn.invars)
           for eqn in _equations(jaxpr.jaxpr)
           if eqn.primitive.name == "dot_general"
           and any(v.aval.shape == (B, G, T, lanes) for v in eqn.invars)]
    # scores and weighted values, in each of the two softmax layers' loop
    assert len(met) >= 2
    for a, b in met:
        other = b if a.shape == (B, G, T, lanes) else a
        assert a.dtype == b.dtype == rows
        assert other.shape[:3] == (B, G, stated["gqa_pieces"] * R)
    wq = [eqn for eqn in _equations(jaxpr.jaxpr)
          if eqn.primitive.name == "reshape"
          and eqn.outvars[0].aval.shape == (B, 1, G, R, lanes)]
    assert wq and all(eqn.outvars[0].aval.dtype == jnp.dtype(
        stated["gqa_query"]) for eqn in wq)


def test_the_cell_reads_what_it_reads():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and OWN <= names
    assert names.isdisjoint({"mla_attend_time_pct",
                             "mla_attend_roofline_pct"})
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    (own,) = [m for m in bench["per_layer"]
              if m["name"] == "gqa_rows_read_pct"]
    assert own == {"name": "gqa_rows_read_pct", "unit": "%",
                   "better": "lower", "source": "program_counter",
                   "layer": "engine programs", "moves": "serve_tokens_per_s",
                   "workloads": [CELL]}
    assert bench["per_layer"][-1] == own and len(bench["per_layer"]) <= 128
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG["name"]
    # what the cell's kimi-like lists are: the cell is on every list Kimi's
    # is on but latent attention's two
    kimis = {m["name"] for m in bench["per_layer"]
             if "serve-kimi-longgen" in m.get("workloads", [])}
    assert kimis - names == {"mla_attend_time_pct", "mla_attend_roofline_pct"}
    assert "1 row a held expert" in bench["workloads"][-1]["why"]


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 50,
        "requests_per_client": 12, "documents": 4,
        "document_uniform": [16384, 24576], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [256, 512],
        "schedule_seed": 49, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert TRAFFIC["question_uniform"][1] <= d["prefill_chunk_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_contexts_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 50 * 12 and plan["clients"] == 50
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        blocks = max(b for b in range(128, 193) if b * 128 <= n - 16)
        assert 16 <= n - blocks * 128 <= 64
        assert 256 <= r["max_tokens"] <= 512 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 24576
        head = tuple(r["prompt_ids"][:blocks * 128])
        assert documents.setdefault(r["document"], head) == head
    assert sorted(documents) == list(range(4))
    assert 16384 <= min(map(len, documents.values()))
    assert max(map(len, documents.values())) <= 24576
    assert len(plan["warmup"]) == 5
    for w, d in zip(plan["warmup"], [0, 1, 2, 3, 0]):
        assert tuple(w["prompt_ids"][:len(documents[d])]) == documents[d]
        assert w["max_tokens"] == 2
    other = closed_loop_documents.generate(TRAFFIC, CONFIG, seed + 1, 51.0)
    assert [(len(r["prompt_ids"]), r["max_tokens"], r["document"])
            for r in requests] == [
        (len(r["prompt_ids"]), r["max_tokens"], r["document"])
        for r in other["requests"]]
    assert requests[0]["prompt_ids"] != other["requests"][0]["prompt_ids"]


def test_roofline_costs_against_hand_counts():
    m = CONFIG["model"]
    one = family.kda_update_cost(m, 1.0)
    # a slot and layer: 64 heads of S [128, 128] and the window [3, 24576],
    # float32, read and written
    assert one["bytes"] == (64 * 128 * 128 + 3 * 24576) * 4 * 2 == 8_978_432
    assert one["flops"] == 64 * 128 * 128 * 7
    row = family.gqa_attend_cost(m, 1.0)
    assert row["bytes"] == 4096 and row["flops"] == 2 * 64 * 128 * 2
    # 40 slots at all 25,600 positions: the issue's 4.2 GB of rows
    assert family.gqa_attend_cost(m, 40 * 25600.0)["bytes"] == 4_194_304_000
    costs = family.roofline_costs(m)
    expert = costs["moe_experts_per_touched_expert"]
    assert expert["bytes"] == 3 * 4096 * 1280 * 2            # 31.5 MB
    assert costs["moe_experts_per_row"]["flops"] == 6 * 4096 * 1280
    peaks = spec.peaks()["TPU v5 lite"]
    assert _moe_scopes.bound_seconds(one, peaks)[0] == "bytes"
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    assert costs == {
        "gqa_layers": 1, "gqa_attend_per_position": row,
        "routed_experts": 40, "moe_experts_per_row":
            costs["moe_experts_per_row"],
        "moe_experts_per_touched_expert": expert, "kda_layers": 3,
        "kda_update_per_slot": one}


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, kind: str) -> dict:
    rng = np.random.default_rng([seed, 0x501A])

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": (1 + 0.1 * rng.standard_normal(n)).astype(
            np.float32)}

    d, inner, heads, rank, f, e = 64, 32, 2, 8, 40, 8
    if kind == "kda":
        mixer = {"norm": scale(d), "w_qkv": w(d, 3 * inner),
                 "conv_w": w(4, 3 * inner),
                 "w_fgb": np.concatenate(
                     [w(d, rank), w(d, rank), w(d, heads),
                      np.zeros((d, 126), np.float32)], axis=1),
                 "w_f2": w(rank, inner), "dt_bias": w(inner),
                 "a_log": w(heads), "w_g2": w(rank, inner),
                 "g_bias": w(inner), "o_norm": scale(16),
                 "w_o": w(inner, d)}
    else:
        mixer = {"norm": scale(d), "wq": w(d, 4 * 16), "wk": w(d, 2 * 16),
                 "wv": w(d, 2 * 16), "w_gate": w(d, 4 * 16),
                 "wo": w(4 * 16, d)}
    return {kind: mixer,
            "moe": {"norm": scale(d), "router": w(d, e), "bias": w(e, std=.1),
                    "shared": {"w_in": w(d, 2 * f), "w_out": w(f, d)}},
            "experts": {"wg": w(4, d, f), "wu": w(4, d, f),
                        "wd": w(4, f, d)}}


def gqa_by_queries(x, p, rows=lambda a: a):
    """The softmax layer a query at a time, float64; `rows` is what the
    cache does to k and v."""
    m = {k: (v["scale"] if isinstance(v, dict) else v).astype(np.float64)
         for k, v in p["gqa"].items()}
    x = x.astype(np.float64)
    u = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * m["norm"]
    q = (u @ m["wq"]).reshape(-1, 4, 16)
    k, v = (rows(u @ m[n]).reshape(-1, 2, 16) for n in ("wk", "wv"))
    out = np.zeros((len(x), 4, 16))
    for t in range(len(x)):
        for h in range(4):
            s = k[:t + 1, h // 2] @ q[t, h] / 4.0
            e = np.exp(s - s.max())
            out[t, h] = (e / e.sum()) @ v[:t + 1, h // 2]
    gate = 1 / (1 + np.exp(-(u @ m["w_gate"])))
    return x + (out.reshape(len(x), -1) * gate) @ m["wo"]


@pytest.mark.parametrize("seed", [0, 1])
def test_the_softmax_layer_agrees_with_a_second_formulation(seed):
    p = tiny_layer(seed, "gqa")
    x = np.random.default_rng(seed).standard_normal((1, 19, 64)).astype(
        np.float32)
    import jax

    with jax.default_matmul_precision("highest"):
        u = family._rms_norm(x[0], p["gqa"]["norm"]["scale"], 1e-5)
        got = x[0] + family._gqa(u, p["gqa"], TINY_MODEL, None)
    np.testing.assert_allclose(got, gqa_by_queries(x[0], p), atol=2e-5)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_delta_rule_is_kimis_reference_with_b_doubled(seed):
    """Two references written apart: without b's factor this family's KDA
    layer is Kimi's to rounding, with it another function."""
    import jax

    p = tiny_layer(seed, "kda")["kda"]
    u = np.random.default_rng(seed).standard_normal((1, 23, 64)).astype(
        np.float32)
    model = {**TINY_MODEL}
    with jax.default_matmul_precision("highest"):
        kimis = kimi_family._kda(u, p, model, None)[0]
        halved = family._kda(u[0], p, model, "b_in_0_1")
        own = family._kda(u[0], p, model, None)
    np.testing.assert_allclose(halved, kimis, atol=1e-5)
    assert np.abs(np.asarray(own) - np.asarray(kimis)).max() > 1e-3


def test_the_reference_leaves_out_what_the_absent_experts_would_add():
    import jax

    p = tiny_layer(0, "gqa")
    h = np.random.default_rng(0).standard_normal((11, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out, chosen = family._expert_block(h, p["moe"], p["experts"],
                                           TINY_MODEL)
        shared = family._swiglu(h, p["moe"]["shared"])
    chosen = np.asarray(chosen)
    assert chosen.shape == (11, 3) and chosen.max() < 8
    none_held = ~((chosen >= 2) & (chosen < 6)).any(axis=1)
    if none_held.any():
        np.testing.assert_allclose(np.asarray(out)[none_held],
                                   np.asarray(shared)[none_held], atol=1e-6)
    assert (~none_held).any()
    assert np.abs(np.asarray(out - shared)[~none_held]).max() > 1e-3


@pytest.mark.parametrize("degrade,kind", [
    ("bfloat16_state", "kda"), ("b_in_0_1", "kda"),
    ("bfloat16_scores", "gqa"), ("no_gate", "gqa")])
def test_a_degraded_reference_is_another_function(degrade, kind):
    p = tiny_layer(3, kind)
    x = np.random.default_rng(3).standard_normal((2, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, degrade))
    assert np.isfinite(off).all() and np.abs(exact - off).max() > 1e-6
    # and it touches only its own kind of layer
    other = tiny_layer(3, "gqa" if kind == "kda" else "kda")
    np.testing.assert_array_equal(
        np.asarray(family.reference_layer(x, other, TINY_MODEL)),
        np.asarray(family.reference_layer(x, other, TINY_MODEL, degrade)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, "float8_state")


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "solar.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_kda", "_gqa", "_swiglu", "_expert_block",
                 "_reference_row", "reference_layer", "reference_head",
                 "Reference", "reference_model", "gqa_attend_cost",
                 "kv_bytes_per_token", "state_bytes_per_slot", "_kda_layers",
                 "experts_cost_model"}
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
               "engine_logit_mean_abs": 1e-3, "engine_logit_floor_abs": 1e-4}
    assert family.verdict(passing)["ok"] is True
    assert set(family.LIMITS) == set(passing)
    for name, limit in family.LIMITS.items():
        assert family.verdict({**passing, name: 2 * limit})["ok"] is False
    assert family.verdict({"error": "nothing served"})["ok"] is False
    limits = CONFIG["limits"]
    for name, limit in family.LIMITS.items():
        assert limits[name]["limit"] == limit
    # the floor holds the precision: its limit lies between its two
    # readings with three times of room on both sides, above every reading
    # of the program and under each degradation's narrowest, and under q
    # and the probabilities as one piece
    floor = limits["engine_logit_floor_abs"]
    assert max(floor["program"] + floor["cell"]) * 3 <= floor["limit"]
    assert set(floor["refused"]) == set(family.DEGRADE) - {None}
    assert floor["limit"] * 3 <= min(
        min(readings) for readings in floor["refused"].values())
    assert floor["limit"] * 1.5 <= min(floor["one_piece"])
    # the mean holds a fault in a minority of the positions: three times
    # above the program's widest, under both roundings all the same, and
    # far under the other mathematics
    mean = limits["engine_logit_mean_abs"]
    assert max(mean["program"] + mean["cell"]) * 3 <= mean["limit"]
    assert mean["limit"] < min(min(readings)
                               for readings in mean["refused"].values())
    assert mean["limit"] * 3 <= min(mean["refused"]["no_gate"]
                                    + mean["refused"]["b_in_0_1"])
    # and the file says what the mean lets pass
    assert all(max(readings) < mean["limit"] for readings in
               mean["passed_and_refused_by_the_floor"].values())


def test_the_floor_is_the_tenth_percentile_over_the_positions():
    """One reply of twenty positions: eighteen lie 0.001 from the
    reference's logits in the mean and two, where a router chose otherwise,
    0.5: the mean reads the two, the floor does not; a rounding, which moves
    every position, moves both."""
    rng = np.random.default_rng(0)
    reference = [rng.standard_normal((20, 32)).astype(np.float32)]
    served = [{"prompt_ids": [1, 2], "token_ids": reference[0].argmax(
        axis=-1).tolist()}]
    off = np.full((20, 1), 0.001, np.float32)
    off[[3, 11]] = 0.5
    got = family.compare(served, [reference[0] + off], reference)
    assert got["engine_logit_mean_abs"] == pytest.approx(0.0509, rel=1e-3)
    assert got["engine_logit_floor_abs"] == pytest.approx(0.001, rel=1e-3)
    everywhere = family.compare(served, [reference[0] + 0.01], reference)
    assert everywhere["engine_logit_floor_abs"] == pytest.approx(0.01,
                                                                 rel=1e-3)
    bad = [np.full((20, 32), np.nan, np.float32)]
    assert "error" in family.compare(served, bad, reference)


def test_the_reference_holds_the_rows_as_the_file_states_them():
    """k and v through bfloat16 where `rows` says so, and only there: the
    softmax layer moves, by about a key's rounding, the delta rule's layer
    does not; and the stated reference is the float32 one computed on keys
    and values rounded beforehand."""
    import jax
    import jax.numpy as jnp

    x = np.random.default_rng(5).standard_normal((2, 40, 64)).astype(
        np.float32)
    stated = {**TINY_MODEL, "rows": "bfloat16"}
    p = tiny_layer(5, "gqa")
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    rounded = np.asarray(family.reference_layer(x, p, stated))
    assert 1e-5 < np.abs(exact - rounded).max() < 0.1 * np.abs(exact).max()
    kda = tiny_layer(5, "kda")
    np.testing.assert_array_equal(
        np.asarray(family.reference_layer(x, kda, TINY_MODEL)),
        np.asarray(family.reference_layer(x, kda, stated)))
    with jax.default_matmul_precision("highest"):
        u = family._rms_norm(x[0], p["gqa"]["norm"]["scale"], 1e-5)
        got = np.asarray(x[0] + family._gqa(u, p["gqa"], stated, None))
    want = gqa_by_queries(x[0], p, rows=lambda a: np.asarray(
        jnp.asarray(a, jnp.float32).astype(jnp.bfloat16).astype(
            jnp.float32), np.float64))
    np.testing.assert_allclose(got, want, atol=2e-5)


# ------------------------------------------------------------- the reader

def record(decode_before, decode_after, costs, steps=10):
    zero = {k: 0 for k in decode_after}
    return {"counters": {
        "before": {"engine_steps": 100, "chunk_steps": 0,
                   "step_counts": {"decode": decode_before, "chunk": zero}},
        "after": {"engine_steps": 100 + steps, "chunk_steps": 0,
                  "step_counts": {"decode": decode_after, "chunk": zero},
                  "roofline_costs": costs}}}


def test_the_new_entry_reads_its_number_and_nothing_where_there_is_none():
    read = spec.metric_reader("gqa_rows_read_pct").read
    costs = family.roofline_costs(CONFIG["model"])
    before = {"attended_positions": 2 ** 32 - 1000, "read_positions": 7}
    after = {"attended_positions": 8_000_000 - 1000,
             "read_positions": 7 + 10_240_000}
    # 40 lanes x 10 steps at a mean position of 20,000 of 25,600, the
    # attended count wrapped
    assert read(record(before, after, costs)) == pytest.approx(128.0)
    # Kimi's program counts both and has no such layer; Kanana's counts
    # neither; a parent has no counters at all
    assert read(record(before, after, kimi_family.roofline_costs(
        spec.load_json(os.path.join(
            CHIP_DIR, "configs",
            "kimi-linear-48b-a3b-serve-1chip.json"))["model"]))) is None
    assert read(record({"expert_rows": 1}, {"expert_rows": 9},
                       costs)) is None
    assert read({"counters": None}) is None and read({}) is None
    assert read(record(before, {**after, "attended_positions":
                                before["attended_positions"]}, costs)) is None


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_solar.py`: the generator, the warm-up, the pool
    hits of both kinds, the engine's counters and `check_served`, through
    the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_solar.py"),
         "--workload", CELL, "--seconds", "6", "--seed", "2490000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 80
    assert other["state_bytes_per_slot"]["value"] == 3 * (
        2 * 16 * 16 + 3 * 3 * 32) * 4
    assert other["kv_bytes_per_token"]["value"] == 2 * 2 * 16 * 2
    # 40 of 320 held: an eighth of the pairs, under the seed's skew
    assert 3 < other["moe_held_rows_pct"]["value"] < 30
    # every decode lane read all 128 positions and stood below them
    assert other["gqa_rows_read_pct"]["value"] > 100
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("solar")
    try:
        with pytest.raises(ValueError, match="no serving family has the "
                                             "preset 'solar-open2-250b'"):
            family.program_config(CONFIG)
    finally:
        models._SERVING.update(saved)
