"""The MiMo family file on the CPU: its configuration against the catalog's
row, its `memory` against the arithmetic, its reference against a second
formulation (attention a token at a time in numpy float64 under a window or
none, 8 or 4 key-value heads, the first lanes rotated at the kind's theta,
the sink in the denominator; the expert block a token at a time), its
arithmetic against hand counts, the traffic file, what the cell reads (and
what the tests a fifteenth cell breaks held of the file), and the cell end
to end at a tiny size."""

import ast
import copy
import importlib
import json
import math
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

from families import mimo as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402


CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "mimo-v2.5-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "mixed-length-hot-documents.json"))
CELL = "serve-mimo-mixedqueue"
KEXAONE, LONGCAT = "serve-kexaone-hotdocs", "serve-longcat-assistant"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
S, G = family.SLIDING, family.GLOBAL
# the lists the issue names beside every `.decode` reading and `engine_*`:
# every list K-EXAONE's cell is on but the shared expert's
OWN = {"setup_engine_build_s", "gqa_attend_time_pct",
       "gqa_attend_roofline_pct", "gqa_rows_read_pct", "kv_bytes_per_token",
       "state_bytes_per_slot", "moe_router_time_pct.decode",
       "moe_dispatch_time_pct.decode", "moe_experts_time_pct.decode",
       "mlp_dense_time_pct", "moe_experts_touched_per_layer",
       "moe_decode_load_max_over_mean", "moe_held_rows_pct",
       "moe_experts_decode_roofline_pct", "rows_without_snapshot_tokens",
       "swa_attend_time_pct"}
ENGINE = {"engine_attn_time_pct", "engine_mlp_time_pct",
          "engine_head_time_pct", "engine_prefix_pool_time_pct",
          "engine_offcpu_ms.decode", "engine_release_ms.decode",
          "engine_put_ms.decode", "engine_dispatch_ms.decode",
          "engine_admit_ms.decode", "engine_slow_pass_pct.decode",
          "idle_in_admit_pct.decode", "idle_in_dispatch_pct.decode"}
# the reference's model at the tiny size: a window of 16, 8 query heads, 2
# and 4 key-value heads, keys of 24 lanes (8 rotate) and values of 16, 8
# router outputs of which experts 2..5 are held
TINY_MODEL = {**CONFIG["model"], "num_hidden_layers": 5, "hidden_size": 64,
              "intermediate_size": 128, "moe_intermediate_size": 32,
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "swa_num_key_value_heads": 4, "head_dim": 24,
              "v_head_dim": 16, "sliding_window": 16,
              "n_routed_experts": 4, "num_experts_per_tok": 3,
              "router_outputs": 8, "first_expert": 2, "rows": "float32"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_three_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "MiMo-V2.5"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert {k: CONFIG[k] for k in REDUCED} == {
        k: CONFIG["model"][k] for k in REDUCED}
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED} == {
        "num_hidden_layers": 48, "n_routed_experts": 256,
        "vocab_size": 152576}
    m = CONFIG["model"]
    assert (m["num_hidden_layers"], m["n_routed_experts"],
            m["vocab_size"]) == (7, 8, 19072)
    # every published width unchanged
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"]) == (4096, 16384, 2048)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["swa_num_key_value_heads"], m["head_dim"],
            m["v_head_dim"]) == (64, 4, 8, 192, 128)
    assert (m["num_experts_per_tok"], m["n_shared_experts"],
            m["scoring_func"], m["norm_topk_prob"],
            m["routed_scaling_factor"]) == (8, None, "sigmoid", True, None)
    assert (m["sliding_window"], m["rope_theta"], m["swa_rope_theta"],
            m["partial_rotary_factor"], m["attention_value_scale"],
            m["layernorm_epsilon"]) == (128, 10000000, 10000, 0.334, 0.707,
                                        1e-5)
    assert family.rotary_lanes(m) == 64
    assert (m["add_swa_attention_sink_bias"],
            m["add_full_attention_sink_bias"],
            m["attention_projection_layout"]) == (True, False, "fused_qkv")
    # the lists a layer stay whole, 48 entries; the layers that are run are
    # their first 7: the dense layer and the whole period after it
    assert len(m["hybrid_layer_pattern"]) == len(m["moe_layer_freq"]) == 48
    assert family.layer_types(m) == [G, S, S, S, S, G, S]
    assert m["moe_layer_freq"][:7] == [0] + [1] * 6
    # the guide's floors: four layers after the dense one, 8 experts or
    # more, an eighth of the vocabulary
    assert m["num_hidden_layers"] - 1 >= 4
    assert m["n_routed_experts"] >= 8 and m["vocab_size"] * 8 == 152576
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "pipeline_stages", "router_outputs",
        "first_expert", "vocabulary_shares", "first_vocab_row")} == {
        "chips_sharing_a_layer": 32, "pipeline_stages": 7,
        "router_outputs": 256, "first_expert": 0, "vocabulary_shares": 8,
        "first_vocab_row": 0}
    assert m["n_routed_experts"] * 32 == share["router_outputs"]
    assert 6 * m["num_hidden_layers"] + 6 == 48     # six stages of 7, one of 6
    assert "2 rows a held expert" in share["experts_load"]
    assert "a thirty-second" in share["experts_load"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "mimo")
    assert CONFIG["deployment"] == {
        "preset": "mimo-v2.5", "max_seq_len": 24576, "max_batch": 64,
        "scheduler": "continuous", "enable_prefix_caching": True,
        "prefill_chunk_size": 128, "kv_blocks": 1920, "kv_block_size": 128}
    # 24 of `slot_rows`' blocks; the chunk and the pool's block the window
    assert CONFIG["deployment"]["max_seq_len"] == 24 * 1024
    assert CONFIG["deployment"]["prefill_chunk_size"] == CONFIG["deployment"][
        "kv_block_size"] == m["sliding_window"]
    assert {"pre_norm", "fused_projection", "window", "sink", "rope",
            "value_scale", "score_scale", "gates", "hidden_act",
            "untied_embeddings", "weights", "table_spread", "selection_bias",
            "float32_islands", "no_drafting_layers", "no_towers",
            "per_layer_lists", "tokenizer",
            "deployment_sizes"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    # the count that holds the reading of the layers up
    assert "308.8 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "15.4 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "kv > q - 128" in CONFIG["assumed"]["window"]
    assert "read by nothing" in CONFIG["assumed"]["window"]
    assert "once in 152,576" in CONFIG["assumed"]["no_drafting_layers"]
    assert any("not the driver's rough 16" in d
               for d in CONFIG["departures"])
    assert any("drafting layers and the two towers" in d
               for d in CONFIG["departures"])
    assert "seven pipeline stages" in CONFIG["stands_for"]
    assert "32 v5e chips" in CONFIG["stands_for"]
    bench = spec.benchmark()
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG["name"]]
    assert entry["reduced"] == REDUCED and entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/chip/configs/" + CONFIG["name"] \
        + ".json"
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
    # the issue: between 25% and 96% of the chip; the guide's three quarters
    assert 0.75 * chip <= held <= 0.96 * chip
    assert memory["kv_bytes_per_token"] == 2 * 4 * (192 + 128) * 2 == 5120
    assert memory["state_bytes_per_slot"] == 5 * 8 * 128 * (192 + 128) * 2 \
        == 3_276_800
    assert family.kv_bytes_per_token(CONFIG["model"]) == 5120
    assert family.state_bytes_per_slot(CONFIG["model"]) == 3_276_800
    # keys of 192 lanes held a position a row would be tiled to 256: 6,144 B
    assert 2 * 4 * (256 + 128) * 2 == 6144
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 10 >= TRAFFIC["documents"] + 2
    assert d["kv_blocks"] == snapshots * (d["max_seq_len"]
                                          // d["kv_block_size"])
    assert memory["prefix_pool_bytes"] == (
        d["kv_blocks"] * d["kv_block_size"] * 5120
        + snapshots * 3_276_800) == 1_291_059_200
    rows = d["max_batch"] * d["max_seq_len"] * 5120
    rings = d["max_batch"] * 3_276_800
    assert (rows, rings) == (8_053_063_680, 209_715_200)
    assert memory["cache_bytes"] == rows + rings       # no leaf is padded
    weights = memory["arguments_bytes"] - rows - rings
    # bf16 but the routers and the small float32 leaves: 4.46 GB
    assert weights == pytest.approx(2 * 2_221_995_840 + 2 * 6_354_752,
                                    rel=1e-4)
    # neither program holds a copy of a leaf (the keys are 4.8 GB): the
    # chunk program's temporaries are a twentieth of the rows at most
    assert chunk - memory["arguments_bytes"] < rows // 16
    assert memory["decode_step_bytes"] - memory["arguments_bytes"] \
        < rings // 10


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert) == (4096, 16384, 2048)
    assert (cfg.n_head, cfg.n_kv_head, cfg.swa_n_kv_head, cfg.head_dim,
            cfg.v_head_dim, cfg.rotary_dim) == (64, 4, 8, 192, 128, 64)
    assert cfg.layer_types == (G, S, S, S, S, G, S)
    assert cfg.n_dense_layer == 1
    assert (cfg.sliding_window, cfg.rope_theta, cfg.swa_rope_theta,
            cfg.value_scale) == (128, 1e7, 1e4, 0.707)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert) == (256, 8, 8, 0)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 1.0)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        7, 19072, 24576, 1e-5)
    assert family.CharTokenizer.eos_id == 19071 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 19070, 7])) == [1, 19070, 7]


def test_what_the_file_states_of_the_cache_is_what_the_program_holds():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import serving_family

    stated, d = CONFIG["stated"], CONFIG["deployment"]
    _, module, _ = serving_family(d["preset"])
    cache = jax.eval_shape(lambda: module.init_cache(
        family.program_config(CONFIG), d["max_batch"], d["max_seq_len"]))
    assert set(cache) == set(stated["rows_leaves"]) | set(
        stated["ring_leaves"]) | {"counts"}
    for leaf, shape in stated["rows_leaves"].items():
        assert list(cache[leaf].shape) == shape
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        assert module.CACHE_TOKEN_AXIS[leaf] == stated[
            "rows_leaf_axes"][leaf].index("positions")
    for leaf, shape in stated["ring_leaves"].items():
        assert list(cache[leaf].shape) == shape
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        (at,) = [i for i, axis in enumerate(stated["ring_leaf_axes"][leaf])
                 if "p mod 128" in axis]
        assert shape[at] == CONFIG["model"]["sliding_window"]
    # four leaves of four shapes, and a key's 192 lanes never the last axis
    assert len({tuple(cache[n].shape) for n in ("k", "v", "wk", "wv")}) == 4
    assert cache["k"].shape[3] == cache["wk"].shape[3] == 192
    assert module.CACHE_STATE == tuple(stated["ring_leaves"])
    assert family.reference_model(CONFIG)["rows"] == stated["rows"]
    assert (stated["stream"], stated["projections"], stated["rotation"],
            stated["sink"], stated["pieces"], stated["router"],
            stated["logits"]) == ("float32", "float32", "float32", "float32",
                                  2, "float32", "float32")
    assert "tiled to 256 lanes" in stated["layout"]


# ------------------------------------------------- what the cell reads

def test_the_cell_reads_what_it_reads():
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and OWN <= names and ENGINE <= names
    assert names.isdisjoint({"kda_update_time_pct", "ssm_update_time_pct",
                             "mla_attend_time_pct", "moe_latent_time_pct",
                             "moe_zero_time_pct", "moe_zero_pairs_pct",
                             "dsa_attend_time_pct", "moe_shared_time_pct"})
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in OWN:
        assert by_name[name]["workloads"][-1] == CELL, name
        assert spec.metric_reader(name) is not None
    # every list K-EXAONE's cell is on but the shared expert's, and no other
    kexaones = {m["name"] for m in bench["per_layer"]
                if KEXAONE in m.get("workloads", [])}
    assert kexaones - names == {"moe_shared_time_pct"}
    assert names - kexaones == {m["name"] for m in bench["per_layer"]
                                if "workloads" not in m}
    # the issue asks for no new entry: the file was full and stays so
    assert len(bench["per_layer"]) == 128
    assert bench["per_layer"][-1]["name"] == "swa_attend_time_pct"
    (mine,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert bench["workloads"][-1] == mine
    assert mine["traffic"] == "mixed-length-hot-documents"
    assert bench["configs"][-1]["name"] == CONFIG["name"] == mine["config"]
    assert "2 rows a step" in mine["why"] and "1/32" in mine["why"]
    assert len(mine["why"]) <= 200
    assert len(bench["workloads"]) == len(bench["configs"]) + 1 == 15
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024


def test_what_the_pinned_tests_held_of_the_lists_a_fifteenth_cell_joins():
    """`tests/conftest.py` `_PINNED` marks the tests under the benchmark's
    `paths` that hold a list to the cells there were (K-EXAONE's, since this
    PR). What they held, of the file as it is: every reading a serving cell
    reports lists every cell that was on it, in the order they joined, with
    this cell appended and nothing else moved; counts read from the file."""
    bench = spec.benchmark()
    serving = [w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve-")]
    assert serving[-4:] == ["serve-nemotron-reasoning", LONGCAT, KEXAONE,
                            CELL]
    decode_cells = [w for w in serving if w != "serve-xl-chat"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in DECODE | {"engine_offcpu_ms.decode",
                          "engine_release_ms.decode", "engine_put_ms.decode",
                          "engine_dispatch_ms.decode",
                          "engine_admit_ms.decode",
                          "engine_slow_pass_pct.decode",
                          "idle_in_admit_pct.decode",
                          "idle_in_dispatch_pct.decode"}:
        assert by_name[name]["workloads"] == decode_cells, name
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"] == decode_cells and tokens["bound"] == 0.06
    # the rooflines and counters the cells joined keep who was on them
    assert by_name["mla_attend_roofline_pct"]["workloads"] == [
        "serve-kanana-docqa", "serve-kimi-longgen", LONGCAT]
    assert by_name["moe_held_rows_pct"]["workloads"] == [
        "serve-kimi-longgen", "serve-solar-longctx",
        "serve-nemotron-reasoning", LONGCAT, KEXAONE, CELL]
    assert by_name["moe_experts_decode_roofline_pct"]["workloads"][-4:] == [
        "serve-nemotron-reasoning", LONGCAT, KEXAONE, CELL]
    assert by_name["moe_latent_time_pct"]["workloads"] == [
        "serve-nemotron-reasoning"]
    assert by_name["gqa_rows_read_pct"]["workloads"] == [
        "serve-solar-longctx", "serve-nemotron-reasoning", KEXAONE, CELL]
    assert by_name["gqa_attend_roofline_pct"]["workloads"][-3:] == [
        "serve-nemotron-reasoning", KEXAONE, CELL]
    assert by_name["mlp_dense_time_pct"]["workloads"] == [LONGCAT, KEXAONE,
                                                          CELL]
    assert by_name["swa_attend_time_pct"] == {
        "name": "swa_attend_time_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "engine programs",
        "moves": "serve_tokens_per_s", "workloads": [KEXAONE, CELL]}
    assert by_name["moe_shared_time_pct"]["workloads"][-1] == KEXAONE
    assert by_name["rows_without_snapshot_tokens"]["workloads"][-2:] == [
        KEXAONE, CELL]
    # every entry is where PR 59 left it
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index("moe_latent_time_pct") == 123
    assert order[124:] == ["moe_zero_pairs_pct", "moe_zero_time_pct",
                           "mlp_dense_time_pct", "swa_attend_time_pct"]
    assert len(set(order)) == len(order)


def test_kexaones_cell_reads_what_it_read_with_its_entries_found_by_name():
    """What `test_exaone_family.py::test_the_cell_reads_what_it_reads` held,
    which held the cells to fourteen, K-EXAONE's to the last and its one
    entry's list to itself: the entries found by name, the counts read from
    the file."""
    exaone = importlib.import_module("test_exaone_family")
    bench = spec.benchmark()
    cell = spec.cell(bench, KEXAONE)
    assert cell["chips"] == 1 and cell["traffic"] == exaone.TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and exaone.OWN <= names and exaone.ENGINE <= names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in exaone.OWN:
        assert KEXAONE in by_name[name]["workloads"]
        assert spec.metric_reader(name) is not None
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(KEXAONE) == 13 and cells.index(CELL) == 14
    assert bench["workloads"][13]["traffic"] == "hot-documents-long-answers"
    assert bench["configs"][12]["name"] == exaone.CONFIG["name"]
    assert "4 rows a held expert" in bench["workloads"][13]["why"]


@pytest.mark.parametrize("name", ["kanana", "brumby", "granite", "kimi",
                                  "keye"])
def test_every_familys_cell_still_reads_what_it_reads_beside_a_later_cell(
        name):
    """What `test_exaone_family.py` held (since this PR marked in
    `_PINNED`) of the copy of the file with a further cell's four entries
    appended, which a full file takes past the 128 entries a file may hold:
    the same copy without the entries that list only cells later than
    Solar's (Nemotron's one, LongCat's two, and the rings', which lists
    K-EXAONE's cell and this one), so no earlier cell reads them; every
    family's cell held to what it reads."""
    tenth = importlib.import_module("test_a_tenth_cell")
    bench = copy.deepcopy(spec.benchmark())
    later = {"serve-nemotron-reasoning", LONGCAT, KEXAONE, CELL}
    alone = [m["name"] for m in bench["per_layer"]
             if "workloads" in m and set(m["workloads"]) < later
             and len(m["workloads"]) <= 2 and LONGCAT not in m[
                 "workloads"][1:]]
    assert alone == ["moe_latent_time_pct", "moe_zero_pairs_pct",
                     "moe_zero_time_pct", "swa_attend_time_pct"]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in alone]
    one_more = tenth.with_a_tenth_cell(bench)
    assert len(one_more["per_layer"]) \
        == len(spec.benchmark()["per_layer"]) - 4 + 4 <= 128
    assert len(one_more["workloads"]) == len(
        spec.benchmark()["workloads"]) + 1
    importlib.import_module(
        f"test_{name}_family").the_cell_reads_what_it_reads(one_more)
    # and from the file itself
    importlib.import_module(
        f"test_{name}_family").the_cell_reads_what_it_reads(spec.benchmark())


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 80,
        "requests_per_client": 8, "documents": 8,
        "document_uniform": [1024, 23296], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [512, 1024],
        "schedule_seed": 62, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    # a question is under a block, so the warm-up pools all eight documents
    assert TRAFFIC["question_uniform"][1] < d["kv_block_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) == d["max_seq_len"] - 192


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_documents_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 80 * 8 and plan["clients"] == 80
    documents = {}
    for r in requests:
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 19072
        documents.setdefault(r["document"], []).append(r["prompt_ids"])
    assert sorted(documents) == list(range(8))
    assert {len(v) for v in documents.values()} == {80}    # equally often
    heads = {}
    for d, prompts in documents.items():
        blocks = (min(len(p) for p in prompts) - 16) // 128
        while len({tuple(p[:blocks * 128]) for p in prompts}) > 1:
            blocks -= 1
        heads[d] = prompts[0][:blocks * 128]
        assert all(16 <= len(p) - blocks * 128 <= 64 for p in prompts)
    # short and long in one queue: the schedule's eight documents, whole
    # blocks of 128 from 3.7k to 23.0k, 771 blocks that the pool's 1,920
    # hold with the ten snapshots' count to spare
    lengths = [len(heads[d]) for d in range(8)]
    assert lengths == [3712, 23040, 6016, 10112, 15488, 14464, 18816, 7040]
    assert sum(lengths) // 128 == 771 <= CONFIG["deployment"]["kv_blocks"]
    assert min(lengths) < 4096 and max(lengths) > 20480
    # a step's lanes stand at 52% of the rows their slots hold, about
    mean = sum(lengths) / 8 + 40 + 768 / 2
    assert 0.48 < mean / CONFIG["deployment"]["max_seq_len"] < 0.56
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
    costs = family.roofline_costs(m)
    # a position a global layer: 4 heads x (192 + 128) lanes, bf16, and for
    # each of 64 query heads a multiply-add a lane of the score and of the
    # weighted value; a ring's row 8 heads
    row = costs["gqa_attend_per_position"]
    assert row == {"bytes": 2560.0, "flops": 64 * 320 * 2.0}
    ring = costs["swa_attend_per_row"]
    assert ring == {"bytes": 5120.0, "flops": 64 * 320 * 2.0}
    assert family.swa_attend_cost(m, 128.0) == {
        "bytes": 128 * 5120.0, "flops": 128 * 40960.0}
    expert = costs["moe_experts_per_touched_expert"]
    assert expert == {"bytes": 3 * 4096 * 2048 * 2, "flops": 0.0}  # 50.3 MB
    assert costs["moe_experts_per_row"] == {
        "bytes": 2 * 4096 * 2, "flops": 6 * 4096 * 2048}
    assert costs == {
        "gqa_layers": 2, "gqa_attend_per_position": row, "swa_layers": 5,
        "swa_attend_per_row": ring, "routed_experts": 8,
        "moe_experts_per_row": costs["moe_experts_per_row"],
        "moe_experts_per_touched_expert": expert}
    assert 2 * row["bytes"] == family.kv_bytes_per_token(m) == 5120
    peaks = spec.peaks()["TPU v5 lite"]
    # the rows are bound by their bytes (16 operations a byte under the
    # chip's ridge of 240), and so is an expert at two rows
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    step = family.moe_experts_decode_cost(family.experts_cost_model(m),
                                          16.0, 7.0)
    assert _moe_scopes.bound_seconds(step, peaks)[0] == "bytes"
    # the issue's reckoning: the global rows of 64 slots at 12.6k positions
    # 4.1 GB a step; the rings 0.21 GB; 6 x 6.9 touched experts 2.1 GB
    assert round(64 * 12600 * family.kv_bytes_per_token(m) / 1e9, 1) == 4.1
    assert round(64 * family.state_bytes_per_slot(m) / 1e9, 2) == 0.21
    assert round(6 * 6.9 * expert["bytes"] / 1e9, 1) == 2.1


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, sliding: bool, dense: bool = False) -> dict:
    """One layer's weights at the tiny size, float32, as the program lays
    them out, every norm's scale its own and a sink a head."""
    rng = np.random.default_rng(seed)
    d, H, hd, vd = 64, 8, 24, 16
    Gk = 4 if sliding else 2

    def normal(*shape, std=0.2):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": rng.uniform(0.5, 1.5, (n,)).astype(np.float32)}

    attn = {"norm": scale(d), "wqkv": normal(d, H * hd + Gk * (hd + vd)),
            "wo": normal(H * vd, d)}
    if sliding:
        attn["sink"] = normal(H, std=1.0) + 1.0
    out = {"attn_s" if sliding else "attn_g": attn}
    if dense:
        return {**out, "dense": {"norm": scale(d), "w_in": normal(d, 256),
                                 "w_out": normal(128, d)}}
    return {**out,
            "moe": {"norm": scale(d), "router": normal(d, 8, std=0.5),
                    "bias": normal(8, std=0.05)},
            "experts": {"wg": normal(4, d, 32), "wu": normal(4, d, 32),
                        "wd": normal(4, 32, d)}}


def attention_a_token_at_a_time(u, p, sliding, window, sink=True):
    """The sublayer's attention of the normed u [T, d] in numpy float64, a
    query at a time against the keys and values it sees, the rotation by
    complex numbers: lane j of the first 8 turns with lane j + 4, the other
    16 pass; a sliding layer 4 key-value heads, theta 1e4 and its sink, a
    global one 2 and theta 1e7."""
    p = {k: np.asarray(v["scale"] if isinstance(v, dict) else v, np.float64)
         for k, v in p.items()}
    u = np.asarray(u, np.float64)
    T, Gk = u.shape[0], 4 if sliding else 2
    theta = 1e4 if sliding else 1e7

    def turn(x, t):                                     # x [.., 24]
        angle = t / theta ** (np.arange(4) / 4)
        z = (x[..., :4] + 1j * x[..., 4:8]) * np.exp(1j * angle)
        return np.concatenate([z.real, z.imag, x[..., 8:]], -1)

    qkv = u @ p["wqkv"]
    q = qkv[:, :8 * 24].reshape(T, 8, 24)
    k = qkv[:, 8 * 24:(8 + Gk) * 24].reshape(T, Gk, 24)
    v = qkv[:, (8 + Gk) * 24:].reshape(T, Gk, 16) * 0.707
    out = np.zeros((T, 8 * 16))
    for t in range(T):
        first = 0 if window is None else max(0, t - window + 1)
        keys = np.stack([turn(k[s], s) for s in range(first, t + 1)])
        q_t = turn(q[t], t)
        for h in range(8):
            g = h // (8 // Gk)
            scores = keys[:, g] @ q_t[h] / math.sqrt(24)
            w = np.exp(scores)
            total = w.sum() + (np.exp(p["sink"][h]) if sliding and sink
                               else 0.0)
            out[t, h * 16:(h + 1) * 16] = (w / total) @ v[first:t + 1, g]
    return out @ p["wo"]


@pytest.mark.parametrize("sliding", [True, False], ids=["sliding", "global"])
def test_attention_agrees_with_a_token_at_a_time(sliding):
    """A sliding layer: a window of 16 that counts the token itself, 4
    key-value heads, theta 1e4, a sink a head; a global one: every earlier
    token, 2 key-value heads, theta 1e7, no sink. 40 tokens: past two
    windows."""
    p = tiny_layer(0, sliding)["attn_s" if sliding else "attn_g"]
    u = np.random.default_rng(1).standard_normal((40, 64)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._attention_row(u, p, TINY_MODEL, sliding,
                                               None))
    want = attention_a_token_at_a_time(u, p, sliding, 16 if sliding else None)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5)
    if sliding:
        others = (attention_a_token_at_a_time(u, p, True, None),
                  attention_a_token_at_a_time(u, p, True, 16, sink=False))
    else:
        others = (attention_a_token_at_a_time(u, p, False, 16),)
    for other in others:
        assert np.abs(got - other).max() > 1e-2


def test_the_expert_block_agrees_with_a_token_at_a_time():
    """8 outputs, 3 a token, experts 2..5 held: a token's block is its held
    pairs' SwiGLUs by their gates (s over the three's sum, no factor), its
    absent pairs nothing, and there is no shared expert."""
    p = tiny_layer(2, True)
    h = np.random.default_rng(3).standard_normal((30, 64)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        got, chosen = family._expert_block(h, p["moe"], p["experts"],
                                           TINY_MODEL)
    got, chosen = np.asarray(got), np.asarray(chosen)
    s = 1 / (1 + np.exp(-(h.astype(np.float64) @ p["moe"]["router"])))

    def swiglu(x, w_gate, w_up, w_down):
        a = x @ w_gate
        return (a / (1 + np.exp(-a)) * (x @ w_up)) @ w_down

    kinds = set()
    for t in range(30):
        top = np.argsort(-(s[t] + p["moe"]["bias"]))[:3]
        assert sorted(top) == sorted(chosen[t])
        total = s[t, top].sum() + 1e-20
        want = np.zeros(64)
        for e in top:
            if 2 <= e < 6:
                w = {k: v[e - 2].astype(np.float64)
                     for k, v in p["experts"].items()}
                want += s[t, e] / total * swiglu(
                    h[t].astype(np.float64), w["wg"], w["wu"], w["wd"])
                kinds.add("held")
            else:
                kinds.add("absent")
        np.testing.assert_allclose(got[t], want, rtol=2e-5, atol=2e-5)
    assert kinds == {"held", "absent"}


ONE_KIND = {"no_sink": True, "sink_weighs_value": True, "window_127": True,
            "window_129": True, "global_8_kv_heads": False}


@pytest.mark.parametrize("degrade", [d for d in family.DEGRADE if d])
def test_a_degraded_reference_is_another_function(degrade):
    """Each of the issue's degradations moves a layer's output: a sliding
    sparse layer's, or (the heads' grouping) a global one's; one that is a
    kind's own leaves the other kind as it is."""
    sliding = ONE_KIND.get(degrade, True)
    p = tiny_layer(3, sliding)
    x = np.random.default_rng(3).standard_normal((2, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL, sliding))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, sliding,
                                            degrade))
    assert np.isfinite(off).all() and np.abs(exact - off).max() > 1e-4
    if degrade in ONE_KIND:
        other = tiny_layer(4, not sliding)
        np.testing.assert_array_equal(
            np.asarray(family.reference_layer(x, other, TINY_MODEL,
                                              not sliding, degrade)),
            np.asarray(family.reference_layer(x, other, TINY_MODEL,
                                              not sliding)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, True, "float8_state")


def test_the_dense_layer_is_a_swiglu_and_the_blocks_of_tokens_add_nothing(
        monkeypatch):
    p = tiny_layer(5, False, dense=True)
    x = np.random.default_rng(5).standard_normal((1, 384, 64)).astype(
        np.float32)
    whole = np.asarray(family.reference_layer(x, p, TINY_MODEL, False))
    monkeypatch.setattr(family, "MLP_BLOCK", 256)   # a last block half padding
    blocks = np.asarray(family.reference_layer(x, p, TINY_MODEL, False))
    np.testing.assert_allclose(blocks, whole, atol=1e-5)
    a = p["attn_g"]
    import jax
    with jax.default_matmul_precision("highest"):
        mid = x[0] + np.asarray(family._attention_row(
            family._rms_norm(x[0], a["norm"]["scale"], 1e-5), a, TINY_MODEL,
            False, None))
    h = (mid / np.sqrt((mid * mid).mean(-1, keepdims=True) + 1e-5)
         * p["dense"]["norm"]["scale"]).astype(np.float64)
    gate, up = np.split(h @ p["dense"]["w_in"], 2, axis=-1)
    want = mid + (gate / (1 + np.exp(-gate)) * up) @ p["dense"]["w_out"]
    np.testing.assert_allclose(whole[0], want, atol=2e-4)


def test_the_reference_holds_its_rows_as_the_file_states_them():
    p = tiny_layer(4, True)
    x = np.random.default_rng(4).standard_normal((1, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL, True))
    stated = np.asarray(family.reference_layer(
        x, p, {**TINY_MODEL, "rows": "bfloat16"}, True))
    coarse = np.asarray(family.reference_layer(x, p, TINY_MODEL, True,
                                               "bfloat16_stream"))
    near, far = np.abs(exact - stated).max(), np.abs(exact - coarse).max()
    assert 0 < near < far


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine; and it sets
    `highest`."""
    with open(os.path.join(CHIP_DIR, "families", "mimo.py")) as f:
        source = f.read()
    tree = ast.parse(source)
    reference = {"_partly_rotated", "_attention_row", "_expert_block",
                 "reference_layer",
                 "reference_head", "Reference", "reference_model",
                 "layer_types", "rotary_lanes", "kv_heads",
                 "experts_cost_model", "_attend_cost", "gqa_attend_cost",
                 "swa_attend_cost", "kv_bytes_per_token",
                 "state_bytes_per_slot"}
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
    # the rest of the reference is K-EXAONE's file's, which is held so too
    assert "from families.exaone import _by_blocks, _piece, _rms_norm, " \
        "_swiglu" in source
    assert "models.mimo" not in source and "models import mimo" not in source
    assert source.count("with jax.default_matmul_precision(\"highest\")") == 2


def test_each_limit_refuses_alone():
    passing = {"served_not_engine_top_share": 0.0,
               "engine_logit_mean_abs": 1e-5, "engine_logit_floor_abs": 1e-6}
    assert family.verdict(passing)["ok"] is True
    assert set(family.LIMITS) == set(passing)
    for name, limit in family.LIMITS.items():
        assert family.verdict({**passing, name: 2 * limit})["ok"] is False
    assert family.verdict({"error": "nothing served"})["ok"] is False
    limits = CONFIG["limits"]
    for name, limit in family.LIMITS.items():
        assert limits[name]["limit"] == limit
    # the floor holds the precision: above every reading of the program
    # with room, and under the nearest precisions below what the file
    # states; every degradation the issue names is refused by one limit at
    # least, in every reading
    floor, mean = (limits["engine_logit_floor_abs"],
                   limits["engine_logit_mean_abs"])
    assert max(floor["program"] + floor["cell"]) * 2 <= floor["limit"]
    assert max(mean["program"] + mean["cell"]) * 2 <= mean["limit"]
    assert set(floor["degraded"]) == set(mean["degraded"]) == {
        d for d in family.DEGRADE if d}
    for degrade in floor["degraded"]:
        readings = list(zip(floor["degraded"][degrade],
                            mean["degraded"][degrade]))
        assert readings, degrade
        assert all(f > floor["limit"] or m > mean["limit"]
                   for f, m in readings), degrade
    for degrade in ("bfloat16_stream", "one_piece"):
        assert floor["limit"] * 1.5 <= min(floor["degraded"][degrade])


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_mimo.py`: the generator, the warm-up, the pool
    hits of rows and rings, the engine's counters (`rows_live_pct` among
    them) and `check_served`, through the harness's own phases and
    readers."""
    workdir = os.path.join(REPO, ".bench_runs")
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_mimo.py"),
         "--workload", CELL, "--seconds", "10", "--seed", "2620000123"],
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
    # two global layers x 2 heads x (24 + 16) lanes, bf16; three rings of
    # 16 positions x 4 heads
    assert other["kv_bytes_per_token"]["value"] == 2 * 2 * 40 * 2
    assert other["state_bytes_per_slot"]["value"] == 3 * 4 * 16 * 40 * 2
    assert other["rows_without_snapshot_tokens"]["value"] == 0
    # 4 of 256 outputs held, under the seed's skew
    assert 0.05 < other["moe_held_rows_pct"]["value"] < 15
    assert "moe_shared_time_pct" not in other
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr
    del workdir


def test_the_engine_reports_the_live_share_of_the_rows_its_slots_hold():
    """`rows_live_pct` is in `engine_stats()` (and so in a run's
    `measure.json` counters, which are the replica's `stats()`), for a
    family with rows a token, and no metric file reads it yet."""
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        source = f.read()
    assert '"rows_live_pct"' in source
    assert spec.metric_reader("rows_live_pct") is None
    assert "rows_live_pct" not in {m["name"]
                                   for m in spec.benchmark()["per_layer"]}


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("mimo")
    try:
        with pytest.raises(ValueError, match="no serving family has the "
                                             "preset 'mimo-v2.5'"):
            family.program_config(CONFIG)
    finally:
        models._SERVING.update(saved)
