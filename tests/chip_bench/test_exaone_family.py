"""The K-EXAONE family file on the CPU: its configuration against the
catalog's row, its `memory` against the arithmetic, its reference against a
second formulation (attention a token at a time in numpy float64 under a
window or none, rotated or not; the expert block a token at a time), its
arithmetic against hand counts, the traffic file, what the cell reads (and
what the tests a fourteenth cell breaks held of the file), the reader of the
one new entry on hand-made records, and the cell end to end at a tiny
size."""

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

from families import exaone as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402


CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "k-exaone-236b-a23b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "hot-documents-long-answers.json"))
CELL = "serve-kexaone-hotdocs"
LONGCAT = "serve-longcat-assistant"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size",
           "num_nextn_predict_layers"]
NEW = "swa_attend_time_pct"
L, G = family.SLIDING, family.GLOBAL
# the lists the issue names beside every `.decode` reading and `engine_*`
OWN = {"setup_engine_build_s", "gqa_attend_time_pct",
       "gqa_attend_roofline_pct", "gqa_rows_read_pct", "kv_bytes_per_token",
       "state_bytes_per_slot", "moe_router_time_pct.decode",
       "moe_dispatch_time_pct.decode", "moe_experts_time_pct.decode",
       "moe_shared_time_pct", "mlp_dense_time_pct",
       "moe_experts_touched_per_layer", "moe_decode_load_max_over_mean",
       "moe_held_rows_pct", "moe_experts_decode_roofline_pct",
       "rows_without_snapshot_tokens", NEW}
ENGINE = {"engine_attn_time_pct", "engine_mlp_time_pct",
          "engine_head_time_pct", "engine_prefix_pool_time_pct",
          "engine_offcpu_ms.decode", "engine_release_ms.decode",
          "engine_put_ms.decode", "engine_dispatch_ms.decode",
          "engine_admit_ms.decode", "engine_slow_pass_pct.decode",
          "idle_in_admit_pct.decode", "idle_in_dispatch_pct.decode"}
# the reference's model at the tiny size: one LLLG period and a half, a
# window of 16, 8 router outputs of which experts 2..5 are held
TINY_MODEL = {**CONFIG["model"], "num_hidden_layers": 4, "hidden_size": 64,
              "intermediate_size": 128, "moe_intermediate_size": 32,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "sliding_window": 16, "num_experts": 4,
              "num_experts_per_tok": 3, "router_outputs": 8,
              "first_expert": 2, "rows": "float32"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_four_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "K-EXAONE-236B-A23B"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert {k: CONFIG[k] for k in REDUCED} == {
        k: CONFIG["model"][k] for k in REDUCED}
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED} == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "num_nextn_predict_layers": 1}
    m = CONFIG["model"]
    assert (m["num_hidden_layers"], m["num_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"]) == (8, 8, 19200, 0)
    # every published width unchanged
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"]) == (6144, 18432, 2048)
    assert (m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"]) == (64, 8, 128)
    assert (m["num_experts_per_tok"], m["num_shared_experts"],
            m["scoring_func"], m["norm_topk_prob"],
            m["routed_scaling_factor"]) == (8, 1, "sigmoid", True, 2.5)
    assert (m["sliding_window"], m["sliding_window_pattern"],
            m["rope_parameters"], m["rms_norm_eps"]) == (
        128, "LLLG", {"rope_theta": 1000000, "rope_type": "default"}, 1e-5)
    # the lists a layer stay whole, 48 entries; the layers that are run are
    # their first 8: two whole periods, layer 0 the dense one
    assert len(m["layer_types"]) == len(m["sliding_windows"]) == len(
        m["mlp_layer_types"]) == 48
    assert family.layer_types(m) == [L, L, L, G] * 2
    assert m["mlp_layer_types"][:8] == ["dense"] + ["sparse"] * 7
    assert m["sliding_windows"][:8] == [128, 128, 128, 0] * 2
    # the guide's floors: whole periods and four layers after the dense
    # one, 8 experts or more, an eighth of the vocabulary
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] >= 4
    assert m["num_hidden_layers"] % len(m["sliding_window_pattern"]) == 0
    assert m["num_experts"] >= 8 and m["vocab_size"] * 8 == 153600
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "pipeline_stages", "router_outputs",
        "first_expert", "vocabulary_shares", "first_vocab_row")} == {
        "chips_sharing_a_layer": 16, "pipeline_stages": 6,
        "router_outputs": 128, "first_expert": 0, "vocabulary_shares": 8,
        "first_vocab_row": 0}
    assert m["num_experts"] * 16 == share["router_outputs"]
    assert share["pipeline_stages"] * m["num_hidden_layers"] == 48
    assert "4 rows a held expert" in share["experts_load"]
    assert "a sixteenth" in share["experts_load"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "exaone")
    assert CONFIG["deployment"] == {
        "preset": "kexaone-236b-a23b", "max_seq_len": 10240,
        "max_batch": 64, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 640, "kv_block_size": 128}
    # ten of `slot_rows`' blocks; the chunk and the pool's block the window
    assert CONFIG["deployment"]["max_seq_len"] == 10 * 1024
    assert CONFIG["deployment"]["prefill_chunk_size"] == CONFIG["deployment"][
        "kv_block_size"] == m["sliding_window"]
    assert {"pre_norm", "head_norms", "rotation", "window", "rope", "gates",
            "shared_expert", "hidden_act", "untied_embeddings", "weights",
            "table_spread", "selection_bias", "float32_islands",
            "no_drafting_module", "per_layer_lists", "tokenizer",
            "deployment_sizes"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    # the count that holds the reading of the layers up
    assert "236.6 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "23.7 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "kv > q - 128" in CONFIG["assumed"]["window"]
    assert "once in 153,600" in CONFIG["assumed"]["no_drafting_module"]
    assert any("head's eighth held on stage 0" in d
               for d in CONFIG["departures"])
    assert any("vocabulary over 8 where the experts are over 16" in d
               for d in CONFIG["departures"])
    assert any("drafting module" in d for d in CONFIG["departures"])
    assert "six pipeline stages" in CONFIG["stands_for"]
    assert "16 chips" in CONFIG["stands_for"]
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
    # the issue: the fullest device holds at least 75% of the chip
    assert 0.75 * chip <= held <= 0.95 * chip
    assert memory["kv_bytes_per_token"] == 2 * 2 * 8 * 128 * 2 == 8192
    assert memory["state_bytes_per_slot"] == 6 * 2 * 8 * 128 * 128 * 2 \
        == 3_145_728
    assert family.kv_bytes_per_token(CONFIG["model"]) == 8192
    assert family.state_bytes_per_slot(CONFIG["model"]) == 3_145_728
    # all eight layers global would hold four times the bytes a token
    assert 8 * 2 * 8 * 128 * 2 == 4 * 8192
    snapshots = d["kv_blocks"] * d["kv_block_size"] // d["max_seq_len"]
    assert snapshots == 8 >= TRAFFIC["documents"]
    assert memory["prefix_pool_bytes"] == (
        d["kv_blocks"] * d["kv_block_size"] * 8192
        + snapshots * 3_145_728) == 696_254_464
    rows = d["max_batch"] * d["max_seq_len"] * 8192
    rings = d["max_batch"] * 3_145_728
    assert (rows, rings) == (5_368_709_120, 201_326_592)
    weights = memory["arguments_bytes"] - rows - rings
    # bf16 but the routers and the small float32 leaves: 7.74 GB
    assert weights == pytest.approx(2 * 3_865_420_672, rel=2e-3)
    assert round(weights / 1e9, 2) == 7.74
    # neither program holds a copy of a leaf (the rows are 2.7 GB each):
    # the chunk program's temporaries are a quarter of one at most
    assert chunk - memory["arguments_bytes"] < rows // 8
    assert memory["decode_step_bytes"] - memory["arguments_bytes"] \
        < rings // 10


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert) == (6144, 18432, 2048)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (64, 8, 128)
    assert cfg.layer_types == (L, L, L, G) * 2 and cfg.n_dense_layer == 1
    assert (cfg.sliding_window, cfg.rope_theta) == (128, 1e6)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.experts_held,
            cfg.first_expert, cfg.n_shared_experts) == (128, 8, 8, 0, 1)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor) == ("sigmoid", True, 2.5)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        8, 19200, 10240, 1e-5)
    assert family.CharTokenizer.eos_id == 19199 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 19198, 7])) == [1, 19198, 7]


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
            "rows_leaf_axes"].index("positions")
    for leaf, shape in stated["ring_leaves"].items():
        assert list(cache[leaf].shape) == shape
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        assert shape[3] == CONFIG["model"]["sliding_window"]
    assert module.CACHE_STATE == tuple(stated["ring_leaves"])
    assert family.reference_model(CONFIG)["rows"] == stated["rows"]
    assert (stated["stream"], stated["projections"], stated["head_norms"],
            stated["rotation"], stated["pieces"], stated["router"],
            stated["logits"]) == ("float32", "float32", "float32", "float32",
                                  2, "float32", "float32")


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
                             "dsa_attend_time_pct"})
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name[NEW] == {
        "name": NEW, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "engine programs",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    assert bench["per_layer"][-1]["name"] == NEW
    # the file is full: the next reader waits for entries to be folded
    assert len(bench["per_layer"]) == 128
    (mine,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert bench["workloads"][-1] == mine
    assert mine["traffic"] == "hot-documents-long-answers"
    assert bench["configs"][-1]["name"] == CONFIG["name"] == mine["config"]
    assert "4 rows a held expert" in mine["why"] and len(mine["why"]) <= 200
    # the cell is on every list both LongCat's and Solar's cells are on
    both = {m["name"] for m in bench["per_layer"]
            if {LONGCAT, "serve-solar-longctx"} <= set(m.get("workloads", []))}
    assert both <= names
    assert len(bench["workloads"]) == len(bench["configs"]) + 1 == 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024


def test_what_the_pinned_tests_held_of_the_lists_a_fourteenth_cell_joins():
    """`tests/conftest.py` `_PINNED` marks the tests under the benchmark's
    `paths` that hold a list to the cells there were (LongCat's, since this
    PR). What they held, of the file as it is: every reading a serving cell
    reports lists every cell that was on it, in the order they joined, with
    this cell appended and nothing else moved; counts read from the file."""
    bench = spec.benchmark()
    serving = [w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve-")]
    assert serving[-3:] == ["serve-nemotron-reasoning", LONGCAT, CELL]
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
    # the rooflines and counters the two cells joined keep who was on them
    assert by_name["mla_attend_roofline_pct"]["workloads"] == [
        "serve-kanana-docqa", "serve-kimi-longgen", LONGCAT]
    assert by_name["moe_held_rows_pct"]["workloads"] == [
        "serve-kimi-longgen", "serve-solar-longctx",
        "serve-nemotron-reasoning", LONGCAT, CELL]
    assert by_name["moe_experts_decode_roofline_pct"]["workloads"][-3:] == [
        "serve-nemotron-reasoning", LONGCAT, CELL]
    assert by_name["moe_latent_time_pct"]["workloads"] == [
        "serve-nemotron-reasoning"]
    assert by_name["gqa_rows_read_pct"]["workloads"] == [
        "serve-solar-longctx", "serve-nemotron-reasoning", CELL]
    assert by_name["gqa_attend_roofline_pct"]["workloads"][-2:] == [
        "serve-nemotron-reasoning", CELL]
    assert by_name["mlp_dense_time_pct"]["workloads"] == [LONGCAT, CELL]
    assert by_name["rows_without_snapshot_tokens"]["workloads"][-1] == CELL
    # every entry but the appended one is where PR 55 left it
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index("moe_latent_time_pct") == 123
    assert order[124:] == ["moe_zero_pairs_pct", "moe_zero_time_pct",
                           "mlp_dense_time_pct", NEW]
    assert len(set(order)) == len(order)


def test_longcats_cell_reads_what_it_read_with_its_entries_found_by_name():
    """What `test_longcat_family.py::test_the_cell_reads_what_it_reads`
    held, which held the list's last three entries to LongCat's own and the
    cells to thirteen: the entries found by name, the counts read from the
    file."""
    longcat = importlib.import_module("test_longcat_family")
    bench = spec.benchmark()
    cell = spec.cell(bench, LONGCAT)
    assert cell["chips"] == 1 and cell["traffic"] == longcat.TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and longcat.OWN <= names and longcat.ENGINE <= names
    assert names.isdisjoint({"kda_update_time_pct", "ssm_update_time_pct",
                             "gqa_attend_time_pct", "moe_shared_time_pct",
                             "moe_latent_time_pct", "state_bytes_per_slot",
                             NEW})
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in longcat.OWN:
        assert LONGCAT in by_name[name]["workloads"]
        assert spec.metric_reader(name) is not None
    for name, source, better in zip(
            longcat.NEW, ("program_counter", "device_trace", "device_trace"),
            ("higher", "lower", "lower")):
        assert {k: v for k, v in by_name[name].items()
                if k != "workloads"} == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": "engine programs", "moves": "serve_tokens_per_s"}
        assert by_name[name]["workloads"][0] == LONGCAT
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(LONGCAT) == 12 and cells.index(CELL) == 13
    assert bench["workloads"][12]["traffic"] == "assistant-tool-turns"
    assert bench["configs"][11]["name"] == longcat.CONFIG["name"]
    kananas = {m["name"] for m in bench["per_layer"]
               if "serve-kanana-docqa" in m.get("workloads", [])}
    assert kananas - names == {"moe_shared_time_pct"}
    assert "2 rows a step" in bench["workloads"][12]["why"]


@pytest.mark.parametrize("name", ["kanana", "brumby", "granite", "kimi",
                                  "keye"])
def test_every_familys_cell_still_reads_what_it_reads_beside_a_later_cell(
        name):
    """What `test_a_tenth_cell.py`, `test_keye_family.py` and (since this
    PR) `test_longcat_family.py` held of the copy of the file with a further
    cell's four entries appended, which a full file takes past the 128
    entries a file may hold: the same copy without the four entries that
    list one of the three latest cells alone (Nemotron's one, LongCat's two
    that this cell did not join, and this cell's own), so no earlier cell
    reads them; every family's cell held to what it reads."""
    tenth = importlib.import_module("test_a_tenth_cell")
    bench = copy.deepcopy(spec.benchmark())
    alone = [m["name"] for m in bench["per_layer"] if m.get("workloads") in (
        ["serve-nemotron-reasoning"], [LONGCAT], [CELL])]
    assert alone == ["moe_latent_time_pct", "moe_zero_pairs_pct",
                     "moe_zero_time_pct", NEW]
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


def test_nemotrons_cell_reads_what_it_read_with_its_entry_found_by_name():
    """What `test_longcat_family.py::test_nemotrons_cell_reads_what_it_read_
    with_its_entry_found_by_name` held, which held the cells to thirteen:
    the counts read from the file."""
    nemotron = importlib.import_module("test_nemotron_family")
    bench = spec.benchmark()
    cell = spec.cell(bench, nemotron.CELL)
    assert cell["chips"] == 1 and cell["traffic"] == nemotron.TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and nemotron.OWN <= names
    assert names.isdisjoint({"mla_attend_time_pct", "kda_update_time_pct",
                             "mla_attend_roofline_pct", "moe_zero_pairs_pct",
                             "moe_zero_time_pct", "mlp_dense_time_pct", NEW})
    for m in bench["per_layer"]:
        if m["name"] in nemotron.OWN:
            assert nemotron.CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    (own,) = [m for m in bench["per_layer"]
              if m["name"] == "moe_latent_time_pct"]
    assert own == {"name": "moe_latent_time_pct", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "engine programs", "moves": "serve_tokens_per_s",
                   "workloads": [nemotron.CELL]}
    assert len(bench["per_layer"]) <= 128
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(nemotron.CELL) == 11
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(nemotron.CONFIG["name"]) == 10
    granites = {m["name"] for m in bench["per_layer"]
                if "serve-granite-docgen" in m.get("workloads", [])}
    assert granites - names == {"rows_without_snapshot_tokens"}
    assert "5.5 rows a held expert" in bench["workloads"][11]["why"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 80,
        "requests_per_client": 8, "documents": 6,
        "document_uniform": [6144, 8960], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [512, 1024],
        "schedule_seed": 59, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    # a question is under a block, so the warm-up pools all six documents
    assert TRAFFIC["question_uniform"][1] < d["kv_block_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_documents_the_questions_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 80 * 8 and plan["clients"] == 80
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 19200
        assert 6144 + 16 <= n <= 8960 + 64
        documents.setdefault(r["document"], []).append(r["prompt_ids"])
    assert sorted(documents) == list(range(6))
    # about equally often (640 is no multiple of 6), and each a whole
    # number of blocks of 128 shared by all its requests, a question of
    # 16-64 after it
    assert {len(v) for v in documents.values()} <= {106, 107}
    heads = {}
    for d, prompts in documents.items():
        shared = min(len(p) for p in prompts) - 16
        blocks = shared // 128
        while len({tuple(p[:blocks * 128]) for p in prompts}) > 1:
            blocks -= 1
        heads[d] = prompts[0][:blocks * 128]
        assert 6144 <= blocks * 128 <= 8960
        assert all(16 <= len(p) - blocks * 128 <= 64 for p in prompts)
    assert len(plan["warmup"]) == 7
    for w, d in zip(plan["warmup"], list(range(6)) + [0]):
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
    # a position a global layer: 8 heads x 128 lanes of key and of value,
    # bf16, and for each of 64 query heads a multiply-add a lane twice
    row = costs["gqa_attend_per_position"]
    assert row == {"bytes": 4096.0, "flops": 2 * 64 * 128 * 2.0}
    assert costs["swa_attend_per_row"] == row
    assert family.swa_attend_cost(m, 128.0) == {
        "bytes": 128 * 4096.0, "flops": 128 * 32768.0}
    expert = costs["moe_experts_per_touched_expert"]
    assert expert == {"bytes": 3 * 6144 * 2048 * 2, "flops": 0.0}  # 75.5 MB
    assert costs["moe_experts_per_row"] == {
        "bytes": 2 * 6144 * 2, "flops": 6 * 6144 * 2048}
    assert costs == {
        "gqa_layers": 2, "gqa_attend_per_position": row, "swa_layers": 6,
        "swa_attend_per_row": row, "routed_experts": 8,
        "moe_experts_per_row": costs["moe_experts_per_row"],
        "moe_experts_per_touched_expert": expert}
    peaks = spec.peaks()["TPU v5 lite"]
    # the rows are bound by their bytes (8 operations a byte under the
    # chip's ridge of 240), and so is an expert at four rows
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    step = family.moe_experts_decode_cost(family.experts_cost_model(m),
                                          32.0, 8.0)
    assert _moe_scopes.bound_seconds(step, peaks)[0] == "bytes"
    # the issue's reckoning: 56 touched experts a step over seven layers,
    # 4.2 GB; the global rows of 64 slots at 8.0k positions, 4.2 GB; the
    # rings 0.2 GB
    assert round(7 * 8 * expert["bytes"] / 1e9, 1) == 4.2
    assert round(64 * 8000 * family.kv_bytes_per_token(m) / 1e9, 1) == 4.2
    assert round(64 * family.state_bytes_per_slot(m) / 1e9, 1) == 0.2


# --------------------------------------------------------------- reference

def tiny_layer(seed: int, dense: bool = False) -> dict:
    """One layer's weights at the tiny size, float32, as the program lays
    them out, every norm's scale its own."""
    rng = np.random.default_rng(seed)
    d, H, Gk, hd = 64, 4, 2, 16

    def normal(*shape, std=0.2):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": rng.uniform(0.5, 1.5, (n,)).astype(np.float32)}

    out = {"attn": {"norm": scale(d), "wq": normal(d, H * hd),
                    "wk": normal(d, Gk * hd), "wv": normal(d, Gk * hd),
                    "q_norm": scale(hd), "k_norm": scale(hd),
                    "wo": normal(H * hd, d)}}
    if dense:
        return {**out, "dense": {"norm": scale(d), "w_in": normal(d, 256),
                                 "w_out": normal(128, d)}}
    return {**out,
            "moe": {"norm": scale(d), "router": normal(d, 8, std=0.5),
                    "bias": normal(8, std=0.05),
                    "shared": {"w_in": normal(d, 64),
                               "w_out": normal(32, d)}},
            "experts": {"wg": normal(4, d, 32), "wu": normal(4, d, 32),
                        "wd": normal(4, 32, d)}}


def attention_a_token_at_a_time(u, p, window, rotated):
    """The sublayer's attention of the normed u [T, d] in numpy float64, a
    query at a time against the keys and values it sees, the rotation by
    complex numbers: lane j of the 16 turns with lane j + 8."""
    p = {k: np.asarray(v["scale"] if isinstance(v, dict) else v, np.float64)
         for k, v in p.items()}
    u = np.asarray(u, np.float64)
    T = u.shape[0]

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w

    def turn(x, t):                                     # x [.., 16]
        if not rotated:
            return x
        angle = t / 1000000 ** (np.arange(8) / 8)
        z = (x[..., :8] + 1j * x[..., 8:]) * np.exp(1j * angle)
        return np.concatenate([z.real, z.imag], -1)

    q = norm((u @ p["wq"]).reshape(T, 4, 16), p["q_norm"])
    k = norm((u @ p["wk"]).reshape(T, 2, 16), p["k_norm"])
    v = (u @ p["wv"]).reshape(T, 2, 16)
    out = np.zeros((T, 4 * 16))
    for t in range(T):
        first = 0 if window is None else max(0, t - window + 1)
        keys = np.stack([turn(k[s], s) for s in range(first, t + 1)])
        q_t = turn(q[t], t)
        for h in range(4):
            scores = keys[:, h // 2] @ q_t[h] / math.sqrt(16)
            w = np.exp(scores - scores.max())
            out[t, h * 16:(h + 1) * 16] = (w / w.sum()) @ v[first:t + 1,
                                                            h // 2]
    return out @ p["wo"]


@pytest.mark.parametrize("sliding", [True, False], ids=["sliding", "global"])
def test_attention_agrees_with_a_token_at_a_time(sliding):
    """A sliding layer: a window of 16 that counts the token itself,
    rotated; a global one: every earlier token, un-rotated. 40 tokens: past
    two windows."""
    p = tiny_layer(0)["attn"]
    u = np.random.default_rng(1).standard_normal((40, 64)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._attention_row(u, p, TINY_MODEL, sliding,
                                               None))
    want = attention_a_token_at_a_time(u, p, 16 if sliding else None, sliding)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-5)
    other = attention_a_token_at_a_time(u, p, None if sliding else 16,
                                        sliding)
    assert np.abs(got - other).max() > 1e-2


def test_the_expert_block_agrees_with_a_token_at_a_time():
    """8 outputs, 3 a token, experts 2..5 held: a token's block is its held
    pairs' SwiGLUs by their gates (2.5 s over the three's sum) plus the
    shared expert, its absent pairs nothing."""
    p = tiny_layer(2)
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

    shared_in = p["moe"]["shared"]["w_in"].astype(np.float64)
    kinds = set()
    for t in range(30):
        top = np.argsort(-(s[t] + p["moe"]["bias"]))[:3]
        assert sorted(top) == sorted(chosen[t])
        total = s[t, top].sum() + 1e-20
        want = swiglu(h[t].astype(np.float64), shared_in[:, :32],
                      shared_in[:, 32:], p["moe"]["shared"]["w_out"])
        for e in top:
            if 2 <= e < 6:
                w = {k: v[e - 2].astype(np.float64)
                     for k, v in p["experts"].items()}
                want += 2.5 * s[t, e] / total * swiglu(
                    h[t].astype(np.float64), w["wg"], w["wu"], w["wd"])
                kinds.add("held")
            else:
                kinds.add("absent")
        np.testing.assert_allclose(got[t], want, rtol=2e-5, atol=2e-5)
    assert kinds == {"held", "absent"}


@pytest.mark.parametrize("degrade", [d for d in family.DEGRADE if d])
def test_a_degraded_reference_is_another_function(degrade):
    """Each of the issue's degradations moves a layer's output (a sliding
    sparse layer's; a rotated global layer is a global layer's)."""
    p = tiny_layer(3)
    x = np.random.default_rng(3).standard_normal((2, 40, 64)).astype(
        np.float32)
    sliding = degrade != "rotate_global"
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL, sliding))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, sliding,
                                            degrade))
    assert np.isfinite(off).all() and np.abs(exact - off).max() > 1e-4
    # a degradation of the other kind of layer leaves this one as it is
    if degrade in ("rotate_global", "window_127", "window_129",
                   "unrotated_sliding"):
        same = np.asarray(family.reference_layer(x, p, TINY_MODEL,
                                                 not sliding, degrade))
        np.testing.assert_array_equal(same, np.asarray(
            family.reference_layer(x, p, TINY_MODEL, not sliding)))
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, True, "float8_state")


def blocks_of(p, x, block, monkeypatch):
    """A sliding layer of `p` over x with the MLP `block` tokens at a time."""
    monkeypatch.setattr(family, "MLP_BLOCK", block)
    return np.asarray(family.reference_layer(x, p, TINY_MODEL, True))


def test_the_dense_layer_is_a_swiglu_and_the_blocks_of_tokens_add_nothing(
        monkeypatch):
    p = tiny_layer(5, dense=True)
    x = np.random.default_rng(5).standard_normal((1, 384, 64)).astype(
        np.float32)
    whole = np.asarray(family.reference_layer(x, p, TINY_MODEL, False))
    for block in (64, 256):             # 256: a last block half padding
        monkeypatch.setattr(family, "MLP_BLOCK", block)
        blocks = np.asarray(family.reference_layer(x, p, TINY_MODEL, False))
        np.testing.assert_allclose(blocks, whole, atol=1e-5)
    # and a sparse layer's, whose padding rows go through the router
    sparse = tiny_layer(6)
    np.testing.assert_allclose(blocks_of(sparse, x, 256, monkeypatch),
                               blocks_of(sparse, x, 1024, monkeypatch),
                               atol=1e-5)
    a = p["attn"]
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
    p = tiny_layer(4)
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
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "exaone.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_piece", "_attention_row", "_swiglu",
                 "_expert_block", "_by_blocks", "reference_layer",
                 "reference_head", "Reference", "reference_model",
                 "layer_types", "experts_cost_model", "swa_attend_cost",
                 "kv_bytes_per_token", "state_bytes_per_slot"}
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


# ------------------------------------------------------------- the reader

def test_the_new_entry_reads_its_number_and_nothing_where_none_is():
    """The reader on records with no trace (a parent's, an untraced run's):
    nothing, and no exception; its scope is the program's, and the global
    layers' attention is not under it."""
    reader = spec.metric_reader(NEW)
    assert reader.read({}) is None
    assert reader.read({"trace_dir": None, "counters": None}) is None
    assert reader.read({"trace_dir": "/nonexistent/trace"}) is None
    assert reader._scope_of(
        "jit(_step)/layers/while/body/attn/swa_attend/swa_attend/"
        "pallas_call") == "swa_attend"
    assert reader._scope_of(
        "jit(_step)/layers/while/body/attn/gqa_attend/gqa_attend/"
        "pallas_call") is None
    assert reader._scope_of(
        "jit(_step)/layers/while/body/attn/kv_update/rows_write") is None
    assert reader._scope_of(None) is None
    # and the rows' readers do not take the rings' time for theirs
    from metrics import _ssm_scopes

    assert _ssm_scopes.ssm_scope_of(
        "jit(_step)/layers/while/body/attn/swa_attend/dot_general") is None
    assert _ssm_scopes.ssm_scope_of(
        "jit(_step)/layers/while/body/attn/gqa_attend/dot_general") \
        == "gqa_attend"
    with open(os.path.join(REPO, "ray_tpu", "models", "exaone.py")) as f:
        source = f.read()
    assert '"swa_attend" if sliding else "gqa_attend"' in source
    assert 'jax.named_scope("mlp_dense")' in source
    assert 'jax.named_scope("moe_shared")' in source


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_exaone.py`: the generator, the warm-up, the pool
    hits of rows and rings, the engine's counters and `check_served`,
    through the harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_exaone.py"),
         "--workload", CELL, "--seconds", "10", "--seed", "2590000123"],
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
    # one global layer x 2 heads x 16 lanes x 2 leaves, bf16; three rings
    # of 16 rows
    assert other["kv_bytes_per_token"]["value"] == 2 * 16 * 2 * 2
    assert other["state_bytes_per_slot"]["value"] == 3 * 2 * 2 * 16 * 16 * 2
    assert other["rows_without_snapshot_tokens"]["value"] == 0
    # 4 of 128 outputs held, under the seed's skew
    assert 0.2 < other["moe_held_rows_pct"]["value"] < 15
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("kexaone")
    try:
        with pytest.raises(ValueError, match="no serving family has the "
                                             "preset 'kexaone-236b-a23b'"):
            family.program_config(CONFIG)
    finally:
        models._SERVING.update(saved)
