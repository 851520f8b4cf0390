"""The LongCat family file on the CPU: its configuration against the
catalog's row, its `memory` against the arithmetic, its reference against a
second formulation (latent attention a token at a time in numpy float64; the
expert block a token at a time), its arithmetic against hand counts, the
traffic file, what the cell reads (and what the tests a thirteenth cell
breaks held of the file), the readers of the three new entries on hand-made
records, and the cell end to end at a tiny size."""

import ast
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

from families import longcat as family  # noqa: E402
from generators import closed_loop_documents  # noqa: E402
from harness import spec  # noqa: E402
from metrics import _moe_scopes  # noqa: E402
from test_kanana_family import DECODE  # noqa: E402


CONFIG = spec.load_json(os.path.join(
    CHIP_DIR, "configs", "longcat-flash-chat-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "assistant-tool-turns.json"))
CELL = "serve-longcat-assistant"
REDUCED = ["num_layers", "n_routed_experts", "vocab_size"]
NEW = ("moe_zero_pairs_pct", "moe_zero_time_pct", "mlp_dense_time_pct")
# the lists the issue names beside every `.decode` reading and `engine_*`
OWN = {"setup_engine_build_s", "mla_attend_time_pct", "mla_project_time_pct",
       "mla_attend_roofline_pct", "moe_router_time_pct.decode",
       "moe_dispatch_time_pct.decode", "moe_experts_time_pct.decode",
       "moe_experts_touched_per_layer", "moe_decode_load_max_over_mean",
       "moe_held_rows_pct", "moe_experts_decode_roofline_pct",
       "kv_bytes_per_token", *NEW}
ENGINE = {"engine_attn_time_pct", "engine_mlp_time_pct",
          "engine_head_time_pct", "engine_prefix_pool_time_pct",
          "engine_offcpu_ms.decode", "engine_release_ms.decode",
          "engine_put_ms.decode", "engine_dispatch_ms.decode",
          "engine_admit_ms.decode", "engine_slow_pass_pct.decode",
          "idle_in_admit_pct.decode", "idle_in_dispatch_pct.decode"}
TINY = {"vocab_size": 512, "num_layers": 2, "hidden_size": 64,
        "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16}
# the reference's model at the tiny size: 12 router outputs of which the
# last 4 zero-compute, experts 2..5 of the 8 routed ones held
TINY_MODEL = {**CONFIG["model"], **TINY, "n_routed_experts": 4,
              "moe_topk": 3, "zero_expert_num": 4, "router_outputs": 12,
              "zero_compute_outputs": 4, "first_expert": 2,
              "rows": "float32"}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_catalogs_row_but_the_three_reduced():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "LongCat-Flash-Chat"]
    assert row["source_url"] == CONFIG["source"]
    assert CONFIG["reduced"] == REDUCED
    kept = {k: v for k, v in row["config"].items() if k not in REDUCED}
    assert {k: CONFIG["model"][k] for k in kept} == kept
    assert {k: CONFIG[k] for k in kept} == kept
    assert set(CONFIG["model"]) == set(row["config"])
    assert {k: CONFIG[k] for k in REDUCED} == {
        k: CONFIG["model"][k] for k in REDUCED}
    assert CONFIG["published"] == {k: row["config"][k] for k in REDUCED} == {
        "num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072}
    m = CONFIG["model"]
    assert (m["num_layers"], m["n_routed_experts"], m["vocab_size"]) == (
        4, 16, 16384)
    # every published width unchanged
    assert (m["hidden_size"], m["ffn_hidden_size"],
            m["expert_ffn_hidden_size"]) == (6144, 12288, 2048)
    assert (m["num_attention_heads"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"]) == (64, 128, 64, 128)
    assert (m["q_lora_rank"], m["kv_lora_rank"], m["mla_scale_q_lora"],
            m["mla_scale_kv_lora"]) == (1536, 512, True, True)
    assert (m["zero_expert_num"], m["zero_expert_type"], m["moe_topk"],
            m["routed_scaling_factor"]) == (256, "identity", 12, 6)
    assert (m["rope_theta"], m["rms_norm_eps"]) == (10000000, 1e-5)
    # the guide's floors: four layers, 8 experts or more, an eighth of the
    # vocabulary
    assert m["num_layers"] >= 4 and m["n_routed_experts"] >= 8
    assert m["vocab_size"] * 8 == 131072
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "pipeline_stages", "router_outputs",
        "zero_compute_outputs", "first_expert", "vocabulary_shares",
        "first_vocab_row")} == {
        "chips_sharing_a_layer": 32, "pipeline_stages": 7,
        "router_outputs": 768, "zero_compute_outputs": 256,
        "first_expert": 0, "vocabulary_shares": 8, "first_vocab_row": 0}
    assert m["n_routed_experts"] * 32 + 256 == share["router_outputs"]
    assert share["pipeline_stages"] * m["num_layers"] == 28
    assert "2 rows a held expert" in share["experts_load"]
    assert "an eighth" in share["experts_load"]
    assert "counted (zero_rows) and not felt" in share["zero_compute"]
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "longcat")
    assert CONFIG["deployment"] == {
        "preset": "longcat-flash-chat", "max_seq_len": 3072,
        "max_batch": 128, "scheduler": "continuous",
        "enable_prefix_caching": True, "prefill_chunk_size": 128,
        "kv_blocks": 160, "kv_block_size": 128}
    assert {"hidden_act", "norm_topk_prob", "latent_scales",
            "untied_embeddings", "rope", "weights", "table_spread",
            "selection_bias", "float32_islands", "no_drafting_module",
            "tokenizer", "deployment_sizes"} <= set(CONFIG["assumed"])
    assert all(isinstance(v, str) and len(v) > 40
               for v in CONFIG["assumed"].values())
    # the count that bears the reading of a layer as a double layer
    assert "560.7 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "27.1 B" in CONFIG["assumed"]["untied_embeddings"]
    assert "3.4641" in CONFIG["assumed"]["latent_scales"]
    assert any("head's eighth held on stage 0" in d
               for d in CONFIG["departures"])
    assert any("vocabulary over 8 where the experts are over 32" in d
               for d in CONFIG["departures"])
    assert any("no drafting module" in d for d in CONFIG["departures"])
    assert "seven pipeline stages" in CONFIG["stands_for"]
    assert "32 chips" in CONFIG["stands_for"]
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
    # the issue: the fullest device holds at least 75% of the chip
    assert 0.75 * chip <= held <= 0.95 * chip
    assert memory["kv_bytes_per_token"] == 8 * (512 + 64) * 2 == 9216
    assert family.kv_bytes_per_token(CONFIG["model"]) == 9216
    assert memory["prefix_pool_bytes"] == (
        d["kv_blocks"] * d["kv_block_size"] * 9216) == 188_743_680
    rows = d["max_batch"] * d["max_seq_len"] * 9216
    assert rows == 3_623_878_656
    weights = memory["arguments_bytes"] - rows
    # bf16 but the routers and the small float32 leaves: 10.38 GB
    assert weights == pytest.approx(2 * 5_172_728_832, rel=5e-3)
    # neither program holds a copy of a leaf (the rows are 3.62 GB): the
    # chunk program's temporaries are a third of them at most
    assert chunk - memory["arguments_bytes"] < rows // 3
    assert memory["decode_step_bytes"] - memory["arguments_bytes"] \
        < rows // 10


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert) == (6144, 12288, 2048)
    assert (cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        64, 128, 64, 128, 1536, 512)
    assert (cfg.n_experts, cfg.zero_experts, cfg.router_outputs,
            cfg.experts_per_token, cfg.experts_held, cfg.first_expert) == (
        512, 256, 768, 12, 16, 0)
    assert (cfg.router_scoring, cfg.norm_topk_prob,
            cfg.routed_scaling_factor, cfg.zero_expert_type) == (
        "softmax", False, 6.0, "identity")
    assert (cfg.mla_scale_q_lora, cfg.mla_scale_kv_lora, cfg.rope_theta) == (
        True, True, 1e7)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len, cfg.norm_eps) == (
        4, 16384, 3072, 1e-5)
    assert family.CharTokenizer.eos_id == 16383 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([1, 16382, 7])) == [1, 16382, 7]


def test_what_the_file_states_of_the_cache_is_what_the_program_holds():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import serving_family

    stated, d = CONFIG["stated"], CONFIG["deployment"]
    _, module, _ = serving_family(d["preset"])
    cache = jax.eval_shape(lambda: module.init_cache(
        family.program_config(CONFIG), d["max_batch"], d["max_seq_len"]))
    assert set(cache) == set(stated["rows_leaves"]) | {"counts"}
    for leaf, shape in stated["rows_leaves"].items():
        assert list(cache[leaf].shape) == shape
        assert cache[leaf].dtype == jnp.dtype(stated["rows"])
        assert module.CACHE_TOKEN_AXIS[leaf] == stated[
            "rows_leaf_axes"].index("positions")
    assert not hasattr(module, "CACHE_STATE")
    assert family.reference_model(CONFIG)["rows"] == stated["rows"]
    assert (stated["stream"], stated["projections"], stated["pieces"],
            stated["router"], stated["logits"]) == (
        "float32", "float32", 2, "float32", "float32")


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
                             "gqa_attend_time_pct", "moe_shared_time_pct",
                             "moe_latent_time_pct", "state_bytes_per_slot"})
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, source, better in zip(
            NEW, ("program_counter", "device_trace", "device_trace"),
            ("higher", "lower", "lower")):
        assert by_name[name] == {
            "name": name, "unit": "%", "better": better, "source": source,
            "layer": "engine programs", "moves": "serve_tokens_per_s",
            "workloads": [CELL]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    assert len(bench["per_layer"]) == 127 <= 128
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["traffic"] == "assistant-tool-turns"
    assert bench["configs"][-1]["name"] == CONFIG["name"]
    # the cell is on every list Kanana's is on but the shared experts'
    kananas = {m["name"] for m in bench["per_layer"]
               if "serve-kanana-docqa" in m.get("workloads", [])}
    assert kananas - names == {"moe_shared_time_pct"}
    assert "2 rows a step" in bench["workloads"][-1]["why"]
    assert len(bench["workloads"]) == 13 and len(bench["configs"]) == 12
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024


def test_what_the_pinned_tests_held_of_the_lists_a_thirteenth_cell_joins():
    """`tests/conftest.py` `_PINNED` marks the tests under the benchmark's
    `paths` that hold a list to the cells there were. What they held, of
    the file as it is: every reading a serving cell reports lists every
    cell that was on it, in the order they joined, with this cell appended
    and nothing else moved; counts read from the file."""
    bench = spec.benchmark()
    serving = [w["name"] for w in bench["workloads"]
               if w["name"].startswith("serve-")]
    assert serving[-2:] == ["serve-nemotron-reasoning", CELL]
    decode_cells = [w for w in serving if w != "serve-xl-chat"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in DECODE | {"engine_offcpu_ms.decode",
                          "engine_release_ms.decode", "engine_put_ms.decode",
                          "engine_dispatch_ms.decode",
                          "engine_admit_ms.decode",
                          "engine_slow_pass_pct.decode",
                          "idle_in_admit_pct.decode",
                          "idle_in_dispatch_pct.decode"}:
        listed = by_name[name]["workloads"]
        assert listed == decode_cells, name
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "serve_tokens_per_s"]
    assert tokens["workloads"] == decode_cells and tokens["bound"] == 0.06
    # the rooflines and counters this cell joins keep who was on them
    assert by_name["mla_attend_roofline_pct"]["workloads"] == [
        "serve-kanana-docqa", "serve-kimi-longgen", CELL]
    assert by_name["moe_held_rows_pct"]["workloads"] == [
        "serve-kimi-longgen", "serve-solar-longctx",
        "serve-nemotron-reasoning", CELL]
    assert by_name["moe_experts_decode_roofline_pct"]["workloads"][-2:] == [
        "serve-nemotron-reasoning", CELL]
    assert by_name["moe_latent_time_pct"]["workloads"] == [
        "serve-nemotron-reasoning"]
    assert by_name["gqa_rows_read_pct"]["workloads"] == [
        "serve-solar-longctx", "serve-nemotron-reasoning"]
    # every entry but the appended ones is where PR 53 left it
    assert [m["name"] for m in bench["per_layer"]].index(
        "moe_latent_time_pct") == 123
    assert len({m["name"] for m in bench["per_layer"]}) == len(
        bench["per_layer"])


@pytest.mark.parametrize("name", ["kanana", "brumby", "granite", "kimi",
                                  "keye"])
def test_every_familys_cell_still_reads_what_it_reads_beside_a_later_cell(
        name):
    """What `test_a_tenth_cell.py` and `test_keye_family.py` held of the
    copy of the file with a further cell's four entries appended, which
    `tests/conftest.py` marks since this PR's three entries took the copy
    past the 128 entries a file may hold: the same copy without this PR's
    three (they list this cell alone, so no other cell reads them), every
    family's cell held to what it reads."""
    import copy

    tenth = importlib.import_module("test_a_tenth_cell")
    bench = copy.deepcopy(spec.benchmark())
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-3:])
    del bench["per_layer"][-3:]
    one_more = tenth.with_a_tenth_cell(bench)
    assert len(one_more["per_layer"]) \
        == len(spec.benchmark()["per_layer"]) + 1 <= 128
    assert len(one_more["workloads"]) == len(
        spec.benchmark()["workloads"]) + 1
    importlib.import_module(
        f"test_{name}_family").the_cell_reads_what_it_reads(one_more)
    # and from the file itself
    importlib.import_module(
        f"test_{name}_family").the_cell_reads_what_it_reads(spec.benchmark())


def test_nemotrons_cell_reads_what_it_read_with_its_entry_found_by_name():
    """What `test_nemotron_family.py::test_the_cell_reads_what_it_reads`
    held, which held the list's last entry to Nemotron's own and the cells
    to twelve: the entry found by name, the counts read from the file."""
    nemotron = importlib.import_module("test_nemotron_family")
    bench = spec.benchmark()
    cell = spec.cell(bench, nemotron.CELL)
    assert cell["chips"] == 1 and cell["traffic"] == nemotron.TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names and nemotron.OWN <= names
    assert names.isdisjoint({"mla_attend_time_pct", "kda_update_time_pct",
                             "mla_attend_roofline_pct", *NEW})
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
    assert cells.index(nemotron.CELL) == 11 and len(cells) == 13
    configs = [c["name"] for c in bench["configs"]]
    assert configs.index(nemotron.CONFIG["name"]) == len(configs) - 2
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
        "generator": "closed_loop_documents", "clients": 160,
        "requests_per_client": 6, "documents": 8,
        "document_uniform": [1024, 1792], "document_block": 128,
        "question_uniform": [32, 96], "output_uniform": [512, 1024],
        "schedule_seed": 55, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] * 4 == 5 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    # a turn is under a block, so the warm-up pools all eight preambles
    assert TRAFFIC["question_uniform"][1] < d["kv_block_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2
    assert (TRAFFIC["documents"] * TRAFFIC["document_uniform"][1]
            <= d["kv_blocks"] * d["kv_block_size"])


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_preambles_the_turns_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 160 * 6 and plan["clients"] == 160
    documents = {}
    for r in requests:
        n = len(r["prompt_ids"])
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 16384
        assert 1024 + 32 <= n <= 1792 + 96
        documents.setdefault(r["document"], []).append(r["prompt_ids"])
    assert sorted(documents) == list(range(8))
    # equally often, and each a whole number of blocks of 128 shared by all
    # its requests, a turn of 32-96 after it
    assert {len(v) for v in documents.values()} == {120}
    heads = {}
    for d, prompts in documents.items():
        shared = min(len(p) for p in prompts) - 32
        blocks = shared // 128
        while len({tuple(p[:blocks * 128]) for p in prompts}) > 1:
            blocks -= 1
        heads[d] = prompts[0][:blocks * 128]
        assert 1024 <= blocks * 128 <= 1792
        assert all(32 <= len(p) - blocks * 128 <= 96 for p in prompts)
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
    # a position a sublayer: 576 bf16 values, and at 64 heads 2 x 64 x (512
    # + 64) operations of scores and 2 x 64 x 512 of mix
    row = costs["mla_attend_per_position"]
    assert row == {"bytes": 1152.0, "flops": 139264.0}
    assert row["flops"] / row["bytes"] == pytest.approx(120.9, abs=0.1)
    expert = costs["moe_experts_per_touched_expert"]
    assert expert == {"bytes": 3 * 6144 * 2048 * 2, "flops": 0.0}  # 75.5 MB
    assert costs["moe_experts_per_row"] == {
        "bytes": 2 * 6144 * 2, "flops": 6 * 6144 * 2048}
    assert costs == {
        "attention_layers": 8, "routed_experts": 16,
        "mla_attend_per_position": row,
        "moe_experts_per_row": costs["moe_experts_per_row"],
        "moe_experts_per_touched_expert": expert}
    peaks = spec.peaks()["TPU v5 lite"]
    # the rows are bound by their bytes at 64 heads too (120 operations a
    # byte under the chip's ridge of 240), and so is an expert at two rows
    assert _moe_scopes.bound_seconds(row, peaks)[0] == "bytes"
    assert peaks["bf16_flops_per_s"] / peaks["hbm_bytes_per_s"] \
        == pytest.approx(240.5, abs=0.1)
    step = family.moe_experts_decode_cost(family.experts_cost_model(m),
                                          32.0, 14.0)
    assert _moe_scopes.bound_seconds(step, peaks)[0] == "bytes"
    # the issue's reckoning: ~56 touched experts a step over four layers,
    # 4.2 GB; the rows of 128 slots at 2.2k positions, 2.6 GB
    assert round(4 * 14 * expert["bytes"] / 1e9, 1) == 4.2
    assert round(128 * 2200 * family.kv_bytes_per_token(m) / 1e9, 1) == 2.6
    assert family.attention_sublayers(m) == 8


# --------------------------------------------------------------- reference

def tiny_layer(seed: int) -> dict:
    """One double layer's weights at the tiny size, float32, as the program
    lays them out, every norm's scale its own."""
    rng = np.random.default_rng(seed)
    d, H, rq, r = 64, 4, 24, 32

    def normal(*shape, std=0.2):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    def scale(n):
        return {"scale": rng.uniform(0.5, 1.5, (2, n)).astype(np.float32)}

    return {"attn": {"norm": scale(d), "wqa": normal(2, d, rq),
                     "q_norm": scale(rq), "wqb": normal(2, rq, H, 24),
                     "wkva": normal(2, d, r + 8), "kv_norm": scale(r),
                     "wkvb": normal(2, r, H, 32),
                     "wo": normal(2, H * 16, d)},
            "dense": {"norm": scale(d), "w_in": normal(2, d, 256),
                      "w_out": normal(2, 128, d)},
            "moe": {"router": normal(1, d, 12, std=0.5),
                    "bias": normal(1, 12, std=0.01)},
            "experts": {"wg": normal(4, d, 32), "wu": normal(4, d, 32),
                        "wd": normal(4, 32, d)}}


def mla_a_token_at_a_time(u, p, i):
    """Sublayer i's attention of the normed u [T, d] in numpy float64, a
    query at a time against the keys and values before it, the rotation by
    complex numbers: lane j of the 8 turns with lane j + 4."""
    p = {k: np.asarray(v[i] if not isinstance(v, dict) else v["scale"][i],
                       np.float64) for k, v in p.items()}
    u = np.asarray(u, np.float64)
    T = u.shape[0]

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w

    def turn(x, t):                                     # x [.., 8]
        angle = t / 10000000 ** (np.arange(4) / 4)
        z = (x[..., :4] + 1j * x[..., 4:]) * np.exp(1j * angle)
        return np.concatenate([z.real, z.imag], -1)

    c_q = math.sqrt(64 / 24) * norm(u @ p["wqa"], p["q_norm"])
    q = np.einsum("tr,rhk->thk", c_q, p["wqb"])
    ckr = u @ p["wkva"]
    c = math.sqrt(64 / 32) * norm(ckr[:, :32], p["kv_norm"])
    kv = np.einsum("tr,rhk->thk", c, p["wkvb"])
    out = np.zeros((T, 4 * 16))
    for t in range(T):
        q_r = turn(q[t, :, 16:], t)
        keys = np.stack([turn(ckr[s, 32:], s) for s in range(t + 1)])
        scores = (np.einsum("hn,shn->hs", q[t, :, :16], kv[:t + 1, :, :16])
                  + q_r @ keys.T) / math.sqrt(24)
        w = np.exp(scores - scores.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[t] = np.einsum("hs,shv->hv", w, kv[:t + 1, :, 16:]).reshape(-1)
    return out @ p["wo"]


def test_latent_attention_agrees_with_a_token_at_a_time():
    p = tiny_layer(0)
    u = np.random.default_rng(1).standard_normal((40, 64)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        for i in (0, 1):
            m = jax.tree.map(lambda a: a[i], p["attn"])
            got = np.asarray(family._mla_row(u, m, TINY_MODEL, None))
            want = mla_a_token_at_a_time(u, p["attn"], i)
            assert np.abs(want).max() > 0.5
            np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_expert_block_agrees_with_a_token_at_a_time():
    """12 outputs of which the last 4 zero-compute, 3 a token, experts 2..5
    held: a token's r is its held pairs' SwiGLUs by their gates plus its
    zero pairs' gates times h, its absent pairs nothing."""
    p = tiny_layer(2)
    h = np.random.default_rng(3).standard_normal((30, 64)).astype(np.float32)
    import jax
    with jax.default_matmul_precision("highest"):
        got, chosen = family._expert_block(h, p["moe"], p["experts"],
                                           TINY_MODEL)
    got, chosen = np.asarray(got), np.asarray(chosen)
    logits = h.astype(np.float64) @ p["moe"]["router"][0]
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    kinds = set()
    for t in range(30):
        top = np.argsort(-(s[t] + p["moe"]["bias"][0]))[:3]
        assert sorted(top) == sorted(chosen[t])
        want = np.zeros(64)
        for e in top:
            if e >= 8:
                want += 6 * s[t, e] * h[t]
                kinds.add("zero")
            elif 2 <= e < 6:
                w = {k: v[e - 2].astype(np.float64)
                     for k, v in p["experts"].items()}
                a = h[t] @ w["wg"]
                want += 6 * s[t, e] * (
                    (a / (1 + np.exp(-a)) * (h[t] @ w["wu"])) @ w["wd"])
                kinds.add("held")
            else:
                kinds.add("absent")
        np.testing.assert_allclose(got[t], want, rtol=2e-5, atol=2e-5)
    assert kinds == {"zero", "held", "absent"}


@pytest.mark.parametrize("degrade", [d for d in family.DEGRADE if d])
def test_a_degraded_reference_is_another_function(degrade):
    p = tiny_layer(3)
    x = np.random.default_rng(3).standard_normal((2, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    off = np.asarray(family.reference_layer(x, p, TINY_MODEL, degrade))
    assert np.isfinite(off).all() and np.abs(exact - off).max() > 1e-4
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY_MODEL, "float8_state")


def test_the_reference_holds_its_rows_as_the_file_states_them():
    p = tiny_layer(4)
    x = np.random.default_rng(4).standard_normal((1, 40, 64)).astype(
        np.float32)
    exact = np.asarray(family.reference_layer(x, p, TINY_MODEL))
    stated = np.asarray(family.reference_layer(
        x, p, {**TINY_MODEL, "rows": "bfloat16"}))
    coarse = np.asarray(family.reference_layer(x, p, TINY_MODEL,
                                               "float8_rows"))
    near, far = np.abs(exact - stated).max(), np.abs(exact - coarse).max()
    assert 0 < near < far / 8


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "longcat.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_mla_row", "_swiglu", "_expert_block",
                 "reference_layer", "reference_head", "Reference",
                 "reference_model", "experts_cost_model",
                 "attention_sublayers", "kv_bytes_per_token"}
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
    # with room, and under the nearest precisions below what the file
    # states; every degradation the issue names is refused by one limit at
    # least, in every reading
    floor, mean = (limits["engine_logit_floor_abs"],
                   limits["engine_logit_mean_abs"])
    assert max(floor["program"] + floor["cell"]) * 2 <= floor["limit"]
    assert max(mean["program"] + mean["cell"]) * 2 <= mean["limit"]
    for degrade in ("bfloat16_stream", "one_piece", "float8_rows",
                    "no_zero_term", "unscaled_latent"):
        readings = list(zip(floor["degraded"][degrade],
                            mean["degraded"][degrade]))
        assert readings, degrade
        assert all(f > floor["limit"] or m > mean["limit"]
                   for f, m in readings), degrade
    for degrade in ("bfloat16_stream", "one_piece"):
        assert floor["limit"] * 1.5 <= min(floor["degraded"][degrade])


# ------------------------------------------------------------ the readers

def _record(decode_before: dict, decode_after: dict, steps: int) -> dict:
    def side(counts, engine_steps):
        return {"step_counts": {"decode": counts, "chunk": counts},
                "engine_steps": engine_steps, "chunk_steps": 0,
                "roofline_costs": family.roofline_costs(CONFIG["model"])}

    return {"counters": {"before": side(decode_before, 10),
                         "after": side(decode_after, 10 + steps)}}


def test_the_new_entries_read_their_numbers_and_nothing_where_none_is():
    """The three readers on records with no trace and no such column (a
    parent's, an untraced run's): nothing, and no exception; their scopes
    and their column are the program's."""
    for name in NEW:
        reader = spec.metric_reader(name)
        assert reader.read({}) is None
        assert reader.read({"trace_dir": None, "counters": None}) is None
        assert reader.read({"trace_dir": "/nonexistent/trace"}) is None
    pairs = spec.metric_reader("moe_zero_pairs_pct")
    zero = {"expert_rows": 0, "experts_touched": 0,
            "busiest_expert_rows": 0, "expert_layer_steps": 0,
            "attended_positions": 0, "read_positions": 0,
            "expert_rows_all": 0}
    after = {**zero, "expert_rows": 64, "expert_rows_all": 3072,
             "zero_rows": 1024, "expert_layer_steps": 8}
    # two steps of 128 slots x 12 pairs x ... a third zero-compute
    assert pairs.read(_record({**zero, "zero_rows": 0}, after, 2)) \
        == pytest.approx(100 / 3)
    # a program that counts no such column (Kimi's seven): nothing
    assert pairs.read(_record(zero, {**zero, "expert_rows_all": 3072},
                              2)) is None
    # the counters wrap
    wrapped = {**after, "zero_rows": 5, "expert_rows_all": 20}
    before = {**zero, "zero_rows": 2 ** 32 - 5,
              "expert_rows_all": 2 ** 32 - 10}
    assert pairs.read(_record(before, wrapped, 1)) == pytest.approx(
        100 * 10 / 30)
    held = spec.metric_reader("moe_held_rows_pct")
    assert held.read(_record({**zero, "zero_rows": 0}, after, 2)) \
        == pytest.approx(100 * 64 / 3072)
    time_zero = spec.metric_reader("moe_zero_time_pct")
    time_dense = spec.metric_reader("mlp_dense_time_pct")
    assert time_zero._scope_of(
        "jit(_step)/layers/while/body/mlp/moe_zero/mul") == "moe_zero"
    assert time_zero._scope_of(
        "jit(_step)/layers/while/body/mlp/moe_dispatch/mul") is None
    assert time_dense._scope_of(
        "jit(_step)/layers/while/body/mlp/mlp_dense/dot_general") \
        == "mlp_dense"
    assert time_dense._scope_of(
        "jit(_step)/layers/while/body/mlp/moe_experts/pallas_call") is None
    assert time_dense._scope_of(None) is None
    with open(os.path.join(REPO, "ray_tpu", "models", "longcat.py")) as f:
        assert 'jax.named_scope("mlp_dense")' in f.read()
    with open(os.path.join(REPO, "ray_tpu", "models", "moe.py")) as f:
        assert 'jax.named_scope("moe_zero")' in f.read()


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_longcat.py`: the generator, the warm-up, the pool
    hits, the engine's counters and `check_served`, through the harness's
    own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_longcat.py"),
         "--workload", CELL, "--seconds", "10", "--seed", "2550000123"],
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
    # 2 layers x 2 sublayers x (32 + 8) bf16 values
    assert other["kv_bytes_per_token"]["value"] == 4 * 40 * 2
    assert "state_bytes_per_slot" not in other
    # 16 of 768 outputs held and 256 zero-compute, under the seed's skew
    assert 0.3 < other["moe_held_rows_pct"]["value"] < 8
    assert 20 < other["moe_zero_pairs_pct"]["value"] < 48
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("longcat")
    try:
        with pytest.raises(ValueError, match="no serving family has the "
                                             "preset 'longcat-flash-chat'"):
            family.program_config(CONFIG)
    finally:
        models._SERVING.update(saved)
