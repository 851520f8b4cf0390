"""A later PR's cell, appended to a copy of BENCHMARK.json in memory as a
`model_config` PR may append it (its name added to lists, its entries added
at the end, nothing that is there touched): every test that reads the
file's entries still holds. What PR 45 bought: before it each family's test
pinned its entries' lists to its own cell, so a new cell brought a suffixed
copy of every reading it shared, and `per_layer` stood at 125 of 128."""

import copy
import importlib

import pytest

import test_benchmark_json as contract
import test_folded_entries as folded
import test_hot_path_metrics as hot_path
from harness import spec

LIKE, TENTH = "serve-kimi-longgen", "serve-tenth-docs"
# the two entries of its own, then two more `.decode` readings such as a
# later `tracing` or `perf_opt` PR appends: one for every decode cell, one
# for the first decode cell alone
APPENDED = ["attn_kernel_time_pct.decode", "flash_attn_roofline_pct.decode",
            "attn_time_pct.decode", "mlp_time_pct.decode"]


def with_a_tenth_cell(bench: dict) -> dict:
    bench = copy.deepcopy(bench)
    (like,) = [w for w in bench["workloads"] if w["name"] == LIKE]
    bench["workloads"].append({**like, "name": TENTH,
                               "traffic": "doc-grounded-generation"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE in m.get("workloads", []):
            m["workloads"].append(TENTH)
    (kda,) = [m for m in bench["per_layer"]
              if m["name"] == "kda_update_time_pct"]
    (serve,) = [m for m in bench["end_to_end"]
                if m["name"] == "serve_tokens_per_s"]
    # readers that no entry moving this metric has
    bench["per_layer"] += [
        {**kda, "name": APPENDED[0], "workloads": [TENTH]},
        {**kda, "name": APPENDED[1], "unit": "%", "better": "higher",
         "workloads": [TENTH]},
        {**kda, "name": APPENDED[2], "workloads": list(serve["workloads"])},
        {**kda, "name": APPENDED[3], "workloads": ["serve-xl-decode"]}]
    return bench


@pytest.fixture(scope="module")
def ten():
    return with_a_tenth_cell(spec.benchmark())


@pytest.mark.parametrize("family", ["kanana", "brumby", "granite", "kimi"])
def test_every_familys_cell_still_reads_what_it_reads(family, ten):
    importlib.import_module(
        f"test_{family}_family").the_cell_reads_what_it_reads(ten)


def test_the_tenth_cell_reads_what_the_cell_it_is_like_reads_and_its_own(ten):
    assert len(ten["workloads"]) == 10
    names = [m["name"] for m in spec.cell(ten, TENTH)["per_layer"]]
    assert names[:-3] == [m["name"]
                          for m in spec.cell(ten, LIKE)["per_layer"]][:-1]
    assert names[-3:] == APPENDED[:3]
    assert [m["name"] for m in ten["per_layer"][-4:]] == APPENDED
    assert len(ten["per_layer"]) == len(spec.benchmark()["per_layer"]) + 4


def test_the_files_own_rules_hold_with_the_tenth_cell(ten):
    for metric in ten["end_to_end"] + ten["per_layer"]:
        contract.test_metric_entry(metric, bench=ten)
    for workload in ten["workloads"]:
        contract.test_workload_entry_and_what_it_names(workload, bench=ten)
    contract.test_no_two_of_a_kind_share_a_name(bench=ten)
    contract.test_one_entry_a_reading(bench=ten)
    contract.test_at_most_a_quarter_of_the_cells_take_four_chips(bench=ten)
    contract.test_one_layer_one_spelling(bench=ten)
    # and the tests beside the contract's that read the file's entries
    folded.test_the_table_of_the_fold(bench=ten)
    hot_path.test_the_new_entries_are_the_ones_the_issue_lists(bench=ten)


def test_a_suffixed_copy_of_a_reading_is_refused(ten):
    (kv,) = [m for m in ten["per_layer"] if m["name"] == "kv_bytes_per_token"]
    ten["per_layer"].append({**kv, "name": "kv_bytes_per_token.tenth",
                             "workloads": [TENTH]})
    with pytest.raises(AssertionError):
        contract.test_one_entry_a_reading(bench=ten)
    ten["per_layer"].pop()
