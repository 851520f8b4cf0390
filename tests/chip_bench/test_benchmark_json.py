"""BENCHMARK.json against the contract it is held to, and the harness
against its own rule: driven by data, it names no family, cell or metric."""

import json
import os
import re

import pytest

from conftest import CHIP_DIR, REPO
from harness import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    # a full check of 24 cells fits into the driver's 43,200 seconds
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_command_and_paths():
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        if os.path.exists(os.path.join(REPO, word)):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
        assert os.path.isdir(os.path.join(REPO, p))
    assert "tests/chip_bench" in BENCH["paths"]


@pytest.mark.parametrize("name", [
    x["name"] for x in METRICS + BENCH["workloads"] + BENCH["configs"]]
    + [w["traffic"] for w in BENCH["workloads"]])
def test_names_use_only_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric, bench=BENCH):
    """`bench`, here and below: the file, or a copy of it in memory that a
    later PR's cell has joined (`test_a_tenth_cell.py`)."""
    e2e = metric in bench["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
        # reported only where the metric it moves is
        (moved,) = [m for m in bench["end_to_end"]
                    if m["name"] == metric["moves"]]
        cells = {w["name"] for w in bench["workloads"]}
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    assert spec.metric_reader(metric["name"]) is not None, \
        f"no reader file for {metric['name']}"


def test_no_two_of_a_kind_share_a_name(bench=BENCH):
    for group in (bench["end_to_end"] + bench["per_layer"],
                  bench["workloads"], bench["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))


def test_one_entry_a_reading(bench=BENCH):
    """An entry is a reader and the end-to-end metric it moves: a cell
    that reads what another reads joins the entry's `workloads`, it brings
    no copy under a suffix of its own (PR 45 folded 18 such copies)."""
    pairs = [(m["name"].split(".")[0], m["moves"])
             for m in bench["per_layer"]]
    assert sorted(set(pairs)) == sorted(pairs)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"].startswith("https://")
    assert 1 <= len(config["why"]) <= 200 and len(config["reduced"]) <= 16
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    body = spec.load_json(os.path.join(REPO, config["file"]))
    assert body["name"] == config["name"]
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"]
    assert body["kind"] in ("train", "serve")
    assert {"assumed", "departures", "stands_for", "model"} <= set(body)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    assert os.path.exists(os.path.join(
        CHIP_DIR, "families", body["family"] + ".py"))
    assert os.path.exists(os.path.join(
        CHIP_DIR, "harness", body["kind"] + "_cell.py"))


@pytest.mark.parametrize("workload", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_workload_entry_and_what_it_names(workload, bench=BENCH):
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    assert workload["chips"] in (1, 4)
    assert 1 <= len(workload["why"]) <= 200 and "\n" not in workload["why"]
    cell = spec.cell(bench, workload["name"])
    assert os.path.exists(os.path.join(
        CHIP_DIR, "generators", cell["traffic"]["generator"] + ".py"))
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    assert {m["moves"] for m in cell["per_layer"]} <= e2e


def test_at_most_a_quarter_of_the_cells_take_four_chips(bench=BENCH):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_one_layer_one_spelling(bench=BENCH):
    layers = {m["layer"] for m in bench["per_layer"]}
    assert len({name.lower() for name in layers}) == len(layers)


def test_peaks_name_their_source():
    with open(os.path.join(CHIP_DIR, "peaks.json")) as f:
        table = json.load(f)
    assert "Google Cloud" in table["_source"]
    assert spec.peaks()["TPU v5 lite"]["bf16_flops_per_s"] == 197e12


def test_the_harness_names_no_family_cell_or_metric():
    named = ({x["name"] for x in METRICS + BENCH["workloads"]
              + BENCH["configs"]} | {w["traffic"] for w in BENCH["workloads"]}
             | {m["name"].split(".")[0] for m in METRICS})
    families = {f[:-3] for f in os.listdir(os.path.join(CHIP_DIR, "families"))
                if f.endswith(".py")}
    files = [os.path.join(CHIP_DIR, "run.py"),
             os.path.join(CHIP_DIR, "trace_reduce.py")] + [
        os.path.join(CHIP_DIR, "harness", f)
        for f in os.listdir(os.path.join(CHIP_DIR, "harness"))
        if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        for word in named | families:
            assert not re.search(rf"(?<![\w.]){re.escape(word)}(?![\w])",
                                 text), f"{path} names {word!r}"
