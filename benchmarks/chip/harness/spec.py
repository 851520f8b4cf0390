"""Where the benchmark's data lives and how a cell is looked up by name."""

from __future__ import annotations

import importlib
import json
import os

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
BENCHMARK_JSON = os.path.join(REPO, "BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(path: str = BENCHMARK_JSON) -> dict:
    return load_json(path)


def peaks() -> dict:
    table = load_json(os.path.join(CHIP_DIR, "peaks.json"))
    return {k: v for k, v in table.items() if not k.startswith("_")}


def cell(bench: dict, name: str) -> dict:
    """One entry of `workloads` joined with its configuration file, its
    traffic file and the metrics it reports."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(by_name)}")
    workload = by_name[name]
    (config_entry,) = [c for c in bench["configs"]
                       if c["name"] == workload["config"]]
    config = load_json(os.path.join(REPO, config_entry["file"]))
    traffic = load_json(os.path.join(CHIP_DIR, "traffic",
                                     workload["traffic"] + ".json"))

    def mine(metric):
        return name in metric.get("workloads", [name])

    return {"name": name, "chips": workload["chips"], "config": config,
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def family(name: str):
    return importlib.import_module(f"families.{name}")


def generator(name: str):
    return importlib.import_module(f"generators.{name}")


def metric_reader(metric_name: str):
    """The reader of one metric: `metrics/<name>.py`. A name may carry a
    suffix after a dot (`<reading>.<suffix>`) where one reading is
    listed once for each end-to-end metric it should move; the suffix is
    not part of the reader's name. None when no file has it."""
    module = metric_name.split(".")[0].replace("-", "_")
    try:
        return importlib.import_module(f"metrics.{module}")
    except ModuleNotFoundError as e:
        if e.name != f"metrics.{module}":
            raise
        return None


def compile_cache_entries() -> int:
    """Files in the persistent compilation cache (`chip_smoke.py`'s
    `cache_entries`, PR 21)."""
    from ray_tpu.utils.platform import compile_cache_dir

    try:
        return len(os.listdir(compile_cache_dir()))
    except OSError:
        return 0
