"""A later PR adds a configuration, a mix, a per-layer metric and a model
family as new files plus entries, and edits no file that is there: shown
in a temporary copy of the benchmark."""

import json
import os
import shutil
import subprocess
import sys

from conftest import CHIP_DIR, REPO

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/benchmarks/chip"]
from harness import output, spec
cell = spec.cell(spec.benchmark(), "dummy-cell")
fam = spec.family(cell["config"]["family"])
plan = spec.generator(cell["traffic"]["generator"]).generate(
    cell["traffic"], cell["config"], 3, 5.0)
record = {"devices": [{"platform": "tpu", "kind": "TPU v5 lite",
                       "peak_bytes_in_use": 7}],
          "t_start": 0.0, "window": {"t0": 2.0, "t1": 7.0},
          "dummy": fam.ANSWER + plan["n"]}
results = {"measure": {"record": record, "attempted": 1, "failed": 0,
                       "correct": True}}
print(json.dumps(output.result_line(cell, results, True, lambda m: None)))
"""


def test_a_dummy_of_each_kind_is_files_plus_entries(tmp_path):
    root = str(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(CHIP_DIR, chip,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {}
    for d, _, files in os.walk(chip):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()

    def add(rel, text):
        path = os.path.join(chip, rel)
        assert not os.path.exists(path)
        with open(path, "w") as f:
            f.write(text)

    add("families/dummy.py", "ANSWER = 40\n")
    add("generators/dummy_gen.py",
        "def generate(traffic, config, seed, seconds):\n"
        "    return {'n': traffic['n']}\n")
    add("traffic/dummy-mix.json", json.dumps({"generator": "dummy_gen",
                                              "n": 2}))
    add("configs/dummy-config.json", json.dumps(
        {"name": "dummy-config", "kind": "serve", "family": "dummy",
         "model": {}}))
    add("metrics/dummy_metric.py", "def read(record):\n"
                                   "    return record['dummy']\n")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "dummy-config", "source": "https://example.org/dummy",
        "file": "benchmarks/chip/configs/dummy-config.json", "reduced": [],
        "why": "a dummy"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "dummy-config",
        "traffic": "dummy-mix", "chips": 1, "why": "a dummy"})
    bench["per_layer"].append({
        "name": "dummy_metric.x", "unit": "things", "better": "higher",
        "source": "program_counter", "layer": "dummy", "moves": "setup_s",
        "workloads": ["dummy-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    out = subprocess.run([sys.executable, "-c", SCRIPT, root],
                         capture_output=True, text=True, timeout=120,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metrics"]["dummy_metric.x"] == {"value": 42,
                                                 "unit": "things"}
    # worker_ready_s and compile_cache_new find nothing to read in the
    # dummy's record and are left out, not invented
    assert set(line["metrics"]) == {"dummy_metric.x"}
    assert line["device"]["count"] == 1
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, f"{path} was edited"
