"""Without the chips a cell asks for, the command gives no result."""

import json
import os
import subprocess
import sys

import pytest

from conftest import REPO
from harness import device, spec

BENCH = spec.benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_run_py_on_a_cpu_exits_nonzero_with_no_result(workload):
    out = subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", workload, "--seed", "1", "--seconds", "1",
           "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""          # no result, no device metric
    said = json.loads(out.stderr.strip().splitlines()[-1])
    assert said["correct"] is False and "metrics" not in said


CPU = [{"platform": "cpu", "kind": "cpu", "peak_bytes_in_use": 0}]
V5E = [{"platform": "tpu", "kind": "TPU v5 lite", "peak_bytes_in_use": 5}]


@pytest.mark.parametrize("devices,chips,ok", [
    (V5E, 1, True), (V5E * 4, 4, True), (CPU, 1, False), (V5E, 4, False),
    ([{**V5E[0], "kind": "TPU v9"}], 1, False)])
def test_require_chip(devices, chips, ok):
    if ok:
        device.require_chip(devices, chips)
    else:
        with pytest.raises(device.NoChip):
            device.require_chip(devices, chips)


def test_an_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        spec.cell(BENCH, "no-such-cell")
