#!/usr/bin/env python3
"""Rehearsal without the chip: one cell end to end on the CPU at a tiny
size, through the same phases, generators, readers and output as the
command, with the device check and the chip request replaced here (the
command has no option for it) and the sizes of the cell's configuration
and traffic cut by the tables below. Nothing it prints is a measurement.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell.py \
        --workload serve-xl-chat [--seconds 8] [--trace 1]

The four-chip cell runs on four virtual CPU devices.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_START = time.time()
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]

TINY_MODEL = {"vocab_size": 500, "padded_vocab_size": 512, "n_positions": 128,
              "n_ctx": 128, "n_embd": 128, "n_layer": 2, "n_head": 4}
TINY = {
    "train": {"job": {"seq_len": 64, "global_batch": 8},
              "traffic": {"dataset_batches": 16, "trace_seconds": 1.0}},
    "serve": {"deployment": {"preset": "gpt2-tiny", "max_seq_len": 128,
                             "prefill_chunk_size": 16, "kv_blocks": 32},
              "traffic": {"rate_per_s": 3.0, "tail_s": 1.0, "ramp_s": 2.0,
                          "drain_s": 10.0, "trace_seconds": 1.0,
                          "prompt": {"system_tokens": 32,
                                     "user_lognormal": [12, 0.9],
                                     "user_clip": [4, 40],
                                     "answer_tokens": 8,
                                     "followup_min_gap_s": 0.5,
                                     "max_prompt_tokens": 100},
                          "output": {"lognormal": [8, 0.7],
                                     "clip": [4, 16]},
                          "clients": 6, "requests_per_client": 500,
                          "prompt_uniform": [4, 16],
                          "output_uniform": [8, 24]}},
}


def merge(into: dict, cut: dict) -> None:
    for k, v in cut.items():
        if isinstance(v, dict) and isinstance(into.get(k), dict):
            merge(into[k], v)
        else:
            into[k] = v


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

    from harness import device, output, procs, spec

    cell = spec.cell(spec.benchmark(), args.workload)
    if cell["chips"] > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}")
    kind_name = cell["config"]["kind"]
    cell["config"]["model"].update(TINY_MODEL)
    cuts = TINY[kind_name]
    for key in ("job", "deployment"):
        if key in cell["config"]:
            merge(cell["config"][key], cuts.get(key, {}))
    merge(cell["traffic"], {k: v for k, v in cuts["traffic"].items()
                            if k in cell["traffic"]})
    device.require_chip = lambda devices, chips: None
    device.chip_request = lambda chips: 0

    import importlib

    kind = importlib.import_module(f"harness.{kind_name}_cell")
    args.workdir = os.path.join(REPO, ".bench_runs", f"rehearse-"
                                                    f"{args.workload}")
    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    args.t_start = T_START
    results = {}
    for phase, _ in kind.PHASES:
        results[phase] = r = {}
        kind.run_phase(phase, cell, args, r)
        procs.remove_cluster_shm(args.workdir)      # as the command does
    line = output.result_line(
        cell, results, bool(args.trace),
        lambda m: print(f"[rehearse] {m}", file=sys.stderr))
    print(json.dumps(line))
    both = output.result_line(cell, results, not args.trace, lambda m: None)
    print("[rehearse] the other set of metrics:",
          json.dumps(both["metrics"]), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
