#!/usr/bin/env python3
"""The benchmark's one command: runs one cell of BENCHMARK.json once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, with `--trace 1`,
`breakdown`. With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics. Everything else goes to
standard error. Without the chips the cell asks for, the exit code is not
0 and standard output carries no result.

This process never opens a device (a process that has touched the chip
holds it); it imports JAX only to read a finished trace. Each phase of a
cell is a child session (`harness/procs.py`); which phases a cell has, and
what they do, comes from its kind (`harness/<kind>_cell.py`), its
configuration, its traffic file and its model family, all found by name.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import time

T_START = time.time()
CHIP_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
for _p in (REPO, CHIP_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import output, procs, spec  # noqa: E402

RUNS_DIR = os.path.join(REPO, ".bench_runs")    # git-ignored, in the checkout


def log(msg: str) -> None:
    print(f"[bench {time.time() - T_START:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def environment() -> None:
    """What every process of the run inherits: the compile cache at the
    program's own fixed path in the checkout (or where the environment
    says), with every program kept in it however quickly it compiled, so
    that a cell's second run compiles nothing."""
    from ray_tpu.utils.platform import enable_compile_cache

    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [REPO, CHIP_DIR] + [p for p in os.environ.get(
            "PYTHONPATH", "").split(os.pathsep) if p])
    enable_compile_cache()


def parent(args) -> int:
    cell = spec.cell(spec.benchmark(), args.workload)
    kind = importlib.import_module(f"harness.{cell['config']['kind']}_cell")
    from ray_tpu.core.resources import detect_num_tpu_chips

    found = detect_num_tpu_chips()      # what ray_tpu.init() would advertise
    if found < cell["chips"]:
        print(json.dumps({"correct": False, "error":
                          f"{args.workload} asks for {cell['chips']} TPU "
                          f"chip(s) and this machine has {found}: no result "
                          f"is produced"}), file=sys.stderr, flush=True)
        return 1
    workdir = os.path.join(RUNS_DIR, f"{args.workload}-s{args.seed}"
                                     f"-t{args.trace}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    results = {}
    for phase, limit_s in kind.PHASES:
        argv = [sys.executable, os.path.abspath(__file__),
                "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", str(args.trace),
                "--phase", phase, "--workdir", workdir,
                "--t-start", repr(T_START)]
        results[phase] = procs.run_phase(
            argv, os.path.join(workdir, f"{phase}.json"), limit_s,
            lambda m, p=phase: log(f"{p}: {m}"))
        if not results[phase]["ok"]:
            err = results[phase]["error"]
            print(json.dumps({"correct": False, "phase": phase,
                              "error": err}), file=sys.stderr, flush=True)
            return 1
    line = output.result_line(cell, results, bool(args.trace), log)
    print(json.dumps(line), flush=True)
    return 0


def child(args) -> int:
    cell = spec.cell(spec.benchmark(), args.workload)
    kind = importlib.import_module(f"harness.{cell['config']['kind']}_cell")
    result: dict = {"ok": False}
    try:
        kind.run_phase(args.phase, cell, args, result)
        result["ok"] = True
    except Exception as e:  # noqa: BLE001 - the phase's failure, reported
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:2000]
    with open(os.path.join(args.workdir, f"{args.phase}.json"), "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    ap.add_argument("--t-start", type=float, dest="t_start",
                    default=T_START, help=argparse.SUPPRESS)
    args = ap.parse_args()
    environment()
    return child(args) if args.phase else parent(args)


if __name__ == "__main__":
    sys.exit(main())
