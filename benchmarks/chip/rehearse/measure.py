#!/usr/bin/env python3
"""Runs cells several times as the driver does and says how far the runs
spread: for each metric the median and the distance between the quartiles
over the median. Every run is the benchmark's own command in a process of
its own, each with another seed. Results and the runs' standard error go
to `chiprun_out/` (git-ignored), where the chip tool brings them back;
`--out` names that directory when the benchmark runs from another
checkout, such as an unpacked `git archive`.

    python benchmarks/chip/rehearse/measure.py --workloads a,b \
        --seeds 1,2,3,4,5,6 [--trace 0] [--seconds N] [--keep record,trace] \
        [--out DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path.insert(0, CHIP_DIR)

from harness.client_log import percentile  # noqa: E402


def quartile_spread(values: list) -> tuple:
    med = percentile(values, 50)
    return med, (percentile(values, 75) - percentile(values, 25)) / med \
        if med else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep", default="")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    args = ap.parse_args()
    out = os.path.abspath(args.out)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(out, exist_ok=True)
    rc_all = 0
    for workload in args.workloads.split(","):
        values: dict = {}
        for seed in args.seeds.split(","):
            stem = f"{workload}.s{seed}.t{args.trace}{args.tag}"
            t0 = time.time()
            with open(os.path.join(out, stem + ".err"), "w") as err:
                p = subprocess.run(
                    bench["command"] + ["--workload", workload, "--seed",
                                        seed, "--seconds", str(seconds),
                                        "--trace", str(args.trace)],
                    cwd=REPO, stdout=subprocess.PIPE, stderr=err, text=True)
            wall = time.time() - t0
            last = (p.stdout.strip().splitlines() or [""])[-1]
            try:
                line = json.loads(last)
            except ValueError:
                line = None
            with open(os.path.join(out, "results.jsonl"), "a") as f:
                f.write(json.dumps({"workload": workload, "seed": int(seed),
                                    "trace": args.trace, "seconds": seconds,
                                    "tag": args.tag, "rc": p.returncode,
                                    "wall_s": wall, "line": line}) + "\n")
            print(f"{stem}: rc {p.returncode} in {wall:.0f}s: {last[:2000]}",
                  flush=True)
            run_dir = os.path.join(REPO, ".bench_runs",
                                   f"{workload}-s{seed}-t{args.trace}")
            if "record" in args.keep and os.path.isdir(run_dir):
                for name in os.listdir(run_dir):
                    if name.endswith(".json"):
                        shutil.copy(os.path.join(run_dir, name),
                                    os.path.join(out, f"{stem}.{name}"))
            if "trace" in args.keep:
                for root, _, files in os.walk(os.path.join(run_dir, "trace")):
                    for name in files:
                        if name.endswith(".xplane.pb"):
                            shutil.copy(os.path.join(root, name),
                                        os.path.join(out, stem + ".xplane.pb"))
            if p.returncode or line is None:
                rc_all = 1
                continue
            for k, v in line["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        for k, v in values.items():
            med, spread = quartile_spread(v)
            print(f"  {workload} {k}: n={len(v)} median {med:.6g} "
                  f"spread(IQR/median) {spread:.4%} values "
                  f"{[round(x, 4) for x in v]}", flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
