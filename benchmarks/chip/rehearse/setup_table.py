#!/usr/bin/env python3
"""Where one traced run's set-up went: a row of PERF.md's "Where set-up
goes", from the run's directory and the start-up record its processes left.

    python benchmarks/chip/rehearse/setup_table.py \
        .bench_runs/<workload>-s<seed>-t1 [more run directories]

Run it in the call that made the run: the record lives under the machine's
`/tmp`. It prints, for each run, one JSON object: the eight `setup_*`
readings, the harness's own stretches from the record's `marks`, the three
longest compiles, every program the cache did not hand over, the longest
stretches no span covers (with the marks they lie between), and each
process's stages. A run whose program kept no record prints nulls.
"""

from __future__ import annotations

import json
import os
import sys

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]

from harness import spec  # noqa: E402
from metrics import _startup  # noqa: E402

READINGS = ("setup_cluster_s", "setup_sched_s", "setup_worker_boot_s",
            "setup_compile_s", "setup_compile_missed",
            "setup_engine_build_s", "setup_train_build_s",
            "setup_unowned_pct", "worker_ready_s", "compile_cache_new",
            "setup_s")


def stretches(record: dict) -> dict:
    """The harness's own stages of set-up, from the record's marks."""
    m, t0 = record["marks"], record["window"]["t0"]
    out = {"before_init_s": m["init"] - record["t_start"],
           "cluster_s": m["cluster"] - m["init"]}
    if "replica_up" in m:
        out.update({
            "build_app_s": m["run"] - m["cluster"],
            "run_to_replica_up_s": m["replica_up"] - m["run"],
            "replica_chip_s": m["replica_chip"] - m["replica_start"],
            "replica_weights_s": m["replica_weights"] - m["replica_chip"],
            "replica_engine_s": m["replica_engine"] - m["replica_weights"],
            "warmup_requests_s": m["warm"] - m["replica_up"],
            "ramp_s": t0 - m["warm"]})
    else:
        out.update({
            "fit_to_loop_s": m["loop_start"] - m["fit"],
            "loop_to_weights_s": m["weights"] - m["loop_start"],
            "compile_step_s": m["compiled"] - m["weights"],
            "warmup_steps_s": m["warm"] - m["compiled"],
            "to_window_s": t0 - m["warm"]})
    return out


def gaps(record: dict, lo: float, hi: float, keep: int = 4) -> list:
    """The longest stretches of [lo, hi] under no start-up span, each with
    the marks on either side of its start."""
    out = _startup.tr.subtract([[lo, hi]], _startup.owned(record, lo, hi))
    marks = sorted((t, k) for k, t in record["marks"].items())

    def around(t):
        before = [k for at, k in marks if at <= t + 1e-3]
        after = [k for at, k in marks if at > t + 1e-3]
        return f"{before[-1] if before else '-'}..{after[0] if after else '-'}"

    out.sort(key=lambda g: g[0] - g[1])
    return [{"seconds": round(b - a, 3), "from_init_s": round(a - lo, 3),
             "between_marks": around(a)} for a, b in out[:keep]]


def row(workdir: str) -> dict:
    record = spec.load_json(os.path.join(workdir, "measure.json"))["record"]
    # an untraced run's processes keep the record too
    record["trace_dir"] = record.get("trace_dir") or os.path.join(
        workdir, "trace")
    out = {"run": os.path.basename(workdir.rstrip("/")), "readings": {}}
    for name in READINGS:
        try:
            out["readings"][name] = spec.metric_reader(name).read(record)
        except (KeyError, TypeError, ZeroDivisionError):
            out["readings"][name] = None
    out["harness"] = {k: round(v, 3) for k, v in stretches(record).items()}
    found = _startup.compiles(record)
    out["longest_compiles"] = [
        {"fun": s["name"][len("compile."):],
         "seconds": round(_startup.seconds(s), 3),
         "cache": s["attributes"].get("cache")}
        for s in sorted(found, key=_startup.seconds, reverse=True)[:3]]
    out["not_from_cache"] = [
        {"fun": s["name"][len("compile."):],
         "seconds": round(_startup.seconds(s), 3),
         "cache": s["attributes"].get("cache")}
        for s in found if s["attributes"].get("cache") != "hit"]
    marks = record["marks"]
    end = marks.get("replica_up", marks.get("compiled"))
    out["unowned"] = gaps(record, marks["init"], end)
    out["stages"] = [
        {"name": s["name"], "role": s["attributes"].get("role"),
         "pid": _startup.pid_of(s),
         "from_init_s": round(s["start_ts"] - marks["init"], 3),
         "seconds": round(_startup.seconds(s), 3)}
        for s in sorted(_startup.spans(record), key=lambda s: s["start_ts"])
        if not s["name"].startswith("compile.")]
    return out


def main() -> int:
    if len(sys.argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    for workdir in sys.argv[1:]:
        print(json.dumps(row(workdir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
