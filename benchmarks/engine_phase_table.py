#!/usr/bin/env python3
"""The engine thread's time over a run's window, from the run's record: a
row a phase in wall and CPU milliseconds a step (`serve/llm.py` `_Phases`,
`engine_stats()`'s `phase_s` and `phase_cpu_s` at the window's edges), what
the phases sum to beside the window, the CPU the replica's other threads
burnt, and the window's slow passes by name. Any run of a serving cell,
traced or not, keeps the record:

    python benchmarks/engine_phase_table.py .bench_runs/<cell>-s<seed>-t0/measure.json [...]

A record of a tree without `phase_cpu_s` prints its wall seconds alone.
"""

from __future__ import annotations

import json
import sys


def table(path: str) -> dict:
    with open(path) as f:
        record = json.load(f)
    record = record.get("record", record)
    c = record["counters"]
    before, after = c["before"], c["after"]
    window = c["after_at"] - c["before_at"]
    steps = after["engine_steps"] - before["engine_steps"]

    def grown(key):
        return {k: after[key][k] - before[key][k] for k in after[key]}

    wall = grown("phase_s")
    out = {"window_s": window, "steps": steps,
           "pass_ms": window * 1e3 / steps,
           "wall_ms_a_step": {k: v * 1e3 / steps for k, v in wall.items()},
           "phases_sum_s": sum(wall.values()),
           "unphased_pct": 100 * (window - sum(wall.values())) / window}
    if "phase_cpu_s" not in after:
        return out
    cpu = grown("phase_cpu_s")
    out["cpu_ms_a_step"] = {k: v * 1e3 / steps for k, v in cpu.items()}
    out["offcpu_ms_a_step"] = sum(
        wall[k] - cpu[k] for k in wall
        if k not in ("fetch", "empty")) * 1e3 / steps
    thread = after["cpu_s"]["engine_thread"] - before["cpu_s"]["engine_thread"]
    process = after["cpu_s"]["process"] - before["cpu_s"]["process"]
    out["cpu_s"] = {"engine_thread": thread, "other_threads": process - thread}
    slow = after["slow_passes"]
    out["slow_passes"] = {
        "count": slow["count"] - before["slow_passes"]["count"],
        "seconds": slow["seconds"] - before["slow_passes"]["seconds"],
        "in_window": [p for p in slow["newest"]
                      if p["step"] > before["engine_steps"]]}
    return out


def main() -> int:
    for path in sys.argv[1:]:
        t = table(path)
        print(f"{path}: window {t['window_s']:.3f} s, {t['steps']} steps, "
              f"{t['pass_ms']:.3f} ms a pass; phases sum "
              f"{t['phases_sum_s']:.3f} s ({t['unphased_pct']:+.3f}% of the "
              "window in no phase)")
        cpu = t.get("cpu_ms_a_step")
        for k, w in t["wall_ms_a_step"].items():
            print(f"  {k:9s} wall {w:8.4f}" + (
                f"  cpu {cpu[k]:8.4f}  off {w - cpu[k]:8.4f}" if cpu else ""))
        if cpu:
            print(f"  off the CPU outside fetch and empty: "
                  f"{t['offcpu_ms_a_step']:.4f} ms a step; CPU seconds: "
                  f"engine thread {t['cpu_s']['engine_thread']:.3f}, the "
                  f"process's other threads "
                  f"{t['cpu_s']['other_threads']:.3f}; slow passes "
                  f"{t['slow_passes']['count']} "
                  f"({t['slow_passes']['seconds']:.3f} s): "
                  f"{json.dumps(t['slow_passes']['in_window'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
