#!/usr/bin/env python3
"""What the engine thread's accounting costs a pass, on the host it runs
on: each clock reading, a phase boundary (`serve/llm.py` `_Phases.to`: two
readings, a profiler annotation closed and one opened, four dictionary
adds) and a pass's mark, each timed alone in a loop of a million.

    chiprun -- python benchmarks/engine_lap_cost.py [--repo .scratch/parent]

No device is touched: JAX is imported (so that `tracing.annotate` is the
profiler's annotation, as in a replica) and nothing runs on it. `--repo`
times another checkout's `ray_tpu` (the parent's `_Phase`, entered and left
as a context: two `perf_counter` readings and the same annotation).
A line of JSON: nanoseconds a call, and `pass_us`, what a pass that runs a
step pays at the tree's own count of boundaries.

Measured on the chip machine's host (PR 51): see PERF.md section 6.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

N = 1_000_000
# boundaries of a pass that dispatches a step and reads the one before it:
# calls, admit, plan, put, dispatch, sample, fetch, notify, release
BOUNDARIES = 9
PARENTS_PHASES = 8      # the same pass in a tree without `put`, `release`


def ns_a_call(fn) -> float:
    t0 = time.perf_counter()
    for _ in range(N):
        fn()
    return (time.perf_counter() - t0) * 1e9 / N


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))
    import jax  # noqa: F401  (tracing.annotate is null without it)

    from ray_tpu.serve import llm

    out = {"repo": args.repo, "n": N,
           "empty_loop_ns": ns_a_call(lambda: None),
           "perf_counter_ns": ns_a_call(time.perf_counter),
           "thread_time_ns": ns_a_call(time.thread_time),
           "time_ns": ns_a_call(time.time)}
    if hasattr(llm, "_Phases"):
        phases = llm._Phases()
        phases.start("release")
        out["boundary_ns"] = ns_a_call(lambda: phases.to("plan"))
        wall = phases.wall
        out["mark_ns"] = ns_a_call(lambda: tuple(wall.values()))
        out["pass_us"] = (BOUNDARIES * out["boundary_ns"]
                          + out["mark_ns"]) / 1e3
    else:
        phase = llm._Phase(dict.fromkeys(llm.ENGINE_PHASES, 0.0), "plan")

        def enter_and_leave():
            with phase:
                pass

        out["boundary_ns"] = ns_a_call(enter_and_leave)
        out["pass_us"] = PARENTS_PHASES * out["boundary_ns"] / 1e3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
