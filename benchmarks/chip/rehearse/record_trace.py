#!/usr/bin/env python3
"""Records the small traces the reduction is tested on, and works out what
the reduction must find in them by another method.

On the chip (one process, all the chips it finds, mesh fsdp=<chips>): a
small GPT-2 train step through the family's `build_train`, a few steps
under the profiler, written to `chiprun_out/<name>.xplane.pb`:

    python benchmarks/chip/rehearse/record_trace.py record one_chip

Anywhere: the expected numbers of a recording, by painting a nanosecond
grid (every operation paints its span, the deepest last, so each
nanosecond has one owner) and counting, not by interval arithmetic:

    python benchmarks/chip/rehearse/record_trace.py expect \
        benchmarks/chip/testdata/one_chip.xplane.pb
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]

MODEL = {"vocab_size": 2000, "padded_vocab_size": 2048, "n_positions": 256,
         "n_embd": 256, "n_layer": 2, "n_head": 4}
STEPS = 3


def record(name: str) -> None:
    import jax
    import numpy as np

    from harness import spec

    devices = jax.devices()
    assert devices[0].platform == "tpu", devices
    job = {"seq_len": 256, "global_batch": 8, "remat": "full",
           "mesh": {"fsdp": len(devices)}, "total_steps": 100}
    prog = spec.family("gpt2").build_train(MODEL, job, devices, 0)
    state = prog.init_state()
    step = prog.compile_step(state)
    tokens = np.random.default_rng(0).integers(
        0, 2000, (8, 257), dtype=np.int32)
    batch = prog.put_batch(tokens)
    for _ in range(2):
        state, metrics = step(state, batch)
    jax.block_until_ready(state)
    out = os.path.join(REPO, "chiprun_out", "record_" + name)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    for _ in range(STEPS):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = step(state, batch)
        float(metrics["loss"])
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    dest = os.path.join(REPO, "chiprun_out", name + ".xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(out)
    print(f"recorded {dest}: {os.path.getsize(dest):,} bytes, "
          f"{len(devices)} device(s)")


def expect(path: str) -> None:
    import numpy as np
    from jax.profiler import ProfileData

    import trace_reduce as tr

    data = ProfileData.from_file(path)
    planes = {p.name: p for p in data.planes
              if tr.DEVICE_PLANE.match(p.name)}
    lines = {name: {ln.name: [(int(e.start_ns), int(e.start_ns
                                                    + e.duration_ns),
                               e.name) for e in ln.events]
                    for ln in p.lines} for name, p in planes.items()}
    ops = {n: l[tr.OPS_LINE] for n, l in lines.items()
           if l.get(tr.OPS_LINE)}
    t0 = min(s for ev in ops.values() for s, _, _ in ev)
    t1 = max(e for ev in ops.values() for _, e, _ in ev)
    n = t1 - t0
    busy_s, coll_s, exposed_s = [], [], []
    for name, events in sorted(ops.items()):
        owner = np.full(n, -1, np.int32)       # the deepest op at each ns
        order = sorted(events, key=lambda ev: (ev[0], -ev[1]))
        is_coll = np.zeros(len(order) + 1, bool)
        for i, (s, e, op) in enumerate(order):
            owner[s - t0:e - t0] = i
            is_coll[i] = bool(tr.COLLECTIVE.search(op))
        owned = owner >= 0
        coll = np.zeros(n, bool)
        coll[owned] = is_coll[owner[owned]]
        other = owned & ~coll
        for s, e, op in lines[name].get(tr.ASYNC_LINE, []):
            if tr.COLLECTIVE.search(op):
                coll[max(s - t0, 0):e - t0] = True
        busy_s.append(int(owned.sum()) / 1e9)
        coll_s.append(int(coll.sum()) / 1e9)
        exposed_s.append(int((coll & ~other).sum()) / 1e9)
    modules = sorted({re.sub(r"\(.*\)$", "", m) for l in lines.values()
                      for _, _, m in l.get(tr.MODULES_LINE, [])})
    want = {"devices": len(ops), "window_s": n / 1e9,
            "busy_s": sum(busy_s) / len(busy_s),
            "idle_worst_s": n / 1e9 - min(busy_s),
            "collective_s": max(coll_s),
            "collective_exposed_s": max(exposed_s), "modules": modules}
    dest = path.replace(".xplane.pb", ".expected.json")
    with open(dest, "w") as f:
        json.dump(want, f, indent=1)
    print(json.dumps(want))


if __name__ == "__main__":
    {"record": record, "expect": expect}[sys.argv[1]](sys.argv[2])
