#!/usr/bin/env python3
"""Once, on the chip, outside any window: the Keye serving programs against
the plain reference at the published widths and the cell's sizes, the six
held layers with all their experts, through the engine's own compiled
programs.

For each seed, `--rows` sequences shaped like the cell's (a document of
whole blocks between `--shortest` and `--longest`, a question, then
`--decode` seeded tokens: seeded, not greedy, since a check is decided
where the choice is close) go the way the cell's check takes what was
served (`families/keye.py`'s `engine_logits`: the whole blocks prefilled in
chunks, the three leaves' rows pooled, a pool hit copied into another slot,
the question as a chunk, a decode step each, all rows live at once). The
logits at the generated positions are compared with the reference's
(float32, `highest`, the selection a mask from a stable sort, a layer at a
time). Then the reference is computed again with another mathematics
(`dense_attend`, `window`, `half_topk`) or one part below what the
configuration states (`bfloat16_scores`, `float8_rows`) and put through the
cell's second limit (the logits' mean absolute distance from the
reference's) as if its logits were the engine's: it has to be refused where
the program passes; the share of positions at which it would choose another
token than the reference is beside it. With `--tiny` the same on the CPU at
a tiny size.

    python benchmarks/chip/rehearse/keye_on_chip.py [--seeds 1,2,3]

Writes `chiprun_out/keye_on_chip.json`. One process, which holds the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR, os.path.join(CHIP_DIR, "rehearse")]

import numpy as np  # noqa: E402

from harness import spec  # noqa: E402

CONFIG = "keye-vl-2.0-30b-a3b-serve-1chip"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--shortest", type=int, default=8192)
    ap.add_argument("--longest", type=int, default=12288)
    ap.add_argument("--question", type=int, default=40)
    ap.add_argument("--decode", type=int, default=384)
    ap.add_argument("--degrade", default="dense_attend,window,half_topk,"
                                         "bfloat16_scores,float8_rows")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    from families import keye as family

    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    block = config["deployment"]["kv_block_size"]
    if args.tiny:
        import cpu_cell_keye

        config["model"].update(cpu_cell_keye.TINY_MODEL)
        config["deployment"].update(cpu_cell_keye.TINY_DEPLOYMENT)
        block = config["deployment"]["kv_block_size"]
        args.shortest, args.longest, args.question, args.decode = 48, 80, 5, 12
    model = config["model"]
    lengths = [int(n) // block * block for n in np.linspace(
        args.shortest, args.longest, args.rows)]
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rng = np.random.default_rng([seed, 0x6B])
        served = [{"prompt_ids": rng.integers(
            0, model["vocab_size"], n + args.question + i).tolist(),
            "token_ids": rng.integers(0, model["vocab_size"],
                                      args.decode).tolist()}
            for i, n in enumerate(lengths)]
        t0 = time.time()
        eng = family.stopped_engine(config, seed)
        t_built = time.time()
        engine = family.engine_logits(eng, served)
        hits = eng.kv.stats()
        del eng
        gc.collect()
        t1 = time.time()
        layer_weights, ends = family.seeded_weights(config, seed)
        rows, at = family._rows_and_positions(served)
        reference = family.Reference(model, layer_weights, ends).logits(rows,
                                                                        at)
        t2 = time.time()
        # the tokens are seeded, not the engine's choices: only the second
        # of the cell's two limits is read here
        limit = family.ENGINE_LOGIT_MEAN_ABS_LIMIT
        readings = family.compare_served(served, engine, reference)
        record = {"seed": seed, "documents": lengths, "pool": hits,
                  "logit_rms": float(np.std(np.concatenate(reference))),
                  "program": {
                      "ok": readings["engine_logit_mean_abs"] <= limit,
                      "engine_logit_mean_abs":
                          readings["engine_logit_mean_abs"],
                      "by_row": [float(np.abs(a - b).mean())
                                 for a, b in zip(engine, reference)],
                      "limit": limit},
                  "choices_differ_share": float(np.mean(np.concatenate(
                      [a.argmax(-1) != b.argmax(-1)
                       for a, b in zip(engine, reference)]))),
                  "seconds": {"engine_build": round(t_built - t0, 1),
                              "engine": round(t1 - t_built, 1),
                              "reference": round(t2 - t1, 1)}}
        print(json.dumps(record), flush=True)
        for degrade in [d for d in args.degrade.split(",") if d]:
            t3 = time.time()
            off = family.Reference(model, layer_weights, ends,
                                   degrade).logits(rows, at)
            as_engine = family.compare_served(served, off, reference)
            record[degrade] = {
                "refused": as_engine["engine_logit_mean_abs"] > limit,
                "logit_mean_abs_from_reference":
                    as_engine["engine_logit_mean_abs"],
                "logit_mean_abs_from_program": float(np.mean(
                    [np.abs(a - b).mean() for a, b in zip(off, engine)])),
                "choices_differ_share": float(np.mean(np.concatenate(
                    [a.argmax(-1) != b.argmax(-1)
                     for a, b in zip(off, reference)]))),
                "seconds": round(time.time() - t3, 1)}
            print(json.dumps({degrade: record[degrade]}), flush=True)
        out.append(record)
        del layer_weights, ends
        gc.collect()
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out", "keye_on_chip.json"),
                  "w") as f:
            json.dump(out, f, indent=1)
    ok = all(r["program"]["ok"] and all(
        r[d]["refused"] for d in args.degrade.split(",") if d) for r in out)
    print("program passes and every degraded reference is refused:", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
