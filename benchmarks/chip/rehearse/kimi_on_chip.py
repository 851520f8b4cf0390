#!/usr/bin/env python3
"""Once, on the chip, outside any window: the Kimi serving programs against
the plain reference at the published widths, the nine held layers with
their 64 held experts, through the engine's own compiled programs.

For each seed, `--rows` sequences shaped like the cell's (a document of
whole blocks, a question, then `--decode` seeded tokens: seeded, not greedy,
since a check is decided where the choice is close) go the way the cell's
check takes what was served (`families/kimi.py`'s `engine_logits`: the
whole blocks prefilled in chunks from a zeroed slot, rows and state pooled,
the snapshot and its row blocks copied into another slot, the rest as a
chunk, a decode step each through the delta-rule kernel). The logits at
the generated positions are compared with the reference's (float32,
`highest`, the recurrence a token at a time, a layer at a time). Then the
reference is computed again with one part below what the configuration
states (`bfloat16_state`, `float8_rows`) or another mathematics
(`scalar_decay`, `no_delta`) and put through the cell's second limit (the logits' mean
absolute distance from the reference's) as if its logits were the engine's:
it has to be refused where the program passes; the share of positions at
which it would choose another token than the reference is beside it. With
`--tiny` the same on the CPU at a tiny size.

    python benchmarks/chip/rehearse/kimi_on_chip.py [--seeds 1,2]

Writes `chiprun_out/kimi_on_chip.json`. One process, which holds the chip.
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

CONFIG = "kimi-linear-48b-a3b-serve-1chip"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--preamble", type=int, default=4096)
    ap.add_argument("--item", type=int, default=40)
    ap.add_argument("--decode", type=int, default=512)
    ap.add_argument("--degrade",
                    default="bfloat16_state,scalar_decay,no_delta,float8_rows")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    from families import kimi as family

    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    if args.tiny:
        import cpu_cell_kimi

        config["model"].update(cpu_cell_kimi.TINY_MODEL)
        config["deployment"].update(cpu_cell_kimi.TINY_DEPLOYMENT)
        args.preamble, args.item, args.decode = 48, 5, 12
    model = family.reference_model(config)
    out = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rng = np.random.default_rng([seed, 0x64])
        served = [{"prompt_ids": rng.integers(
            0, model["vocab_size"], args.preamble + args.item + i).tolist(),
            "token_ids": rng.integers(0, model["vocab_size"],
                                      args.decode).tolist()}
            for i in range(args.rows)]
        t0 = time.time()
        eng = family.stopped_engine(config, seed)
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
        readings = {"ok": readings["engine_logit_mean_abs"] <= limit,
                    "engine_logit_mean_abs":
                        readings["engine_logit_mean_abs"], "limit": limit}
        record = {"seed": seed, "pool": hits,
                  "logit_rms": float(np.std(np.concatenate(reference))),
                  "program": readings,
                  "choices_differ_share": float(np.mean(np.concatenate(
                      [a.argmax(-1) != b.argmax(-1)
                       for a, b in zip(engine, reference)]))),
                  "seconds": {"engine": round(t1 - t0, 1),
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
    with open(os.path.join(REPO, "chiprun_out", "kimi_on_chip.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    ok = all(r["program"]["ok"] and all(
        r[d]["refused"] for d in args.degrade.split(",") if d) for r in out)
    print("program passes and every degraded reference is refused:", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
