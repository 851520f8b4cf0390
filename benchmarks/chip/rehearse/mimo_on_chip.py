#!/usr/bin/env python3
"""Once, on the chip, outside any window: the MiMo serving programs against
the plain reference at the published widths, the seven held layers (G S S S
S G S) with their 8 held experts, through the engine's own compiled programs.

For each seed and at each end of the cell's documents (`--contexts`, 1,024
and 23,296), `--rows` sequences shaped like the cell's (a document of whole
blocks, a question, then `--decode` seeded tokens: seeded, not greedy, since
a check is decided where the choice is close) go the way the cell's check
takes what was served (`families/granite.py`'s `engine_logits`: the whole
blocks prefilled in chunks through the chunk program, the global layers'
rows pooled by the block and the sliding layers' rings as a snapshot, found
again and copied into another slot, so that a ring restored from its
snapshot is in the comparison; the rest as a chunk, a decode step each
through `ops/gqa_attend.py` over rows and rings and `ops/expert_mlp.py`).
The logits at the generated positions are compared with the reference's
(float32, `highest`, k and v through bfloat16 as the configuration states
them, attention over the whole sequence under a banded or a causal mask, the
sink a column, a layer at a time). Then the reference is computed again with
one part below what the configuration states (`bfloat16_stream`,
`one_piece`) or another mathematics (`no_sink`, `sink_weighs_value`,
`window_127`, `window_129`, `rotate_all_lanes`, `thetas_swapped`,
`no_value_scale`, `global_8_kv_heads`, `gates_not_renormalised`) and put
through the cell's two limits on the logits' distance from the reference's
(its floor and its mean over the generated positions) as if its logits were
the engine's: it has to be refused where the program passes, and by how many
times each limit is beside it, as is the share of positions at which it
would choose another token than the reference. Every degradation for the
first seed, `--degrade-rest` for the seeds after it. With `--served` the
sequences are a run of the cell's own sampled replies (its `served.json`):
the cell's comparison at the cell's load, and each degradation through the
same. With `--tiny` the same on the CPU at a tiny size. MiMo has no norm a
head: `choices_distinct_share` (how many distinct tokens the engine's logits
choose over a row's positions) is where PERF.md PR 59's collapse would show.

    python benchmarks/chip/rehearse/mimo_on_chip.py [--seeds 1,2,3]
    python benchmarks/chip/rehearse/mimo_on_chip.py --seeds 6200201 \
        --served .bench_runs/serve-mimo-mixedqueue-s6200201-t0/served.json

Writes `chiprun_out/mimo_on_chip.json`. One process, which holds the chip.
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

CONFIG = "mimo-v2.5-serve-1chip"


def sequences(args, model: dict, seed: int) -> list:
    """[(what they are, served)]: a cell's own sampled replies (`--served`),
    or at each of `--contexts` `--rows` seeded sequences shaped like the
    cell's (seeded tokens, not greedy ones: a check is decided where the
    choice is close)."""
    if args.served:
        return [(os.path.basename(os.path.dirname(args.served)),
                 spec.load_json(args.served))]
    out = []
    for context in args.contexts:
        rng = np.random.default_rng([seed, context, 0x64])
        out.append((context, [
            {"prompt_ids": rng.integers(0, model["vocab_size"],
                                        context + args.item + i).tolist(),
             "token_ids": rng.integers(0, model["vocab_size"],
                                       args.decode).tolist()}
            for i in range(args.rows)]))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rows", type=int, default=2)
    ap.add_argument("--contexts", default="1024,23296",
                    help="both ends of the cell's documents")
    ap.add_argument("--item", type=int, default=64)
    ap.add_argument("--decode", type=int, default=384)
    ap.add_argument("--degrade",
                    default="bfloat16_stream,one_piece,no_sink,"
                    "sink_weighs_value,window_127,window_129,"
                    "rotate_all_lanes,thetas_swapped,no_value_scale,"
                    "global_8_kv_heads,gates_not_renormalised")
    ap.add_argument("--degrade-rest", default="bfloat16_stream,one_piece",
                    help="the degradations of the seeds after the first")
    ap.add_argument("--served", default="",
                    help="a run of the cell's served.json (.bench_runs/"
                         "<cell>-s<seed>-t<0|1>/) with --seeds <seed>: the "
                         "cell's own replies through its comparison, and "
                         "each degradation through the same")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    from families import mimo as family

    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    args.contexts = [int(c) for c in args.contexts.split(",")]
    if args.tiny:
        import cpu_cell_mimo

        config["model"].update(cpu_cell_mimo.TINY_MODEL)
        config["deployment"].update(cpu_cell_mimo.TINY_DEPLOYMENT)
        args.contexts, args.item, args.decode = [32, 64], 5, 12
    model = family.reference_model(config)
    first, rest = ([d for d in text.split(",") if d]
                   for text in (args.degrade, args.degrade_rest))
    # seeded tokens are not the engine's choices: only the two limits on the
    # logits' distance are read of them
    limits = {k: v for k, v in family.LIMITS.items()
              if k.startswith("engine_logit")}

    def distances(logits, reference):
        got = family.compare(served, logits, reference)
        return {k: got[k] for k in limits}
    out = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        degrades = rest if n else first
        for what, served in sequences(args, model, seed):
            t0 = time.time()
            eng = family.stopped_engine(config, seed)
            engine = family.engine_logits(eng, served)
            hits = eng.kv.stats()
            del eng
            gc.collect()
            t1 = time.time()
            layer_weights, ends = family.seeded_weights(config, seed)
            rows, at = family._rows_and_positions(served)
            reference = family.Reference(model, layer_weights,
                                         ends).logits(rows, at)
            t2 = time.time()
            apart = distances(engine, reference)
            record = {"seed": seed, "sequences": what, "pool": hits,
                      "logit_rms": float(np.std(np.concatenate(reference))),
                      "program": {"ok": all(apart[k] <= limits[k]
                                            for k in limits),
                                  **apart, "limits": limits},
                      "choices_differ_share": float(np.mean(np.concatenate(
                          [a.argmax(-1) != b.argmax(-1)
                           for a, b in zip(engine, reference)]))),
                      # a head that repeats one token would choose few
                      "choices_distinct_share": float(np.mean(
                          [len(set(a.argmax(-1).tolist())) / len(a)
                           for a in engine])),
                      "seconds": {"engine": round(t1 - t0, 1),
                                  "reference": round(t2 - t1, 1)}}
            print(json.dumps(record), flush=True)
            for degrade in degrades:
                t3 = time.time()
                off = family.Reference(model, layer_weights, ends,
                                       degrade).logits(rows, at)
                apart = distances(off, reference)
                record[degrade] = {
                    "refused": any(apart[k] > limits[k] for k in limits),
                    **apart,
                    "times_the_limit": {k: apart[k] / limits[k]
                                        for k in limits},
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
    with open(os.path.join(REPO, "chiprun_out", "mimo_on_chip.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    ok = all(r["program"]["ok"] for r in out)
    print("the program passes:", ok, "; refused:",
          {d: [r[d]["refused"] for r in out if d in r] for d in first})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
