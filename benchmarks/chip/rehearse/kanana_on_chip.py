#!/usr/bin/env python3
"""Once, on the chip, outside any window: the Kanana serving programs
against the plain reference at the published widths (the configuration's
depth), through the engine's own compiled programs.

A seeded prompt of `--prompt` tokens and `--decode` seeded tokens after it
(seeded, not greedy: a seeded model's greedy text settles on a few tokens
far above the rest, and a check is decided where the choice is close) go
through `LLMEngine._chunk_step` and `_step` three ways: prefilled in chunks
into the last slot and decoded a step each; the way the cell's check takes
what was served (`families/kanana.py`'s `engine_logits`: the prompt's whole
blocks prefilled and pooled, a pool hit copied into another slot, the rest
as a chunk, a decode step each), which has to give the same logits to the
bit; and as a window's steps are mixed, every `--chunk-every`-th decode
step taken by the chunk program with the lane as a chunk of one token (the
engine does that whenever another slot is prefilling), which rounds
elsewhere: how often its greedy choice is not the check's is what a sound
engine reads against the check's first limit. The logits are compared
with the reference's (float32, `highest`, plain attention, a layer at a
time). Then the reference is computed twice more with one part below what
the configuration states (the routed experts' weights through float8 e4m3;
the cached latents and rotary keys through it) and put through the cell's
two limits as if it had served and as if its logits were the engine's:
both have to be refused where the program passes.

    python benchmarks/chip/rehearse/kanana_on_chip.py [--seeds 1,2]

Writes `chiprun_out/kanana_on_chip.json`. One process, which holds the chip.
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
sys.path[:0] = [REPO, CHIP_DIR]

import numpy as np  # noqa: E402

from harness import spec  # noqa: E402

CONFIG = "kanana-2-30b-a3b-serve-1chip"


def through_the_last_slot(eng, prompt: list, forced: list,
                          chunk_every: int = 0):
    """Logits [len(forced), V]: `prompt` in chunks, then `forced[:-1]` a
    step each; every `chunk_every`-th of those by the chunk program."""
    B, C = eng.max_batch, eng.prefill_chunk_size
    slot = B - 1
    lanes = np.arange(B) == slot

    def chunk(text, pos):
        tokens = np.zeros((B, C), np.int32)
        tokens[slot, :len(text)] = text
        logits, eng.cache = eng._chunk_step(
            eng.params, eng.cache, tokens,
            np.where(lanes, pos, 0).astype(np.int32),
            np.where(lanes, len(text), 0).astype(np.int32), lanes)
        return logits

    for pos in range(0, len(prompt), C):
        logits = chunk(prompt[pos:pos + C], pos)
    rows = [np.asarray(logits[slot])]
    for j, token in enumerate(forced[:-1]):
        pos = len(prompt) + j
        if chunk_every and j % chunk_every == chunk_every - 1:
            logits = chunk([token], pos)
        else:
            tokens = np.zeros((B,), np.int32)
            tokens[slot] = token
            logits, eng.cache = eng._step(
                eng.params, eng.cache, tokens,
                np.where(lanes, pos, 0).astype(np.int32), lanes)
        rows.append(np.asarray(logits[slot]))
    return np.stack(rows)


def compare(config: dict, seed: int, n_prompt: int, n_decode: int,
            chunk_every: int) -> dict:
    family = spec.family(config["family"])
    model = config["model"]
    rng = np.random.default_rng([seed, 0x0C41])
    prompt = rng.integers(0, model["vocab_size"], n_prompt).tolist()
    forced = rng.integers(0, model["vocab_size"], n_decode).tolist()
    served = [{"prompt_ids": prompt, "token_ids": forced}]
    t0 = time.time()
    eng = family.stopped_engine(config, seed)
    plain = through_the_last_slot(eng, prompt, forced)
    mixed = through_the_last_slot(eng, prompt, forced, chunk_every)
    by_pool = family.engine_logits(eng, served)[0]
    counters = eng._device_counters()
    del eng
    gc.collect()
    t_served = time.time() - t0
    rows, at = family._rows_and_positions(served)
    layer_weights, ends = family.seeded_weights(config, seed)
    want = family.Reference(model, layer_weights, ends).logits(rows, at)[0]

    def choice(logits) -> list:
        return [{"prompt_ids": prompt,
                 "token_ids": logits.argmax(axis=-1).tolist()}]

    def as_if_served(logits) -> dict:
        """Its own choice at every position, against the check's engine."""
        return family.verdict(family.compare_served(
            choice(logits), [by_pool], [want]))

    out = {"seed": seed, "prompt_tokens": n_prompt, "decoded": n_decode,
           "served_s": round(t_served, 1), "counters": counters,
           "reference_logit_rms": float(np.sqrt(np.mean(want ** 2))),
           "reference_top1_minus_top2_median": float(np.median(
               want.max(axis=-1) - np.partition(want, -2, axis=-1)[:, -2])),
           "pool_route_against_plain_max_abs": float(
               np.abs(plain - by_pool).max()),
           # a sound engine, its steps mixed as a window's are
           "program": as_if_served(mixed),
           "mixed_against_pool_route_mean_abs": float(
               np.abs(mixed - by_pool).mean())}
    for degrade in family.DEGRADE[1:]:
        low = family.Reference(model, layer_weights, ends, degrade).logits(
            rows, at)[0]
        out[degrade] = {
            "as_if_served": as_if_served(low),
            "as_if_the_engines": family.verdict(family.compare_served(
                choice(low), [low], [want]))}
        del low
    out["total_s"] = round(time.time() - t0, 1)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--prompt", type=int, default=2500)
    ap.add_argument("--decode", type=int, default=256)
    ap.add_argument("--chunk-every", type=int, default=16,
                    help="the mixed route: this decode step in so many "
                         "goes through the chunk program")
    ap.add_argument("--tiny", action="store_true",
                    help="the CPU rehearsal's sizes (cpu_cell_kanana.py)")
    args = ap.parse_args()
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    if args.tiny:
        import cpu_cell_kanana

        cpu_cell_kanana.cut(config)
    results = []
    for seed in (int(s) for s in args.seeds.split(",")):
        results.append(compare(config, seed, args.prompt, args.decode,
                               args.chunk_every))
        print(json.dumps(results[-1]), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "kanana_on_chip.json"),
              "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
