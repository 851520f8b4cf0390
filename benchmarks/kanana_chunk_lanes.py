#!/usr/bin/env python3
"""On the chip: `models/deepseek.py`'s chunk program at the shapes of the
cell `serve-kanana-docqa` (Kanana-2-30B-A3B's widths, 1 + 7 layers, 32 slots
of 4,096 positions, chunks of 128, every slot at position 2,560), by how many
slots prefill in the step: the others ride along with one lane each, as a
decode lane does. PR 39's form of the program and what `max_num_batched_
tokens` may be set to are read off this table (PERF.md §6, PR 39). Refuses
to run without a TPU, prints one JSON line a measurement and writes
chiprun_out/KANANA_CHUNK_LANES.{json,md} (a run's output, never committed).

    chiprun -- python benchmarks/kanana_chunk_lanes.py
        [--parent .scratch/parent] [--slots 0,1,2,4,8,32] [--tokens 128,32]
        [--forms reused,sliced]

Forms:
  decode   `deepseek.decode_step`: what every slot's first lane costs
  reused   `deepseek.prefill_chunk`: the decode program's work on every
           slot's first lane, the further lanes only of the slots that
           prefill, a slot at a time, with the layer's weights as the
           layers' scan holds them and the routed experts' matrices where
           the stack of all layers' holds them
  sliced   the same but for one thing: a slot's turn of the loop slices
           the layer's weights (all but the routed experts') out of the
           stack itself (granite and kimi do so: there slicing once made
           the compiler copy every matrix; `lm.each_slot` has the rule)
  parent_decode, parent   the two programs of a checkout of the parent
           commit (`--parent`; left out when the directory is not there)

Measured (TPU v5 lite, one chip, PR 48, the parent PR 47's tree; ms a step,
calls dispatched back to back, every slot at position 2,560):

    slots that prefill      0      1      2      4      8      32
    decode              13.94
    reused              15.67  31.61  47.52  79.33 142.63  523.66
    sliced              16.18  32.03  47.86  79.51 142.40  521.25
    parent_decode       39.64
    parent              41.49  57.29  73.02 104.47 167.11  544.17

A step in which no slot prefills costs the decode program's time and 1.7 ms
of predicates, a slot that prefills 15.9 ms, as on the parent: what PR 48
took out is the 25.7 ms a step that copied each expert layer's three
matrices out of the stack, once a step whoever prefilled. `reused` and
`sliced` no longer differ (until PR 48 a turn that sliced the layer again
copied its three expert matrices again, 41.7 ms a slot for 15.8, PR 39):
the scan's form stays because it is the shorter. The all-lanes form of PR 38
(310 ms a step whoever prefilled) is passed where 19 slots prefill at once:
the engine's default budget (`max_num_batched_tokens` B + C) hands out at
most two; a budget that lets 19 or more slots prefill in one step (about
19 C = 2,432 tokens here) buys steps that cost more than all lanes at once
did, up to 524 ms where all 32 do.

Since PR 60 `reused` is the packed form: a layer's MLP (experts, shared
experts, the dense layer's SwiGLU) takes the first lanes and the valid
further lanes of the slots that prefill as the rows of one call of
max(2 B, B + C) = 160 rows (`lm.all_lanes`), and only attention goes a slot
at a time; a slot whose lanes no longer fit the 128 rows behind the first
lanes is a round of its own. Measured (TPU v5 lite, one chip, PR 60, the
parent PR 59's tree in the same call; `--tokens 128,32`: what each
prefilling slot holds):

    slots that prefill            0      1      2      4
    decode                    13.94
    reused, 128 tokens each   16.05  22.17  38.50  71.09
    reused, 32 tokens each    16.05  21.15  24.94  31.81
    parent, 128 or 32 each    15.67  31.60  47.52  79.32   (PR 48's `reused`)

One slot rides in the first lanes' call for 6.1 ms at 128 tokens and 5.1 at
32 (its attention over 128 padded lanes either way) where it cost 15.9; two
slots of
128 tokens are 254 further lanes for 128 rows, so the second is a round (a
pass of the experts, 16.3 ms); four slots of 32 tokens, 124 lanes, are one
round at 3.4-5 ms a slot. No slot prefilling, the call's 160 rows cost 0.4
ms over the parent's 32.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from unittest import mock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
sys.path[:0] = [REPO, CHIP_DIR]

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import deepseek, lm

CONFIG = "kanana-2-30b-a3b-serve-1chip"
POSITION = 2560          # the cell's slots decode at 2,100-3,650


def further_lanes_sliced(stacks: dict):
    """`deepseek._further_lanes` but for where a slot's weights come from:
    layer l of `stacks` (the engine's `dense` and `blocks`, the latter
    without the routed experts' matrices, which are `stack`'s in both
    forms), sliced inside the loop's body. Since PR 60 the loop is the
    layer's attention alone."""
    n_dense = jax.tree.leaves(stacks["dense"])[0].shape[0]
    moe = stacks["blocks"]["moe"]
    blocks = {**stacks["blocks"],
              "moe": {k: moe[k] for k in moe if k not in deepseek.ROUTED}}

    def further_lanes(rest, bp, cfg, lat, kr, l, pos, ok, prefilling):
        M = rest.shape[1]
        layers, at = ((blocks, l - n_dense) if "moe" in bp
                      else (stacks["dense"], l))

        def slot(b, carry):
            rest, lat, kr = carry
            own = lm.layer_weights(layers, at)
            xb, okb, first = lm.slot_lanes(b, rest, ok, pos)
            xb, lat, kr = deepseek._attention(
                xb, own, cfg, lat, kr, l, first,
                first[:, None] + jnp.arange(M), okb, slot=b)
            return lm.put_lanes(rest, xb, b), lat, kr

        return lm.each_slot(prefilling, slot, (rest, lat, kr))

    return further_lanes


def forms(cfg, parent: str) -> tuple:
    """(name -> the decode program, name -> the chunk program), jitted as
    the engine jits them."""
    def decode(module):
        return jax.jit(lambda p, c, t, pos, a: module.decode_step(
            p, c, t, pos, a, cfg), donate_argnums=(1,))

    def chunk(module):
        return jax.jit(
            lambda p, c, t, pos0, n, a: module.prefill_chunk(
                p, c, t, pos0, n, a, cfg), donate_argnums=(1,))

    def sliced(p, c, t, pos0, n, a):
        with mock.patch.object(deepseek, "_further_lanes",
                               further_lanes_sliced(p)):
            return deepseek.prefill_chunk(p, c, t, pos0, n, a, cfg)

    decodes = {"decode": decode(deepseek)}
    chunks = {"reused": chunk(deepseek),
              "sliced": jax.jit(sliced, donate_argnums=(1,))}
    path = os.path.join(parent, "ray_tpu", "models", "deepseek.py")
    if os.path.isfile(path):
        spec = importlib.util.spec_from_file_location("parent_deepseek", path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = module       # its dataclass looks itself up
        spec.loader.exec_module(module)
        decodes["parent_decode"] = decode(module)
        chunks["parent"] = chunk(module)
    return decodes, chunks


def timed_ms(step, cache, args, seconds: float = 2.0):
    """(ms a call, the cache): calls dispatched back to back, the cache
    handed from one to the next as the engine hands it, one wait at the
    end; the median of three such loops."""
    for _ in range(2):                          # compiles, then settles
        logits, cache = step(cache, *args)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    logits, cache = step(cache, *args)
    jax.block_until_ready(logits)
    n = max(3, min(50, int(seconds / (time.perf_counter() - t0))))
    loops = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            logits, cache = step(cache, *args)
        jax.block_until_ready(logits)
        loops.append((time.perf_counter() - t0) / n * 1e3)
    return sorted(loops)[1], cache


def main() -> None:
    from harness import spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(REPO, ".scratch",
                                                     "parent"))
    ap.add_argument("--slots", default="0,1,2,4,8,32")
    ap.add_argument("--tokens", default="128",
                    help="tokens a slot that prefills has, comma-separated")
    ap.add_argument("--forms", default="",
                    help="only these forms, comma-separated")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"kanana_chunk_lanes.py measures the TPU and found platform "
                 f"{device.platform!r}: no number is produced")
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    cfg = spec.family(config["family"]).program_config(config)
    d = config["deployment"]
    B, T, C = d["max_batch"], d["max_seq_len"], d["prefill_chunk_size"]
    params = deepseek.init_params(jax.random.key(0), cfg)
    cache = deepseek.init_cache(cfg, B, T)
    pos0 = jnp.full((B,), POSITION, jnp.int32)
    on = jnp.ones((B,), bool)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, C)), jnp.int32)
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []

    def record(form, prefilling, fn, step_args, chunk_tokens=1):
        nonlocal cache
        row = {"form": form, "prefilling_slots": prefilling,
               "chunk_tokens": chunk_tokens}
        try:
            row["ms"], cache = timed_ms(
                lambda c, *a: fn(params, c, *a), cache, step_args)
        except Exception as e:  # noqa: BLE001 - a refusal is a result
            row["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
            cache = deepseek.init_cache(cfg, B, T)      # it was donated
        rows.append(row)
        print(json.dumps(row), flush=True)
        with open(os.path.join(out_dir, "KANANA_CHUNK_LANES.json"),
                  "w") as f:
            json.dump({"device": {"platform": device.platform,
                                  "kind": device.device_kind,
                                  "count": len(jax.devices())},
                       "shapes": {"slots": B, "positions": T, "chunk": C,
                                  "at": POSITION, "layers": cfg.n_layer},
                       "rows": rows}, f, indent=1)

    wanted = [f for f in args.forms.split(",") if f]
    decodes, chunks = forms(cfg, args.parent)
    for form, fn in decodes.items():
        record(form, 0, fn, (tokens[:, 0], pos0, on))
    for form, fn in chunks.items():
        if wanted and form not in wanted:
            continue
        for many in (int(s) for s in args.tokens.split(",")):
            for n in (int(s) for s in args.slots.split(",")):
                length = jnp.where(jnp.arange(B) < n, many, 1).astype(
                    jnp.int32)
                record(form, n, fn, (tokens, pos0, length, on), many)
    lines = ["| form | slots that prefill | tokens each | ms a step |",
             "| --- | --- | --- | --- |"]
    lines += [f"| {r['form']} | {r['prefilling_slots']} | "
              f"{r['chunk_tokens']} | "
              + (f"{r['ms']:.2f} |" if "ms" in r
                 else f"refused: {r['refused'][:80]} |") for r in rows]
    with open(os.path.join(out_dir, "KANANA_CHUNK_LANES.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
