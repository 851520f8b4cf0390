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
        [--parent .scratch/parent] [--slots 0,1,2,4,8,32] [--forms reused,sliced]

Forms:
  decode   `deepseek.decode_step`: what every slot's first lane costs
  parent   `prefill_chunk` of a checkout of the parent commit (`--parent`;
           left out when the directory is not there): all 32 x 128 lanes
           through every layer, whoever prefills
  reused   `deepseek.prefill_chunk`: the decode program's work on every
           slot's first lane, the further lanes only of the slots that
           prefill, a slot at a time, with the layer's weights as the
           layers' loop already holds them
  sliced   the same but for one thing: a slot's turn of the loop slices
           the layer's weights out of the stack itself (granite and kimi do
           so: there slicing once made the compiler copy every matrix;
           `lm.each_slot` has the rule)

Measured (TPU v5 lite, one chip, PR 39; ms a step, calls dispatched back to
back, every slot at position 2,560):

    slots that prefill      0      1      2      4      8      32
    decode              41.15
    parent                     309.82                           309.80
    reused              43.53  59.40  75.26 106.86 169.78  548.56
    sliced              41.96  83.70 125.41 208.77 375.20 1374.63

`reused` is kept: a slot that prefills costs 15.8 ms with the weights the
layers' loop already copied out of the stack (ROADMAP S12a) and 41.7 ms when
its branch slices them again (the three copies a layer, once more for every
slot). A step in which no slot prefills costs the decode program's time and
2.4 ms of predicates. The all-lanes form's 310 ms is passed at 17 slots
prefilling at once: the engine's default budget (`max_num_batched_tokens`
B + C) hands out at most two; a budget that lets 17 or more slots prefill
in one step (about 17 C = 2,176 tokens here) buys steps that cost more
than they did before PR 39, up to 549 ms where all 32 do.

Since PR 43 the loop over the slots is `models/lm.each_slot`: as many turns
as slots prefill, where the loop this table timed turned over all 32 with a
branch each.
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
    layer l of `stacks` (the engine's `dense` and `blocks`), sliced inside
    the loop's body."""
    n_dense = jax.tree.leaves(stacks["dense"])[0].shape[0]

    def further_lanes(rest, bp, cfg, lat, kr, given, l, pos, ok, prefilling):
        M = rest.shape[1]
        stack, at = ((stacks["blocks"], l - n_dense) if "moe" in bp
                     else (stacks["dense"], l))

        def slot(b, carry):
            rest, lat, kr, given = carry
            own = lm.layer_weights(stack, at)
            xb, okb, first = lm.slot_lanes(b, rest, ok, pos)
            xb, lat, kr = deepseek._attention(
                xb, own, cfg, lat, kr, l, first,
                first[:, None] + jnp.arange(M), okb, slot=b)
            xb, given = deepseek._mlp(xb, own, cfg, given, okb)
            return lm.put_lanes(rest, xb, b), lat, kr, given

        return lm.each_slot(prefilling, slot, (rest, lat, kr, given))

    return further_lanes


def forms(cfg, parent: str) -> dict:
    """name -> the chunk program, jitted as the engine jits it."""
    def chunk(module):
        return jax.jit(
            lambda p, c, t, pos0, n, a: module.prefill_chunk(
                p, c, t, pos0, n, a, cfg), donate_argnums=(1,))

    def sliced(p, c, t, pos0, n, a):
        with mock.patch.object(deepseek, "_further_lanes",
                               further_lanes_sliced(p)):
            return deepseek.prefill_chunk(p, c, t, pos0, n, a, cfg)

    out = {"reused": chunk(deepseek),
           "sliced": jax.jit(sliced, donate_argnums=(1,))}
    path = os.path.join(parent, "ray_tpu", "models", "deepseek.py")
    if os.path.isfile(path):
        spec = importlib.util.spec_from_file_location("parent_deepseek", path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = module       # its dataclass looks itself up
        spec.loader.exec_module(module)
        out["parent"] = chunk(module)
    return out


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

    def record(form, prefilling, fn, step_args):
        nonlocal cache
        row = {"form": form, "prefilling_slots": prefilling}
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

    decode = jax.jit(lambda p, c, t, pos, a: deepseek.decode_step(
        p, c, t, pos, a, cfg), donate_argnums=(1,))
    record("decode", 0, decode, (tokens[:, 0], pos0, on))
    wanted = [f for f in args.forms.split(",") if f]
    for form, fn in forms(cfg, args.parent).items():
        if wanted and form not in wanted:
            continue
        for n in (int(s) for s in args.slots.split(",")):
            if form == "parent" and n not in (1, B):
                continue                  # every lane, whoever prefills
            length = jnp.where(jnp.arange(B) < n, C, 1).astype(jnp.int32)
            record(form, n, fn, (tokens, pos0, length, on))
    lines = ["| form | slots that prefill | ms a step |", "| --- | --- | --- |"]
    lines += [f"| {r['form']} | {r['prefilling_slots']} | "
              + (f"{r['ms']:.2f} |" if "ms" in r
                 else f"refused: {r['refused'][:80]} |") for r in rows]
    with open(os.path.join(out_dir, "KANANA_CHUNK_LANES.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
