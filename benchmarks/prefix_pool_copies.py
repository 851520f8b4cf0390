#!/usr/bin/env python3
"""Once, on the chip: what a prefix's way between pool and slot costs the
host and the device, at the three rows-only pools' sizes, from their
configuration files (`serve/kv_cache.py`: `store_prefix`, `copy_into_slot`).

    chiprun -- python benchmarks/prefix_pool_copies.py [--repo .scratch/parent]

A pool and a cache of random rows as the cell's engine builds them
(`PagedKVCache.for_cache`); a prefix of n blocks stored from slot 0, found
again and copied into slot 1, for each n; the rows compared on the device.
A line a geometry and n: `host_ms` until the call returns (the engine's
thread is held that long), `ready_ms` until the device has done it, the
pool's own count of program calls, and how the device lays the pool out
beside the cache (`major_to_minor`). `--layers 1 --slots 2` rehearses on
the CPU. `--repo` runs another checkout's `ray_tpu` (the parent's: one
program call a block a leaf there).

Measured on a v5e (PR 47; ms, this tree / the parent's; host until the call
returns, ready until the device is done):

    pool, blocks of a hit      store host   store ready   admit host   admit ready
    GPT-2 XL by head, 16       0.63 / 20.2  163.5 / 163.2  8.5 / 20.7   75.0 / 70.3
    GPT-2 XL by head, 64       1.16 / 488   646.3 / 646.2  227 / 209    295.6 / 277.5
    Kanana's latent, 24        0.86 / 33.7  1.60 / 34.2    0.45 / 32.8  1.57 / 33.4
    Keye's three leaves, 96    2.00 / 199   4.13 / 200.0   0.60 / 197   4.80 / 197.8

GPT-2's pool lies with its blocks along the lanes ((0, 2, 3, 4, 1) beside
the cache's (0, 1, 2, 4, 3)): 5.1 ms a leaf to store a block, 2.3 to read
one, whoever calls. Its rows above are of the tree that looped over a
store's blocks (admissions a call a block); the pool has since moved such a
leaf a block a call both ways (`serve-xl-chat` read the loop's store no
better, PERF.md, PR 47; ROADMAP S6).

Writes `chiprun_out/prefix_pool_copies[-parent].json`. One process, which
holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "benchmarks", "chip", "configs")

# cell's configuration -> (leaf -> shape after [layers, slots]; token axis),
# the hits' lengths in blocks
GEOMETRIES = {
    "gpt2-xl-serve-1chip": (
        lambda c, T: {n: ((25, T, 64), 3) for n in ("k", "v")}, 48,
        (1, 16, 28, 64)),
    "kanana-2-30b-a3b-serve-1chip": (
        lambda c, T: {"latent": ((T, c["kv_lora_rank"]), 2),
                      "k_rope": ((T, c["qk_rope_head_dim"]), 2)}, None,
        (1, 16, 24, 32)),
    "keye-vl-2.0-30b-a3b-serve-1chip": (
        lambda c, T: {
            "k": ((T, c["num_key_value_heads"] * c["head_dim"]), 2),
            "v": ((T, c["num_key_value_heads"] * c["head_dim"]), 2),
            "ik": ((T, 64), 2)}, None,
        (1, 64, 96, 104)),
}


def timed(fn, ready):
    t0 = time.perf_counter()
    out = fn()
    t1 = time.perf_counter()
    ready(out)
    return out, (t1 - t0) * 1e3, (time.perf_counter() - t0) * 1e3


def layout(array) -> list:
    return list(array.format.layout.major_to_minor)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=REPO)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--layers", type=int, help="rehearsals: fewer layers")
    ap.add_argument("--slots", type=int, help="rehearsals: fewer slots")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.repo))

    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.kv_cache import PagedKVCache

    device = jax.devices()[0]
    out = {"repo": args.repo, "device": device.device_kind,
           "platform": device.platform, "rows": []}
    for config, (leaves_of, layers, hits) in GEOMETRIES.items():
        with open(os.path.join(CONFIGS, config + ".json")) as f:
            c = json.load(f)
        d = c["deployment"]
        L, B, T = (args.layers or layers or c["num_hidden_layers"],
                   args.slots or d["max_batch"], d["max_seq_len"])
        size = d["kv_block_size"]
        leaves = leaves_of(c, T)
        keys = jax.random.split(jax.random.key(0), len(leaves))
        cache = {n: jax.random.normal(k, (L, B) + shape, jnp.bfloat16)
                 for k, (n, (shape, _)) in zip(keys, leaves.items())}
        kv = PagedKVCache.for_cache(
            cache, {n: axis for n, (_, axis) in leaves.items()},
            num_blocks=d["kv_blocks"], block_size=size)

        laid = {n: {"cache": layout(cache[n]), "pool": layout(kv.pools[n])}
                for n in leaves}
        prompt = 0
        for n in hits:
            rows = []
            for rep in range(args.reps + 1):        # the first compiles
                prompt += 1
                ids = [prompt * 7919 % 50021 + i for i in range(n * size)]
                before = kv.stats()
                stored, host_out, ready_out = timed(
                    lambda: kv.store_prefix(ids, cache, 0),
                    lambda _: jax.block_until_ready(kv.pools))
                hit, blocks = kv.match_prefix(ids)
                assert stored == n and hit == n * size, (stored, hit, n)
                cache, host_in, ready_in = timed(
                    lambda: kv.copy_into_slot(cache, 1, blocks),
                    jax.block_until_ready)
                after = kv.stats()
                calls = {k: after[k] - before[k] for k in after
                         if k.startswith("copy_")}
                rows.append((host_out, ready_out, host_in, ready_in))
            same = all(bool(jnp.array_equal(
                jax.lax.slice_in_dim(cache[name][:, 1], 0, n * size,
                                     axis=axis - 1),
                jax.lax.slice_in_dim(cache[name][:, 0], 0, n * size,
                                     axis=axis - 1)))
                for name, (_, axis) in leaves.items())
            med = [statistics.median(col) for col in zip(*rows[1:])]
            row = {"config": config, "blocks": n, "leaves": len(leaves),
                   "store_host_ms": med[0], "store_ready_ms": med[1],
                   "admit_host_ms": med[2], "admit_ready_ms": med[3],
                   "first_ms": rows[0], "calls": calls, "same_rows": same,
                   "laid": laid}
            out["rows"].append(row)
            print(json.dumps(row), flush=True)
        out[config + ".peak_bytes"] = (device.memory_stats() or {}).get(
            "peak_bytes_in_use")
        del cache, kv
    name = "prefix_pool_copies" + (
        "" if os.path.abspath(args.repo) == REPO else "-parent")
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", name + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if all(r["same_rows"] for r in out["rows"]) else 1


if __name__ == "__main__":
    sys.exit(main())
