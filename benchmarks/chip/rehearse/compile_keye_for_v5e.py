#!/usr/bin/env python3
"""Rehearsal without the chip: the Keye serving cell's two step programs at
the configuration's sizes, compiled by the TPU's compiler for a described
`v5e:2x2` (`compile_kimi_for_v5e.py`'s method). Nothing runs; what it
prints are `memory_analysis()` bytes and what the compiled programs are made
of. It decides `max_batch`, and shows that neither program holds a second
copy of a cache leaf, copies an expert matrix out of the stack, or (the
decode program) writes a dense `[slots, heads, positions]` score array.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_keye_for_v5e.py \
        [--slots 32,28] [--chunks 128] [--hlo DIR]

A script, not a test: `tests/test_tpu_compile.py` imports `compile_step`
and `made_of` and holds the configuration file's bytes to them.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.dirname(os.path.dirname(CHIP_DIR)),
                            CHIP_DIR, os.path.join(CHIP_DIR, "rehearse"))
                if p not in sys.path]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from compile_brumby_for_v5e import STATE_IN_PLACE  # noqa: E402
from compile_kanana_for_v5e import (CHIP_BYTES, program_bytes,  # noqa: E402
                                    written_arrays)
from harness import spec  # noqa: E402

CONFIG = "keye-vl-2.0-30b-a3b-serve-1chip"


def _cache(config: dict, slots: int, max_len: int = 0):
    from ray_tpu.models import keye

    cfg = spec.family(config["family"]).program_config(config)
    return keye, cfg, jax.eval_shape(lambda: keye.init_cache(
        cfg, slots, max_len or config["deployment"]["max_seq_len"]))


def kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the cache: k, v and the indexer's key, every
    layer."""
    keye, _, cache = _cache(config, 1, 1)
    return sum(cache[name].size * cache[name].dtype.itemsize
               for name in keye.CACHE_TOKEN_AXIS)


def pool_bytes(config: dict) -> int:
    """The prefix pool's arrays: `kv_blocks` blocks of all three leaves."""
    d = config["deployment"]
    return d["kv_blocks"] * d["kv_block_size"] * kv_bytes_per_token(config)


def compile_step(config: dict, chips, program: str, chunk: int = 0):
    """`decode` or `prefill` as `serve/llm.LLMEngine` jits them (the cache
    donated), lowered for one described chip at the configuration's sizes
    and compiled. The caller steers `jax.default_backend` to the chip's."""
    d = config["deployment"]
    B = d["max_batch"]
    keye, cfg, cache = _cache(config, B)
    C = chunk or d["prefill_chunk_size"]
    one = SingleDeviceSharding(chips[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(
        lambda: keye.init_params(jax.random.key(0), cfg)))
    cache = on(cache)
    ints, flags = arr((B,), jnp.int32), arr((B,), jnp.bool_)
    if program == "decode":
        fn = jax.jit(lambda p, c, t, pos, a: keye.decode_step(
            p, c, t, pos, a, cfg), donate_argnums=(1,))
        return fn.lower(params, cache, ints, ints, flags).compile()
    fn = jax.jit(lambda p, c, t, pos0, n, a: keye.prefill_chunk(
        p, c, t, pos0, n, a, cfg), donate_argnums=(1,))
    return fn.lower(params, cache, arr((B, C), jnp.int32), ints, ints,
                    flags).compile()


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas kernels (the experts');
    every instruction that materialises an array as large as a whole cache
    leaf or one layer of it and is none of `STATE_IN_PLACE` (a `copy`:
    there must be none); what it materialises of the experts' matrices, one
    layer's [128, d, F] or the whole stack's, which it must not (ROADMAP
    S12a); and the dense float32 scores of every slot over every position,
    `[slots, heads (or 4, 8), positions]`, which the decode program must
    not write: attention reads the chosen rows."""
    keye, cfg, cache = _cache(config, config["deployment"]["max_batch"])
    copies = {}
    for name in keye.CACHE_TOKEN_AXIS:
        leaf = cache[name]
        whole = ",".join(str(n) for n in leaf.shape)
        layer = ",".join(str(n) for n in leaf.shape[1:])
        copies[name] = sorted(
            op for op, _ in written_arrays(
                hlo, f"{whole}|(?:1,)?{layer}", "bf16")
            if op not in STATE_IN_PLACE)
    D, F, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    matrices = "|".join(f"{n},{a},{b}" for n in (E, E * cfg.n_layer)
                        for a, b in ((D, F), (F, D)))
    B, T = cache["k"].shape[1:3]
    H, G, R = cfg.n_head, cfg.n_kv_head, cfg.queries_per_kv
    dense = f"{B},(?:1,)?(?:{H}|{G},{R}),(?:1,)?{T}"
    return {"kernels": hlo.count("tpu_custom_call"),
            "leaf_copies": {k: v for k, v in copies.items() if v},
            "expert_matrix_copies": sorted(
                op for op, _ in written_arrays(hlo, matrices, "bf16")
                if op not in STATE_IN_PLACE),
            "dense_scores": sorted(op for op, _ in written_arrays(
                hlo, dense, "f32"))}


def main() -> None:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--programs", default="decode,prefill")
    ap.add_argument("--hlo", default="", help="a directory for the HLO text")
    args = ap.parse_args()
    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"     # the branches taken on the chip
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    pool = pool_bytes(config)
    print(f"kv_bytes_per_token {kv_bytes_per_token(config)}; prefix pool: "
          f"{pool:,} bytes", flush=True)
    d = config["deployment"]
    chunks = [int(c) for c in args.chunks.split(",") if c] or [
        d["prefill_chunk_size"]]
    programs = [("decode", 0)] * ("decode" in args.programs) + [
        ("prefill", c) for c in chunks if "prefill" in args.programs]
    for slots in [int(s) for s in args.slots.split(",") if s] or [
            d["max_batch"]]:
        d["max_batch"] = slots
        for program, C in programs:
            t0 = time.time()
            try:
                compiled = compile_step(config, chips, program, C)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                print(f"{slots} slots {program} C={C}: refused: "
                      f"{str(e)[:400]}", flush=True)
                continue
            b = program_bytes(compiled)
            print(f"{slots} slots {program} C={C or 1}: {b}; with the pool "
                  f"{(b['total'] + pool) / CHIP_BYTES:.1%} of the chip; "
                  f"{made_of(compiled.as_text(), config)}; bytes accessed "
                  f"{compiled.cost_analysis().get('bytes accessed', 0):,.0f}"
                  f"; compiled in {time.time() - t0:.0f}s", flush=True)
            if args.hlo:
                os.makedirs(args.hlo, exist_ok=True)
                with open(os.path.join(
                        args.hlo, f"keye_{slots}_{program}_{C}.hlo"),
                        "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main()
