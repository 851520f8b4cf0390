#!/usr/bin/env python3
"""Rehearsal without the chip: the Nemotron serving cell's two step programs at
the configuration's sizes, compiled by the TPU's compiler for a described
`v5e:2x2` (`compile_solar_for_v5e.py`'s method, with the cache's leaves
taken from the module that serves the preset). Nothing runs; what it prints
are `memory_analysis()` bytes and what the compiled programs are made of. It
decides `max_batch`, and shows that neither program holds a second copy of a
cache leaf, copies an expert matrix out of the stack, or writes a chunk's
scores over all of a slot's positions.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_nemotron_for_v5e.py \
        [--slots 40,32] [--chunks 128] [--hlo DIR]

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

CONFIG = "nemotron-3-super-120b-a12b-serve-1chip"


def _cache(config: dict, slots: int):
    from ray_tpu.models import serving_family

    _, module, _ = serving_family(config["deployment"]["preset"])
    cfg = spec.family(config["family"]).program_config(config)
    return module, cfg, jax.eval_shape(lambda: module.init_cache(
        cfg, slots, config["deployment"]["max_seq_len"]))


def _bytes(leaves) -> int:
    return sum(a.size * a.dtype.itemsize for a in leaves)


def cache_bytes(config: dict) -> dict:
    """What the cache holds a slot (the state) and a token (the rows)."""
    module, _, cache = _cache(config, 1)
    T = config["deployment"]["max_seq_len"]
    return {"state_bytes_per_slot": _bytes(
                cache[name] for name in module.CACHE_STATE),
            "kv_bytes_per_token": _bytes(
                cache[name] for name in module.CACHE_TOKEN_AXIS) // T}


def pool_bytes(config: dict) -> int:
    """The prefix pool's arrays: `kv_blocks` blocks of rows and a snapshot
    for every whole slot of rows they hold (`PagedKVCache.for_cache`)."""
    d, per = config["deployment"], cache_bytes(config)
    tokens = d["kv_blocks"] * d["kv_block_size"]
    return (tokens * per["kv_bytes_per_token"]
            + max(1, tokens // d["max_seq_len"]) * per["state_bytes_per_slot"])


def compile_step(config: dict, chips, program: str, chunk: int = 0):
    """`decode` or `prefill` as `serve/llm.LLMEngine` jits them (the cache
    donated), lowered for one described chip at the configuration's sizes
    and compiled. The caller steers `jax.default_backend` to the chip's."""
    d = config["deployment"]
    B = d["max_batch"]
    module, cfg, cache = _cache(config, B)
    C = chunk or d["prefill_chunk_size"]
    one = SingleDeviceSharding(chips[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(
        lambda: module.init_params(jax.random.key(0), cfg)))
    cache = on(cache)
    ints, flags = arr((B,), jnp.int32), arr((B,), jnp.bool_)
    if program == "decode":
        fn = jax.jit(lambda p, c, t, pos, a: module.decode_step(
            p, c, t, pos, a, cfg), donate_argnums=(1,))
        return fn.lower(params, cache, ints, ints, flags).compile()
    fn = jax.jit(lambda p, c, t, pos0, n, a: module.prefill_chunk(
        p, c, t, pos0, n, a, cfg), donate_argnums=(1,))
    return fn.lower(params, cache, arr((B, C), jnp.int32), ints, ints,
                    flags).compile()


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas kernels (the SSM state's
    update, the rows' write, attention's read and the experts' two-matrix
    MLP); every instruction that materialises an array as large as a whole
    cache leaf and is none of `STATE_IN_PLACE` (a `copy`: there must be
    none); what it materialises of one layer's SSM state for all slots,
    which it must not; what it materialises of the held experts' matrices,
    one layer's [E', c, F] or the whole stack's, which it must not either
    (ROADMAP S12a); and the float32 arrays as large as one slot's scores
    over all T positions for a chunk's 2 x 16 x 128 queries, which the
    further lanes' loop over blocks must not make."""
    module, cfg, cache = _cache(config, config["deployment"]["max_batch"])
    copies = {}
    for name in list(module.CACHE_STATE) + list(module.CACHE_TOKEN_AXIS):
        leaf = cache[name]
        shape = ",".join(str(n) for n in leaf.shape)
        copies[name] = sorted(
            op for op, _ in written_arrays(
                hlo, shape, "f32" if leaf.dtype == jnp.float32 else "bf16")
            if op not in STATE_IN_PLACE)
    layer = ",".join(str(n) for n in cache["ssm"].shape[1:])
    C, F = cfg.d_latent, cfg.d_ff_expert
    held = (cfg.experts_held, cfg.experts_held * cfg.layers_of("moe"))
    matrices = "|".join(f"{n},{a},{b}" for n in held
                        for a, b in ((C, F), (F, C)))
    d = config["deployment"]
    queries = cfg.queries_per_kv * d["prefill_chunk_size"]
    scores = f"{cfg.n_kv_head},{queries},{d['max_seq_len']}"
    return {"kernels": hlo.count("tpu_custom_call"),
            "whole_slot_scores": sorted(op for op, _ in written_arrays(
                hlo, scores, "f32")),
            "leaf_copies": {k: v for k, v in copies.items() if v},
            "ssm_layer_copies": sorted(op for op, _ in written_arrays(
                hlo, f"(?:1,)?{layer}", "f32")),
            "expert_matrix_copies": sorted(
                op for op, _ in written_arrays(hlo, matrices, "bf16")
                if op not in STATE_IN_PLACE)}


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
    print(f"{cache_bytes(config)}; prefix pool: {pool:,} bytes", flush=True)
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
                        args.hlo, f"nemotron_{slots}_{program}_{C}.hlo"),
                        "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main()
