#!/usr/bin/env python3
"""Rehearsal without the chip: the Brumby serving cell's two step programs
at the configuration's sizes, compiled by the TPU's compiler for a
described `v5e:2x2` (`compile_kanana_for_v5e.py`'s method). Nothing runs;
what it prints are `memory_analysis()` bytes and what the compiled programs
are made of. It decides `prefill_chunk_size`, and shows that the decode
program holds no second copy of the state.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_brumby_for_v5e.py \
        [--layers 8] [--chunks 64,128] [--hlo DIR]

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

from compile_kanana_for_v5e import (CHIP_BYTES, IN_PLACE,  # noqa: E402
                                    program_bytes, written_arrays)
from harness import spec  # noqa: E402

CONFIG = "brumby-14b-serve-1chip"


def _state(config: dict, slots: int):
    from ray_tpu.models import brumby

    cfg = spec.family(config["family"]).program_config(config)
    return brumby, cfg, jax.eval_shape(lambda: brumby.init_cache(cfg, slots))


def state_bytes_per_slot(config: dict) -> int:
    brumby, _, cache = _state(config, 1)
    return sum(cache[name].size * cache[name].dtype.itemsize
               for name in brumby.CACHE_STATE)


def pool_bytes(config: dict) -> int:
    """The prefix pool's arrays: `kv_blocks` snapshots of a slot's state."""
    return config["deployment"]["kv_blocks"] * state_bytes_per_slot(config)


def compile_step(config: dict, chips, program: str, chunk: int = 0):
    """`decode` or `prefill` as `serve/llm.LLMEngine` jits them (the cache
    donated), lowered for one described chip at the configuration's sizes
    and compiled. The caller steers `jax.default_backend` to the chip's."""
    d = config["deployment"]
    B = d["max_batch"]
    brumby, cfg, cache = _state(config, B)
    C = chunk or d["prefill_chunk_size"]
    one = SingleDeviceSharding(chips[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(
        lambda: {**brumby.init_ends(jax.random.key(0), cfg),
                 "blocks": jax.tree.map(
                     lambda a: jnp.stack([a] * cfg.n_layer),
                     brumby._init_layer(jax.random.key(0), 0, cfg))}))
    cache = on(cache)
    ints, flags = arr((B,), jnp.int32), arr((B,), jnp.bool_)
    if program == "decode":
        fn = jax.jit(lambda p, c, t, pos, a: brumby.decode_step(
            p, c, t, pos, a, cfg), donate_argnums=(1,))
        return fn.lower(params, cache, ints, ints, flags).compile()
    fn = jax.jit(lambda p, c, t, pos0, n, a: brumby.prefill_chunk(
        p, c, t, pos0, n, a, cfg), donate_argnums=(1,))
    return fn.lower(params, cache, arr((B, C), jnp.int32), ints, ints,
                    flags).compile()


# what an instruction may do with the whole state leaf without copying it:
# the kernel writes it where it reads it, a skipped slot passes it through
STATE_IN_PLACE = IN_PLACE | {"custom-call", "conditional", "fusion"}


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas state-update kernel (one
    in the layers' loop body of the decode program, none in the chunk
    program); every instruction that materialises an array as large as the
    whole state leaf and is none of `STATE_IN_PLACE` (a `copy`: there must
    be none; `temp` of `program_bytes` is the bound that a fusion which
    wrote a second leaf would break); and what it materialises of one
    layer's state for all slots, which the decode program must not."""
    _, cfg, _ = _state(config, 1)
    B, L = config["deployment"]["max_batch"], cfg.n_layer
    G, d, W = cfg.n_kv_head, cfg.head_dim, cfg.expanded_width
    whole = [op for op, _ in written_arrays(hlo, f"{L},{B},{G},{d},{W}",
                                            "f32") if op not in STATE_IN_PLACE]
    layer = [op for op, _ in written_arrays(
        hlo, f"(?:1,)?{B},{G},{d},{W}", "f32")]
    return {"retention_kernels": hlo.count("tpu_custom_call"),
            "state_copies": sorted(whole), "layer_copies": sorted(layer)}


def main() -> None:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--chunks", default="")
    ap.add_argument("--programs", default="decode,prefill")
    ap.add_argument("--hlo", default="", help="a directory for the HLO text")
    args = ap.parse_args()
    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"     # the branches taken on the chip
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    if args.layers:
        config["model"]["num_hidden_layers"] = args.layers
    pool = pool_bytes(config)
    print(f"state a slot: {state_bytes_per_slot(config):,} bytes; prefix "
          f"pool: {pool:,} bytes", flush=True)
    chunks = [int(c) for c in args.chunks.split(",") if c] or [
        config["deployment"]["prefill_chunk_size"]]
    programs = [("decode", 0)] * ("decode" in args.programs) + [
        ("prefill", c) for c in chunks if "prefill" in args.programs]
    for program, C in programs:
        t0 = time.time()
        try:
            compiled = compile_step(config, chips, program, C)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            print(f"{program} C={C}: refused: {str(e)[:400]}", flush=True)
            continue
        b = program_bytes(compiled)
        print(f"{program} C={C or 1}: {b}; with the pool "
              f"{(b['total'] + pool) / CHIP_BYTES:.1%} of the chip; "
              f"{made_of(compiled.as_text(), config)}; bytes accessed "
              f"{compiled.cost_analysis().get('bytes accessed', 0):,.0f}; "
              f"compiled in {time.time() - t0:.0f}s", flush=True)
        if args.hlo:
            os.makedirs(args.hlo, exist_ok=True)
            with open(os.path.join(args.hlo, f"brumby_{program}_{C}.hlo"),
                      "w") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
