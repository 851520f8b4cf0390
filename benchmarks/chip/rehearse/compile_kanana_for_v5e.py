#!/usr/bin/env python3
"""Rehearsal without the chip: the Kanana serving cell's two step programs
at the configuration's sizes, compiled by the TPU's compiler for a
described `v5e:2x2` (`compile_for_v5e.py`'s method). Nothing runs; what it
prints are `memory_analysis()` bytes and what the compiled programs are
made of. It decides `prefill_chunk_size` and the depth.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_kanana_for_v5e.py \
        [--layers 8] [--chunks 128,64] [--hlo DIR]

A script, not a test: `tests/test_tpu_compile.py` imports `compile_step`
and `made_of` and holds the configuration file's bytes to them.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.dirname(os.path.dirname(CHIP_DIR)),
                            CHIP_DIR) if p not in sys.path]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from harness import spec  # noqa: E402

CONFIG = "kanana-2-30b-a3b-serve-1chip"
CHIP_BYTES = 16_909_336_064      # bytes_limit of one v5e chip (PR 21's probe)


def program_bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {"temp": m.temp_size_in_bytes, "arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes, "aliased": m.alias_size_in_bytes}
    out["total"] = (out["temp"] + out["arguments"] + out["outputs"]
                    - out["aliased"])
    return out


def pool_bytes(config: dict) -> int:
    """The prefix pool's arrays: a block of each per-token cache leaf."""
    from ray_tpu.models import deepseek

    d = config["deployment"]
    cfg = spec.family(config["family"]).program_config(config)
    cache = jax.eval_shape(lambda: deepseek.init_cache(cfg, 1,
                                                       d["kv_block_size"]))
    return d["kv_blocks"] * sum(
        cache[name].size * cache[name].dtype.itemsize
        for name in deepseek.CACHE_TOKEN_AXIS)


def compile_step(config: dict, chips, program: str, chunk: int = 0):
    """`decode` or `prefill` as `serve/llm.LLMEngine` jits them (the cache
    donated), lowered for one described chip at the configuration's sizes
    and compiled. The caller steers `jax.default_backend` to the chip's."""
    from ray_tpu.models import deepseek

    d = config["deployment"]
    cfg = spec.family(config["family"]).program_config(config)
    B, T = d["max_batch"], d["max_seq_len"]
    C = chunk or d["prefill_chunk_size"]
    one = SingleDeviceSharding(chips[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(
        lambda: {**deepseek.init_ends(jax.random.key(0), cfg),
                 "dense": jax.tree.map(
                     lambda a: jnp.stack([a] * cfg.n_dense_layer),
                     deepseek._init_layer(jax.random.key(0), 0, cfg, True)),
                 "blocks": jax.tree.map(
                     lambda a: jnp.stack(
                         [a] * (cfg.n_layer - cfg.n_dense_layer)),
                     deepseek._init_layer(jax.random.key(0), 1, cfg,
                                          False))}))
    cache = on(jax.eval_shape(lambda: deepseek.init_cache(cfg, B, T)))
    ints, flags = arr((B,), jnp.int32), arr((B,), jnp.bool_)
    if program == "decode":
        fn = jax.jit(lambda p, c, t, pos, a: deepseek.decode_step(
            p, c, t, pos, a, cfg), donate_argnums=(1,))
        return fn.lower(params, cache, ints, ints, flags).compile()
    fn = jax.jit(lambda p, c, t, pos0, n, a: deepseek.prefill_chunk(
        p, c, t, pos0, n, a, cfg), donate_argnums=(1,))
    return fn.lower(params, cache, arr((B, C), jnp.int32), ints, ints,
                    flags).compile()


def written_arrays(hlo: str, dims: str, dtype: str = "bf16") -> list:
    """(operation, type) of every instruction outside a fused computation
    whose result holds a `<dtype>[<dims>]`: what the program materialises
    (`tests/test_tpu_compile.py`'s `_written_arrays`)."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    holds = re.compile(r"%s\[(?:%s)\]" % (dtype, dims))
    found, skip = [], False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            skip = head.group(1) in fused
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m and not skip and holds.search(m.group(1)):
            found.append((m.group(2), m.group(1)))
    return found


# what an instruction may do with a whole cache leaf without copying it
IN_PLACE = {"parameter", "get-tuple-element", "tuple", "while",
            "dynamic-update-slice", "bitcast"}


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas grouped-matmul kernels
    (three in the expert layers' loop body); a copy of a whole cache leaf or
    of one layer of it, which it must not hold; and the copies of the routed
    experts' matrices (a layer's or the stack's), which it holds today: the
    scan slices a layer's three out of the stack and the compiler copies
    each for the kernel, half of a decode step's time (ROADMAP S12: the
    kernels can read them where the stack holds them)."""
    d, m = config["deployment"], config["model"]
    B, T, L = d["max_batch"], d["max_seq_len"], m["num_hidden_layers"]
    copies = []
    for width in (m["kv_lora_rank"], m["qk_rope_head_dim"]):
        copies += [op for op, _ in written_arrays(
            hlo, f"{L},{B},{T},{width}") if op not in IN_PLACE]
        copies += [op for op, _ in written_arrays(
            hlo, f"(?:1,)?{B},{T},{width}")]
    E, D, F = (m["n_routed_experts"], m["hidden_size"],
               m["moe_intermediate_size"])
    n_moe = L - m["first_k_dense_replace"]
    experts = [op for op, _ in written_arrays(
        hlo, f"(?:(?:{n_moe}|1),)?(?:{E}|{n_moe * E}),(?:{D},{F}|{F},{D})")
        if op not in IN_PLACE]
    return {"grouped_matmul_kernels": hlo.count("tpu_custom_call"),
            "cache_copies": sorted(copies),
            "expert_weight_copies": sorted(experts)}


def main() -> None:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--chunks", default="")
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
    print(f"prefix pool: {pool:,} bytes", flush=True)
    chunks = [int(c) for c in args.chunks.split(",") if c] or [
        config["deployment"]["prefill_chunk_size"]]
    for program, C in [("decode", 0)] + [("prefill", c) for c in chunks]:
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
            with open(os.path.join(args.hlo, f"kanana_{program}_{C}.hlo"),
                      "w") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
