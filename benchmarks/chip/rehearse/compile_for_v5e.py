#!/usr/bin/env python3
"""Rehearsal without the chip: the cells' programs at their real sizes,
compiled by the TPU's compiler for a described `v5e:2x2` (the method of
`tests/test_tpu_compile.py` and the `on-chip-measurement` guide, section
2.3). Nothing runs; what it prints are `memory_analysis()` bytes per device
and the collectives in the HLO. It decides `prefill_chunk_size` and
`kv_blocks` of the serving configuration and shows that the four-chip step
fits before a four-chip call is spent on it.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_for_v5e.py \
        [serve] [train-small] [train-xl]

A script, not a test: only one test file may describe the topology under
the driver's six workers, and `tests/test_tpu_compile.py` already does.
"""

from __future__ import annotations

import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.dirname(os.path.dirname(CHIP_DIR)), CHIP_DIR]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from harness import spec  # noqa: E402

CHIP_BYTES = 16_909_336_064      # bytes_limit of one v5e chip (PR 21's probe)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def say(msg: str) -> None:
    print(msg, flush=True)


def sized(name: str, compiled, extra: int = 0) -> None:
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    say(f"{name}: temp {m.temp_size_in_bytes:,} + arguments "
        f"{m.argument_size_in_bytes:,} + outputs {m.output_size_in_bytes:,}"
        f" - aliased {m.alias_size_in_bytes:,} = {total:,} bytes a device"
        f"{f' (+ {extra:,} held beside it)' if extra else ''}; "
        f"{(total + extra) / CHIP_BYTES:.1%} of {CHIP_BYTES:,}")


def on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def serve(chips) -> None:
    from ray_tpu.models import gpt2

    config = spec.load_json(os.path.join(
        CHIP_DIR, "configs", "gpt2-xl-serve-1chip.json"))
    family = spec.family(config["family"])
    d = config["deployment"]
    cfg = family.program_config(config["model"])
    B, T = d["max_batch"], d["max_seq_len"]
    one = SingleDeviceSharding(chips[0])
    params = on(one, jax.eval_shape(
        lambda: gpt2.init_params(jax.random.key(0), cfg)))
    cache = on(one, jax.eval_shape(lambda: gpt2.init_cache(cfg, B, T)))
    pool = (2 * cfg.n_layer * d["kv_blocks"] * cfg.n_head
            * d["kv_block_size"] * cfg.head_dim * 2)
    say(f"prefix pool: {d['kv_blocks']} blocks of {d['kv_block_size']} "
        f"tokens = {pool:,} bytes")

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # as serve/llm.LLMEngine jits them: the cache is donated
    decode = jax.jit(lambda p, c, t, pos, a: gpt2.decode_step(
        p, c, t, pos, a, cfg), donate_argnums=(1,))
    t0 = time.time()
    sized(f"decode_step B={B} T={T}", decode.lower(
        params, cache, arr((B,), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.bool_)).compile(), pool)
    say(f"  compiled in {time.time() - t0:.0f}s")
    for C in sorted({d["prefill_chunk_size"], 256, 64}, reverse=True):
        chunk = jax.jit(lambda p, c, t, pos0, n, a: gpt2.prefill_chunk(
            p, c, t, pos0, n, a, cfg), donate_argnums=(1,))
        t0 = time.time()
        try:
            sized(f"prefill_chunk B={B} C={C} T={T}", chunk.lower(
                params, cache, arr((B, C), jnp.int32), arr((B,), jnp.int32),
                arr((B,), jnp.int32), arr((B,), jnp.bool_)).compile(), pool)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            say(f"prefill_chunk C={C}: refused: {str(e)[:300]}")
        say(f"  compiled in {time.time() - t0:.0f}s")


def train(chips, config_name: str) -> None:
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         config_name + ".json"))
    family = spec.family(config["family"])
    n = 1
    for v in config["job"]["mesh"].values():
        n *= v
    prog = family.build_train(config["model"], config["job"], chips[:n], 0)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(prog.program.init_fn, jax.random.key(0)),
        prog.program.state_sharding)
    t0 = time.time()
    compiled = prog.compile_step(state)
    sized(f"{config_name} step, mesh {config['job']['mesh']}, global batch "
          f"{prog.global_batch}, remat {config['job']['remat']}", compiled)
    text = compiled.as_text()
    counts = {c: len(re.findall(rf"\b{c}(-start)?\(", text))
              for c in COLLECTIVES}
    say(f"  compiled in {time.time() - t0:.0f}s; collectives {counts}; "
        f"tpu_custom_call: {'tpu_custom_call' in text}; involuntary "
        f"rematerialization warned: "
        f"{'nvoluntary full rematerialization' in text}")


def main() -> None:
    from jax.experimental import topologies

    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"     # the branches taken on the chip
    what = sys.argv[1:] or ["serve", "train-small", "train-xl"]
    if "serve" in what:
        serve(chips)
    if "train-small" in what:
        train(chips, "gpt2-small-train-1chip")
    if "train-xl" in what:
        train(chips, "gpt2-xl-train-fsdp4")


if __name__ == "__main__":
    main()
