#!/usr/bin/env python3
"""Rehearsal without the chip: the OLMoE train step at the published widths,
compiled by the TPU's compiler for a described `v5e:2x2` (one chip of it),
as `compile_for_v5e.py` does for the GPT-2 cells. It decides the
configuration's depth and `global_batch` (the largest whose step fits) and
fills its `memory` block, and it checks what the step is made of: the
Pallas attention and grouped-matmul kernels are there, and neither a
`[., 4096, 64, .]` one-hot dispatch tensor nor 64 unrolled dense expert
products are.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_olmoe_for_v5e.py \
        [--layers 1,2] [--batches 2,3,4] [--remat 1,0]

With no arguments: the configuration as the file has it, asserted.
"""

from __future__ import annotations

import argparse
import copy
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import compile_for_v5e as base  # noqa: E402  (sets the environment, paths)

import jax  # noqa: E402

from harness import spec  # noqa: E402

CONFIG = "olmoe-1b-7b-train-1chip"


def compile_step(config: dict, chips):
    family = spec.family(config["family"])
    prog = family.build_train(config["model"], config["job"], chips[:1], 0)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(prog.program.init_fn, jax.random.key(0)),
        prog.program.state_sharding)
    return prog.compile_step(state)


def made_of(text: str, model: dict, seq_len: int) -> dict:
    """What the compiled step's HLO holds: the Mosaic kernels by the name
    stack of the `pallas_call` they came from, any tensor shaped like a
    one-hot dispatch mask (`[., T, E, .]`), and any dense product (a `dot`
    or the `convolution` the TPU compiler makes of one) whose result is
    as wide as an expert's hidden layer: only the experts are."""
    experts = model["num_experts"]
    inner = model["intermediate_size"]
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    kernels += re.findall(
        r'op_name="([^"]*)"[^\n]*custom_call_target="tpu_custom_call"', text)
    return {"flash_kernels": sum(k.endswith("attn/pallas_call")
                                 for k in kernels),
            "grouped_matmul_kernels": sum(
                k.endswith(("jit(gmm)/pallas_call", "jit(tgmm)/pallas_call"))
                for k in kernels),
            "one_hot_dispatch_tensors": len(re.findall(
                rf"\[(\d+,)?{seq_len},{experts},\d+\]", text)),
            "dense_expert_products": len(re.findall(
                rf"= \w+\[[\d,]*{inner}\]\S* (dot|convolution)\(", text))}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", default="")
    ap.add_argument("--batches", default="")
    ap.add_argument("--remat", default="")
    args = ap.parse_args()
    from jax.experimental import topologies

    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"     # the branches taken on the chip
    config = spec.load_json(os.path.join(base.CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    sweep = bool(args.layers or args.batches or args.remat)
    layers = [int(x) for x in args.layers.split(",") if x] \
        or [config["model"]["num_hidden_layers"]]
    batches = [int(x) for x in args.batches.split(",") if x] \
        or [config["job"]["global_batch"]]
    remats = [bool(int(x)) for x in args.remat.split(",") if x] \
        or [config["job"]["remat"]]
    for n_layers in layers:
        for remat in remats:
            for batch in batches:
                c = copy.deepcopy(config)
                c["model"]["num_hidden_layers"] = n_layers
                c["job"]["global_batch"], c["job"]["remat"] = batch, remat
                name = f"{CONFIG} layers {n_layers} batch {batch} remat {remat}"
                t0 = time.time()
                try:
                    compiled = compile_step(c, chips)
                except Exception as e:  # noqa: BLE001 - the compiler's refusal
                    base.say(f"{name}: refused: {str(e)[:400]}")
                    assert sweep, "the configuration's own step must compile"
                    continue
                base.sized(name, compiled)
                parts = made_of(compiled.as_text(), c["model"],
                                c["job"]["seq_len"])
                base.say(f"  compiled in {time.time() - t0:.0f}s; {parts}")
                if not sweep:
                    m = compiled.memory_analysis()
                    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
                             + m.output_size_in_bytes - m.alias_size_in_bytes)
                    assert 0.8 * base.CHIP_BYTES <= total <= base.CHIP_BYTES, total
                    assert total == config["memory"][
                        "step_program_bytes_compiled_for_v5e"], total
                    assert parts["flash_kernels"] == 3, parts
                    assert parts["grouped_matmul_kernels"] == 9, parts
                    assert parts["one_hot_dispatch_tensors"] == 0, parts
                    assert parts["dense_expert_products"] == 0, parts
                    base.say("  as the configuration file says: ok")


if __name__ == "__main__":
    main()
