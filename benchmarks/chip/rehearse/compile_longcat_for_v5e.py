#!/usr/bin/env python3
"""Rehearsal without the chip: the LongCat serving cell's two step programs
at the configuration's sizes, compiled by the TPU's compiler for a described
`v5e:2x2` (`compile_nemotron_for_v5e.py`'s method). Nothing runs; what it
prints are `memory_analysis()` bytes and what the compiled programs are made
of. It decides `max_seq_len`, and shows that neither program holds a second
copy of a cache leaf or copies an expert matrix or a dense FFN's out of its
stack.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_longcat_for_v5e.py \
        [--slots 128,64] [--chunks 128] [--hlo DIR]

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

from compile_brumby_for_v5e import STATE_IN_PLACE  # noqa: E402
from compile_kanana_for_v5e import (CHIP_BYTES, program_bytes,  # noqa: E402
                                    written_arrays)
# the two programs of any family `serving_family` knows, by the file's preset
from compile_nemotron_for_v5e import _cache, compile_step  # noqa: E402,F401
from harness import spec  # noqa: E402

CONFIG = "longcat-flash-chat-serve-1chip"


def kv_bytes_per_token(config: dict) -> int:
    """What a token leaves in the cache, every sublayer."""
    module, _, cache = _cache(config, 1)
    return sum(cache[name].size * cache[name].dtype.itemsize
               for name in module.CACHE_TOKEN_AXIS) \
        // config["deployment"]["max_seq_len"]


def pool_bytes(config: dict) -> int:
    """The prefix pool's arrays: `kv_blocks` blocks of rows."""
    d = config["deployment"]
    return d["kv_blocks"] * d["kv_block_size"] * kv_bytes_per_token(config)


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas kernels (attention's read
    and the experts' MLP, each once in the layers' loop body for the first
    lanes; the chunk program's further lanes run the experts' again); every
    instruction that materialises an array as large as a whole cache leaf
    and is none of `STATE_IN_PLACE` (a `copy`: there must be none); what it
    materialises of one sublayer's rows for all slots, which it must not;
    and what it materialises of the held experts' matrices (a layer's [E',
    d, F] or the whole stack's) or of a dense FFN's (one sublayer's [d, 2 F]
    or [F, d]), which it must not either (ROADMAP S12a)."""
    module, cfg, cache = _cache(config, config["deployment"]["max_batch"])
    copies, layer_copies = {}, {}
    for name in module.CACHE_TOKEN_AXIS:
        leaf = cache[name]
        shape = ",".join(str(n) for n in leaf.shape)
        copies[name] = sorted(
            op for op, _ in written_arrays(hlo, shape, "bf16")
            if op not in STATE_IN_PLACE)
        one = ",".join(str(n) for n in leaf.shape[1:])
        layer_copies[name] = sorted(op for op, _ in written_arrays(
            hlo, f"(?:1,)?{one}", "bf16"))
    D, F, Fd = cfg.d_model, cfg.d_ff_expert, cfg.d_ff
    held = (cfg.experts_held, cfg.experts_held * cfg.n_layer)
    experts = "|".join(f"{n},{a},{b}" for n in held
                       for a, b in ((D, F), (F, D)))
    dense = "|".join(f"(?:1,)?{a},{b}" for a, b in ((D, 2 * Fd), (Fd, D)))
    return {"kernels": hlo.count("tpu_custom_call"),
            "leaf_copies": {k: v for k, v in copies.items() if v},
            "sublayer_rows_copies": {k: v for k, v in layer_copies.items()
                                     if v},
            "expert_matrix_copies": sorted(
                op for op, _ in written_arrays(hlo, experts, "bf16")
                if op not in STATE_IN_PLACE),
            "dense_matrix_copies": sorted(
                op for op, _ in written_arrays(hlo, dense, "bf16")
                if op not in STATE_IN_PLACE)}


def main() -> None:
    from jax.experimental import topologies

    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--seq", type=int, default=0, help="max_seq_len")
    ap.add_argument("--programs", default="decode,prefill")
    ap.add_argument("--hlo", default="", help="a directory for the HLO text")
    args = ap.parse_args()
    chips = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    jax.default_backend = lambda: "tpu"     # the branches taken on the chip
    config = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                         CONFIG + ".json"))
    d = config["deployment"]
    if args.seq:
        d["max_seq_len"] = args.seq
    pool = pool_bytes(config)
    print(f"kv_bytes_per_token {kv_bytes_per_token(config)}; prefix pool: "
          f"{pool:,} bytes", flush=True)
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
                        args.hlo, f"longcat_{slots}_{program}_{C}.hlo"),
                        "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main()
