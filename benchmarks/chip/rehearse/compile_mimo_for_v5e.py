#!/usr/bin/env python3
"""Rehearsal without the chip: the MiMo serving cell's two step programs
at the configuration's sizes, compiled by the TPU's compiler for a described
`v5e:2x2` (`compile_nemotron_for_v5e.py`'s method). Nothing runs; what it
prints are `memory_analysis()` bytes and what the compiled programs are made
of. It decides `max_batch` and `kv_blocks`, and shows that neither program
holds a second copy of a cache leaf (rows or rings), copies an expert matrix
or the dense MLP's out of its stack, or writes a chunk's scores over all of a
slot's positions.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/compile_mimo_for_v5e.py \
        [--slots 64,48] [--chunks 128] [--hlo DIR]

A script, not a test: `tests/test_tpu_compile_mimo.py` imports
`compile_step` and `made_of` and holds the configuration file's bytes to
them.
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
# the two programs of any family `serving_family` knows, by the file's
# preset, and the bytes of a cache of both kinds and of its pool
from compile_nemotron_for_v5e import (_cache, cache_bytes,  # noqa: E402,F401
                                      compile_step, pool_bytes)
from harness import spec  # noqa: E402

CONFIG = "mimo-v2.5-serve-1chip"


def made_of(hlo: str, config: dict) -> dict:
    """What the compiled program holds: the Pallas kernels (the rows' and
    the rings' write, attention's read of either, the experts' MLP); every
    instruction that materialises an array as large as a whole cache leaf,
    rows or rings, and is none of `STATE_IN_PLACE` (a `copy`: there must be
    none); what it materialises of one layer's rows or rings for all slots,
    which it must not; what it materialises of the held experts' matrices
    (a layer's [E', d, F] or the whole stack's) or of the dense MLP's ([d, 2
    F] or [F, d]), which it must not either (ROADMAP S12a); and the float32
    arrays as large as one slot's scores over all T positions for a chunk's
    4 x 16 x 128 queries, which the further lanes' loop over blocks must not
    make; and `padded`: the leaves whose bytes in the compiled program's
    layout are more than their elements' (a bf16 `[.., T, 192]` would be
    tiled to 256 lanes), of which there must be none."""
    module, cfg, cache = _cache(config, config["deployment"]["max_batch"])
    copies, layer_copies = {}, {}
    for name in list(module.CACHE_STATE) + list(module.CACHE_TOKEN_AXIS):
        leaf = cache[name]
        shape = ",".join(str(n) for n in leaf.shape)
        copies[name] = sorted(
            op for op, _ in written_arrays(hlo, shape, "bf16")
            if op not in STATE_IN_PLACE)
        one = ",".join(str(n) for n in leaf.shape[1:])
        layer_copies[name] = sorted(op for op, _ in written_arrays(
            hlo, f"(?:1,)?{one}", "bf16"))
    D, F, Fd = cfg.d_model, cfg.d_ff_expert, cfg.d_ff
    sparse = cfg.n_layer - cfg.n_dense_layer
    held = (cfg.experts_held, cfg.experts_held * sparse)
    experts = "|".join(f"{n},{a},{b}" for n in held
                       for a, b in ((D, F), (F, D)))
    dense = "|".join(f"(?:1,)?{a},{b}" for a, b in ((D, 2 * Fd), (Fd, D)))
    d = config["deployment"]
    queries = cfg.n_head // cfg.n_kv_head * d["prefill_chunk_size"]
    scores = f"{cfg.n_kv_head},(?:{queries}|{2 * queries}),{d['max_seq_len']}"
    return {"kernels": hlo.count("tpu_custom_call"),
            "whole_slot_scores": sorted(op for op, _ in written_arrays(
                hlo, scores, "f32")),
            "leaf_copies": {k: v for k, v in copies.items() if v},
            "layer_copies": {k: v for k, v in layer_copies.items() if v},
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
    chunks = [int(c) for c in args.chunks.split(",") if c] or [
        d["prefill_chunk_size"]]
    programs = [("decode", 0)] * ("decode" in args.programs) + [
        ("prefill", c) for c in chunks if "prefill" in args.programs]
    for slots in [int(s) for s in args.slots.split(",") if s] or [
            d["max_batch"]]:
        d["max_batch"] = slots
        pool = pool_bytes(config)
        print(f"{cache_bytes(config)}; prefix pool: {pool:,} bytes",
              flush=True)
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
                        args.hlo, f"mimo_{slots}_{program}_{C}.hlo"),
                        "w") as f:
                    f.write(compiled.as_text())


if __name__ == "__main__":
    main()
