#!/usr/bin/env python3
"""On the chip: the unembedding and loss, forward + backward, at the
training cells' per-device shapes, for the parent commit's way and for the
fused loss at every chunk the budget could give. `models/lm.py`'s
LOGITS_CHUNK_BYTES and the table in `chunked_cross_entropy`'s docstring are
read off this (PERF.md §6, PR 34). Refuses to run without a TPU, prints one
JSON line a measurement and writes chiprun_out/LOSS_CROSSOVER.json (a run's
output, never committed).

    chiprun -- python benchmarks/loss_crossover.py [--parent .scratch/parent]
        [--shapes small-1k,xl-1k,olmoe-4k] [--chunks 1,2,4,8,16]

What is timed is `jax.value_and_grad` of the loss with respect to the final
hidden state x `[B,T,D]` (bf16) and the float32 weight the head is made
from, cast to bf16 inside as the families' `_w` does: the embedding table
`[V,D]` transposed where the model ties it (GPT-2), a `[D,V]` matrix of its
own where it does not (OLMoE). The final norm is left out.

  parent   the parent commit's `models/lm.py`, called as its families call
           it: `cross_entropy(x @ head, targets)` on whole logits for GPT-2,
           `chunked_cross_entropy(x, head, targets, 1024)` for OLMoE
           (left out when `--parent` is not a checkout)
  fused@K  this tree's `chunked_cross_entropy` with the budget set so that
           the sequence is taken in K chunks; `fused` is the K the kept
           budget gives

`pct_peak` charges three passes of the head, 6 B T D V operations, against
the chip's bf16 peak (197 TFLOP/s): what the mathematics needs, whatever the
implementation runs.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]

import jax
import jax.numpy as jnp

from flash_crossover import BF16_PEAK_FLOPS, timed_ms

VOCAB = 50304
SHAPES = {                          # name: (B, T, D, tied) on one device
    "small-1k": (20, 1024, 768, True),      # train-small-1k
    "xl-1k": (8, 1024, 1600, True),         # train-xl-fsdp4-1k, a chip's share
    "olmoe-4k": (8, 4096, 2048, False),     # train-olmoe-4k
}


def parent_loss(parent: str, tied: bool):
    path = os.path.join(parent, "ray_tpu", "models", "lm.py")
    spec = importlib.util.spec_from_file_location("parent_lm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if tied:
        return lambda x, head, t: mod.cross_entropy(x @ head, t)
    return lambda x, head, t: mod.chunked_cross_entropy(x, head, t, 1024)


def fused_loss(shape, chunks):
    """This tree's loss; `chunks` None keeps the module's budget."""
    from ray_tpu.models import lm

    B, T, _, _ = shape
    kept = lm.LOGITS_CHUNK_BYTES
    budget = kept if chunks is None else B * T * VOCAB * 4 // chunks

    def fn(x, head, t):
        lm.LOGITS_CHUNK_BYTES = budget
        try:        # read while tracing: the chunk is fixed at compile time
            return lm.chunked_cross_entropy(x, head, t)
        finally:
            lm.LOGITS_CHUNK_BYTES = kept

    return fn


def measure(loss, shape, seed: int = 0) -> dict:
    B, T, D, tied = shape
    kx, kw, kt = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(kx, (B, T, D), jnp.bfloat16)
    w = 0.02 * jax.random.normal(kw, (VOCAB, D) if tied else (D, VOCAB),
                                 jnp.float32)
    t = jax.random.randint(kt, (B, T), 0, VOCAB)

    def f(x, w):
        return loss(x, (w.T if tied else w).astype(jnp.bfloat16), t)

    both = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
    ms = timed_ms(both, (x, w), budget_s=1.0)
    value = float(both(x, w)[0])
    mem = both.lower(x, w).compile().memory_analysis()
    return {"fwd_bwd_ms": ms, "loss": value,
            "pct_peak": 6.0 * B * T * D * VOCAB / BF16_PEAK_FLOPS
            / (ms / 1e3) * 100,
            "temp_gb": mem.temp_size_in_bytes / 1e9}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(REPO, ".scratch",
                                                     "parent"))
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--chunks", default="1,2,4,8,16")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU; found {dev.platform} ({dev.device_kind})")
    rows = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        impls = {}
        if os.path.isdir(args.parent):
            impls["parent"] = lambda: parent_loss(args.parent, shape[3])
        impls["fused"] = lambda: fused_loss(shape, None)
        for k in (int(c) for c in args.chunks.split(",") if c):
            impls[f"fused@{k}"] = lambda k=k: fused_loss(shape, k)
        for impl, build in impls.items():
            row = {"shape": name, "B_T_D": shape[:3], "tied": shape[3],
                   "impl": impl, "device": dev.device_kind}
            try:
                row.update(measure(build(), shape))
            except Exception as e:  # noqa: BLE001 - does not fit, say
                row["error"] = str(e).splitlines()[0][:300]
            print(json.dumps(row), flush=True)
            rows.append(row)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "LOSS_CROSSOVER.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
