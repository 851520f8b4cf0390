"""Flash-vs-dense attention crossover on REAL TPU hardware.

Times fwd+bwd of `ray_tpu.ops.flash_attention` against the dense XLA
attention (the same math the models' attn_impl="dense" path runs) across
sequence lengths, at GPT-2-class head geometry. Refuses to run without a
TPU, prints one JSON line per cell and writes
chiprun_out/FLASH_CROSSOVER.json (never committed: it is a run's output).

Run:  python benchmarks/flash_crossover.py            # real chip
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def dense_attention(q, k, v):
    """The models' attn_impl='dense' math (XLA-fused)."""
    Dh = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(Dh)
    T = q.shape[2]
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def bench_impl(fn, q, k, v, iters=10):
    def loss(q, k, v):
        return fn(q, k, v).astype(jnp.float32).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(step(q, k, v))    # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        g = step(q, k, v)
    jax.block_until_ready(g)
    return (time.perf_counter() - t0) / iters


def main():
    from ray_tpu.ops.flash_attention import flash_attention

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"flash_crossover.py measures the TPU and found platform "
                 f"{device.platform!r}: no number is produced")
    B, H, Dh = 4, 12, 64
    results = {}
    for T in (512, 1024, 2048, 4096):
        rng = np.random.default_rng(0)
        shape = (B, H, T, Dh)
        q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        cell = {
            "dense": round(bench_impl(dense_attention, q, k, v) * 1e3, 3),
            "flash": round(bench_impl(
                lambda q, k, v: flash_attention(q, k, v, True),
                q, k, v) * 1e3, 3)}
        cell["flash_speedup"] = round(cell["dense"] / cell["flash"], 3)
        results[f"T{T}"] = cell
        print(json.dumps({f"T{T}": cell}), flush=True)
    out = {
        "metric": "flash_vs_dense_fwd_bwd_ms",
        "geometry": {"B": B, "H": H, "head_dim": Dh,
                     "dtype": "bfloat16"},
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "results": results,
    }
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "FLASH_CROSSOVER.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"written": path}))


if __name__ == "__main__":
    main()
