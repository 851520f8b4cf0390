#!/usr/bin/env python3
"""On the chip: causal attention, forward and forward + backward, in bf16,
at the training cells' per-device shapes and around them, for every
implementation the repo could call. `ops/flash_attention.py`'s tiles and
`models/lm.resolve_attn_impl`'s rule are read off this table (PERF.md §6,
PR 31). Refuses to run without a TPU, prints one JSON line a measurement
and writes chiprun_out/FLASH_CROSSOVER.{json,md} (a run's output, never
committed).

    chiprun -- python benchmarks/flash_crossover.py [--parent .scratch/parent]
        [--sweep 1] [--shapes small-1k,xl-1k,olmoe-4k,t128,t256,t512,t2048]
        [--impls kept,splash]

Implementations:
  dense      XLA's path as `models/gpt2._attention` writes it: [B,H,T,T]
             scores in float32, masked, softmaxed, rounded to bf16
  first      the repo's first kernel, loaded from a checkout of the parent
             commit (`--parent`; left out when the directory is not there):
             float32 operands, 128 x 128 blocks, a head's K/V whole in VMEM
  kept@128/128  the kept kernel at tiles of 128, computed whole: what bf16
             operands and tiled K/V give at the first kernel's block size
  kept       the kept kernel at the tiles it chooses from the shape
  kept@A/B   (--sweep 1) the kept kernel at tiles of A rows of q and of
             k/v a program, scores computed B x B at a time
  jax-flash  jax.experimental.pallas.ops.tpu.flash_attention, its default
             128 blocks and 512 blocks
  splash     jax.experimental.pallas.ops.tpu.splash_attention, 512 blocks,
             its two backward kernels and its fused one

`pct_peak` is the share of the chip's bf16 peak (197 TFLOP/s) at the FLOPs a
causal kernel with 128-wide blocks executes: n(n+1)/2 block pairs a head,
4 * 128 * 128 * Dh forward and 18 * 128 * 128 * Dh forward + backward, the
count `benchmarks/chip/families/olmoe.flash_attention_cost` keeps. The dense
path executes the whole square and is charged the same.

Measured on a TPU v5e (my chip runs, PR 31; ms forward, ms forward +
backward, % of the bf16 peak forward + backward):

                          small-1k           xl-1k              olmoe-4k
                          B20 H12 T1024 Dh64 B8 H25 T1024 Dh64  B8 H16 T4096 Dh128
  implementation          fwd   f+b     %    fwd   f+b     %    fwd    f+b     %
  XLA dense               3.08  10.03   8.2  2.54   8.19   8.4  does not fit the chip
  first kernel            4.04  12.06   6.9  3.29   9.81   7.0  25.95  78.11  16.6
  kept, tiles 128/128     6.86  17.74   4.7  5.72  14.74   4.7  48.29 127.25  10.2
  kept, tiles chosen      1.38   3.68  22.5  1.15   3.06  22.5   4.21  15.27  84.8
  jax flash_attention 128 5.82  21.59   3.8  4.09  17.60   3.9  41.43 150.28   8.6
  jax flash_attention 512 1.49   7.54  11.0  1.05   6.29  11.0   5.99  31.17  41.5
  splash_attention 512    1.73   5.35  15.5  1.21   4.04  17.1   6.99  26.38  49.1
  splash 512, fused bwd   1.73   4.26  19.4  1.21   3.40  20.3   6.99  21.45  60.4

  GPT-2 small's heads (H12 Dh64) at 20,480 tokens, f+b ms, dense / kept:
  T=128 1.20 / 4.56, T=256 2.65 / 3.63, T=512 5.13 / 3.15, T=1024 10.03 /
  3.68, T=2048 19.16 / 5.13: the crossover lies between 256 and 512.

  The kept kernel's tiles (`block`/`sub_block`), f+b ms at small-1k |
  olmoe-4k: 512/128 4.89 | 26.74, 1024/128 3.68 | 19.83, 1024/256 3.88 |
  18.82, 1024/1024 (a tile's scores whole, nothing skipped inside it) 4.53 |
  20.13, 2048/256 - | 21.71 (2048/128 17.15, 2048/512 17.19), 4096/256 - |
  15.27; at T=2048: 1024/256 5.73, 2048/128 5.22, 2048/256 5.13. An earlier
  form with non-square tiles and no skipping inside a tile: 128x128 18.30 |
  134.85, 256x256 8.50 | 53.11, 512x512 5.07 | 26.12. A grid step costs
  more than the causal work a narrower tile skips, at every shape.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

BF16_PEAK_FLOPS = 197e12            # benchmarks/chip/peaks.json, "TPU v5 lite"
SHAPES = {                          # name: (B, H, T, Dh) on one device
    "small-1k": (20, 12, 1024, 64),     # train-small-1k
    "xl-1k": (8, 25, 1024, 64),         # train-xl-fsdp4-1k, a device's share
    "olmoe-4k": (8, 16, 4096, 128),     # train-olmoe-4k
    "t128": (160, 12, 128, 64),         # GPT-2 small's heads, the same tokens
    "t256": (80, 12, 256, 64),
    "t512": (40, 12, 512, 64),
    "t2048": (10, 12, 2048, 64),
}
SWEEP_TILES = ((512, 128), (512, 256), (512, 512), (1024, 128), (1024, 256),
               (1024, 512), (1024, 1024), (2048, 128), (2048, 256),
               (2048, 512), (4096, 256))


def dense_attention(q, k, v):
    """`models/gpt2._attention`'s dense branch, line for line."""
    T, Dh = q.shape[2], q.shape[3]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(Dh)
    causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
    scores = jnp.where(causal[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


def kernel_flops(shape, products: int) -> float:
    B, H, T, Dh = shape
    n = T // 128
    return B * H * (n * (n + 1) // 2) * products * 2.0 * 128 * 128 * Dh


def timed_ms(fn, args, budget_s: float = 0.15) -> float:
    """Median of three timed loops (one alone read twice its neighbours
    now and then: a machine's stall, not the kernel)."""
    jax.block_until_ready(fn(*args))            # compiles
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    once = time.perf_counter() - t0
    n = max(3, min(30, int(budget_s / max(once, 1e-5))))
    loops = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        loops.append((time.perf_counter() - t0) / n * 1e3)
    return sorted(loops)[1]


def measure(fn, qkv) -> dict:
    fwd = jax.jit(fn)
    both = jax.jit(jax.grad(
        lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    return {"fwd_ms": timed_ms(fwd, qkv), "fwd_bwd_ms": timed_ms(both, qkv)}


def implementations(shape, parent: str, sweep: bool) -> dict:
    """name -> fn(q, k, v), built lazily: one that cannot be built is
    reported, not fatal."""
    B, H, T, Dh = shape
    scale = 1.0 / math.sqrt(Dh)
    kept = importlib.import_module("ray_tpu.ops.flash_attention")

    def at(block, sub):
        return lambda q, k, v: kept.flash_attention(q, k, v, True, None,
                                                    block, sub)

    def first():
        path = os.path.join(parent, "ray_tpu", "ops", "flash_attention.py")
        spec = importlib.util.spec_from_file_location("first_flash", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return lambda q, k, v: mod.flash_attention(q, k, v, True)

    def jax_flash(block):
        from jax.experimental.pallas.ops.tpu import flash_attention as jf

        b = min(block, T)
        sizes = jf.BlockSizes(
            block_q=b, block_k_major=b, block_k=b, block_b=1,
            block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
            block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
        return lambda q, k, v: jf.flash_attention(
            q, k, v, causal=True, sm_scale=scale, block_sizes=sizes)

    def splash(fused):
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk, splash_attention_mask as sm)

        b = min(512, T)
        sizes = sk.BlockSizes(
            block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
            block_kv_dkv=b, block_kv_dkv_compute=b,
            **({"use_fused_bwd_kernel": True} if fused
               else {"block_q_dq": b, "block_kv_dq": b}))
        call = sk.make_splash_mha_single_device(
            mask=sm.MultiHeadMask([sm.CausalMask((T, T))] * H),
            block_sizes=sizes)
        # splash takes no scale: q carries it (timing only)
        return lambda q, k, v: jax.vmap(call)(
            (q * scale).astype(q.dtype), k, v)

    impls = {"dense": lambda: dense_attention}
    if os.path.isdir(parent):
        impls["first"] = first
    impls["kept@128/128"] = lambda: at(128, 128)
    impls["kept"] = lambda: at(None, None)
    if sweep:
        for block, sub in SWEEP_TILES:
            if T % block == 0:
                impls[f"kept@{block}/{sub}"] = \
                    lambda block=block, sub=sub: at(block, sub)
    impls["jax-flash@128"] = lambda: jax_flash(128)
    impls["jax-flash@512"] = lambda: jax_flash(512)
    impls["splash@512"] = lambda: splash(False)
    impls["splash@512-fused-bwd"] = lambda: splash(True)
    return impls


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=os.path.join(REPO, ".scratch",
                                                     "parent"))
    ap.add_argument("--sweep", type=int, default=0)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--impls", default="",
                    help="only implementations whose name starts with one "
                         "of these, comma-separated")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"flash_crossover.py measures the TPU and found platform "
                 f"{device.platform!r}: no number is produced")
    kept = importlib.import_module("ray_tpu.ops.flash_attention")
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for name in args.shapes.split(","):
        shape = SHAPES[name]
        keys = jax.random.split(jax.random.key(0), 3)
        qkv = tuple(jax.random.normal(k, shape, jnp.bfloat16) for k in keys)
        for impl, build in implementations(shape, args.parent,
                                           bool(args.sweep)).items():
            if args.impls and not impl.startswith(tuple(
                    args.impls.split(","))):
                continue
            row = {"shape": name, "B_H_T_Dh": list(shape), "impl": impl}
            if impl == "kept":
                row["tiles"] = list(kept.block_sizes(shape[2], shape[2],
                                                     shape[3]))
            try:
                row.update(measure(build(), qkv))
                row["bwd_ms"] = row["fwd_bwd_ms"] - row["fwd_ms"]
                row["pct_peak_fwd"] = 100 * kernel_flops(shape, 2) / (
                    row["fwd_ms"] / 1e3) / BF16_PEAK_FLOPS
                row["pct_peak"] = 100 * kernel_flops(shape, 9) / (
                    row["fwd_bwd_ms"] / 1e3) / BF16_PEAK_FLOPS
            except Exception as e:  # noqa: BLE001 - a refusal is a result
                row["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
            with open(os.path.join(out_dir, "FLASH_CROSSOVER.json"),
                      "w") as f:
                json.dump({"device": {"platform": device.platform,
                                      "kind": device.device_kind,
                                      "count": len(jax.devices())},
                           "rows": rows}, f, indent=1)
    lines = ["| shape (B,H,T,Dh) | implementation | fwd ms | fwd+bwd ms "
             "| % of bf16 peak |", "| --- | --- | --- | --- | --- |"]
    for r in rows:
        where = f"{r['shape']} {tuple(r['B_H_T_Dh'])}"
        if "refused" in r:
            lines.append(f"| {where} | {r['impl']} | refused: "
                         f"{r['refused'][:80]} | | |")
        else:
            lines.append(f"| {where} | {r['impl']} | {r['fwd_ms']:.2f} | "
                         f"{r['fwd_bwd_ms']:.2f} | {r['pct_peak']:.1f} |")
    with open(os.path.join(out_dir, "FLASH_CROSSOVER.md"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines))


if __name__ == "__main__":
    main()
