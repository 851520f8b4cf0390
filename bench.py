"""Headline benchmark: GPT-2-125M SPMD training throughput per chip.

Runs on a TPU only: one process drives every chip `jax.devices()` reports,
and the script refuses to run on any other backend. Prints the device, then
ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device", "extra"}.

Baseline: the reference publishes no in-repo number for its north-star config
("Ray Train GPT-2 DDP tokens/sec/chip", BASELINE.md "Gaps" section). We use
the public NCCL/A100 equivalent — GPT-2-124M torch DDP on A100-40GB sustains
~60k tokens/s/GPU (nanoGPT-class training, bf16, flash attention) — as the
per-chip baseline the north star asks us to match on TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BASELINE_TOKENS_PER_SEC_PER_CHIP = 60_000.0

# Peak bf16 FLOP/s of one chip, keyed by JAX's `device_kind`. Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per chip). A kind that is
# not listed is an error, never a default.
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def main():
    from ray_tpu.utils.platform import device_report, enable_compile_cache

    enable_compile_cache()
    import jax

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"bench device: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"bench.py measures the TPU and found platform "
                 f"{device['platform']!r}: no number is produced")
    if device["kind"] not in PEAK_BF16_FLOPS:
        sys.exit(f"bench.py has no published peak for device kind "
                 f"{device['kind']!r}; add it to PEAK_BF16_FLOPS with its "
                 f"source")
    n = len(devices)
    mesh = build_mesh(MeshConfig(dp=n), devices=devices)

    preset = os.environ.get("BENCH_PRESET", "gpt2-125m")
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))
    # per-chip batch per preset: remat "dots" with the batch that fits the
    # v5e's 15.75 GiB (125M: 24 needs 16.1 GiB under jax 0.9.0, 20 fits —
    # chip_smoke.py's train phase prints the memory analysis that decides)
    default_batch = {"gpt2-125m": 20, "gpt2-350m": 14,
                     "gpt2-774m": 4, "gpt2-1.5b": 2}.get(preset, 8)
    per_chip_batch = int(os.environ.get("BENCH_BATCH", str(default_batch)))
    batch = per_chip_batch * n
    cfg = gpt2.GPT2Config.preset(
        preset, max_seq_len=seq_len,
        remat=os.environ.get("BENCH_REMAT", "1") != "0",
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", "dots"),
        attn_impl=os.environ.get("BENCH_ATTN", "auto"),
        ce_chunk=int(os.environ.get("BENCH_CE_CHUNK", "0")))

    train = compile_gpt2_train(cfg, mesh, optimizer=default_optimizer(total_steps=100))
    state = train.init_fn(jax.random.key(0))

    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (batch, seq_len + 1), dtype=np.int32),
        train.batch_sharding)
    data = {"tokens": tokens}

    # warmup / compile
    for _ in range(3):
        state, metrics = train.step_fn(state, data)
    jax.block_until_ready(metrics)

    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = train.step_fn(state, data)
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0
    loss_val = float(metrics["loss"])

    tokens_per_step = batch * seq_len
    tps_per_chip = tokens_per_step * iters / dt / n
    mfu = (gpt2.flops_per_token(cfg, seq_len) * tps_per_chip
           / PEAK_BF16_FLOPS[device["kind"]])

    print(json.dumps({
        "metric": f"{preset.replace('-', '_').replace('.', '_')}"
                  f"_train_tokens_per_sec_per_chip",
        "value": round(tps_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tps_per_chip / BASELINE_TOKENS_PER_SEC_PER_CHIP, 3)
        if preset == "gpt2-125m" else None,
        "device": device,
        "extra": {"n_chips": n, "seq_len": seq_len, "per_chip_batch": per_chip_batch,
                  "preset": preset,
                  "step_ms": round(dt / iters * 1e3, 2), "approx_mfu": round(mfu, 3),
                  "loss": loss_val,
                  "peak_hbm_bytes": device_report()[0]["peak_bytes_in_use"]},
    }))


if __name__ == "__main__":
    main()
