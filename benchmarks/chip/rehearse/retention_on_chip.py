#!/usr/bin/env python3
"""On the chip: the one-token retention update (`ops/power_retention.py`'s
Pallas kernel) at the Brumby cell's sizes, timed a layer's call and against
the plain-XLA form of the same arithmetic on a smaller state.

    chiprun -- python benchmarks/chip/rehearse/retention_on_chip.py \
        [--slots 16] [--layers 8] [--precisions HIGHEST,DEFAULT]

Prints, for each precision of the read-out's products, the milliseconds one
layer's call takes (the mean of 20 dispatched back to back, the state
donated), the bytes it must move (`families/brumby.py`'s
`retention_update_cost`) and their share of the chip's HBM peak.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [p for p in (os.path.dirname(os.path.dirname(CHIP_DIR)),
                            CHIP_DIR) if p not in sys.path]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import power_retention as pr  # noqa: E402

HBM_BYTES_PER_S = 819e9


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def inputs(key, L, B, H, R, d):
    """state, norm, q [B,H,R,d], k [B,H,d], v, g."""
    W = pr.expanded_width(d)
    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (L, B, H, d, W), jnp.float32),
            jax.random.normal(ks[1], (L, B, H, W), jnp.float32),
            jax.random.normal(ks[3], (B, H, R, d)),
            jax.random.normal(ks[2], (B, H, d)),
            jax.random.normal(ks[4], (B, H, d)),
            jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)) + 4.0))


def per_call_ms(fn, state, norm, layers: int, rest: tuple, calls: int):
    """(milliseconds a call, state, norm): `calls` calls dispatched back to
    back behind a warm one, and one barrier; a barrier after every call
    would add the host's round trip to each."""
    state, norm, num, _ = fn(state, norm, jnp.int32(0), *rest)
    jax.block_until_ready(num)
    t0 = time.perf_counter()
    for i in range(calls):
        state, norm, num, _ = fn(state, norm, jnp.int32(i % layers), *rest)
    jax.block_until_ready(num)
    return (time.perf_counter() - t0) / calls * 1e3, state, norm


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--precisions", default="HIGHEST",
                    help="of the read-out's products: HIGHEST,HIGH,DEFAULT")
    args = ap.parse_args()
    print(jax.devices(), flush=True)
    H, R, d = 8, 5, 128

    # agreement with the plain form, one slot inactive
    state, norm, q, k, v, g = inputs(jax.random.key(0), 2, 4, H, R, d)
    active = jnp.array([1, 0, 1, 1])
    want = jax.jit(lambda *a: pr.retention_update(*a, kernel=False))(
        state, norm, jnp.int32(1), q, k, v, g, active)
    got = jax.jit(lambda *a: pr.retention_update(*a, kernel=True))(
        state, norm, jnp.int32(1), q, k, v, g, active)
    on = np.asarray(active, bool)
    for name, w, x in zip(("state", "norm", "num", "den"), want, got):
        w, x = np.asarray(w), np.asarray(x)
        if name in ("num", "den"):
            w, x = w[on], x[on]
        print(f"{name}: max |kernel - plain| {np.abs(w - x).max():.3e} of "
              f"{np.abs(w).max():.3e}", flush=True)
    print("inactive slot bit-identical:",
          bool((np.asarray(got[0])[1, 1] == np.asarray(state)[1, 1]).all()),
          flush=True)
    del state, norm, want, got

    L, B = args.layers, args.slots
    state, norm, q, k, v, g = inputs(jax.random.key(1), L, B, H, R, d)
    active = jnp.ones((B,), jnp.int32)
    W = pr.expanded_width(d)
    moved = 2 * B * H * (d * W + W) * 4
    plain = jax.jit(lambda s, z, l, *a: pr.retention_update(
        s, z, l, *a, kernel=False), donate_argnums=(0, 1))
    rest = (q, k, v, g, active)
    ms, state, norm = per_call_ms(plain, state, norm, L, rest, 5)
    print(f"plain XLA: {ms:.3f} ms a layer's call", flush=True)
    for precision in args.precisions.split(","):
        pr.READ_OUT_PRECISION = getattr(jax.lax.Precision, precision)
        fn = jax.jit(lambda s, z, l, *a: pr.retention_update(
            s, z, l, *a, kernel=True), donate_argnums=(0, 1))
        try:
            ms, state, norm = per_call_ms(fn, state, norm, L, rest, 20)
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            print(f"{precision}: refused: {str(e)[:300]}", flush=True)
            continue
        print(f"{precision}: {ms:.3f} ms a layer's call, {moved / 1e9:.3f} "
              f"GB of state read and written, "
              f"{100 * moved / (ms / 1e3) / HBM_BYTES_PER_S:.1f}% of "
              f"819 GB/s", flush=True)


if __name__ == "__main__":
    main()
