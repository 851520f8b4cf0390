#!/usr/bin/env python3
"""Once, on the chip: the delta-rule state-update kernel alone at the Kimi
cell's shape (7 layers x 128 slots x 32 heads of [128, 128] float32), calls
dispatched back to back as the layers' loop dispatches them (a barrier a
call adds the host's round trip: `retention_on_chip.py`), against the same
arithmetic in plain XLA, with the bytes the roofline counts.

    python benchmarks/chip/rehearse/kda_on_chip.py [--slots 128] [--calls 70]

Writes `chiprun_out/kda_on_chip.json`. One process, which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--layers", type=int, default=7)
    ap.add_argument("--calls", type=int, default=70)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import spec
    from ray_tpu.ops.kda_update import kda_update

    L, B, H, N = args.layers, args.slots, 32, 128
    ks = jax.random.split(jax.random.key(0), 7)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    a = jax.nn.sigmoid(jax.random.normal(ks[1], (B, H, N)) + 3.0)
    k, q = (unit(jax.random.normal(ks[i], (B, H, N))) for i in (2, 3))
    v = jax.random.normal(ks[4], (B, H, N))
    b = jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    on = jnp.ones((B,), bool)
    out = {"device": jax.devices()[0].device_kind, "slots": B, "layers": L}
    peak = spec.peaks()[out["device"]]["hbm_bytes_per_s"]
    least = B * H * N * N * 4 * 2 / peak
    for name, kernel in (("kernel", True), ("plain", False)):
        step = jax.jit(lambda s, l, kernel=kernel: kda_update(
            s, l, a, k, q, v, b, on, kernel=kernel), donate_argnums=(0,))
        state = jax.random.normal(ks[0], (L, B, H, N, N))
        state, o = step(state, jnp.int32(0))
        jax.block_until_ready(o)
        t0 = time.perf_counter()
        for i in range(args.calls):
            state, o = step(state, jnp.int32(i % L))
        jax.block_until_ready((state, o))
        seconds = (time.perf_counter() - t0) / args.calls
        out[name] = {"ms_a_call": seconds * 1e3,
                     "state_roofline_pct": 100 * least / seconds,
                     "o_rms": float(np.std(np.asarray(o)))}
        del state
    print(json.dumps(out), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "kda_on_chip.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
