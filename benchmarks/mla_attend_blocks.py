#!/usr/bin/env python3
"""Once, on the chip: `ops/mla_attend.py` alone at the two serving cells'
shapes, the kernel at every block length against the plain form, the calls
one program's loop as the layers' loop is.

    chiprun -- python benchmarks/mla_attend_blocks.py [--calls 200]

Kimi's cell: 2 layers x 128 slots x 10,240 positions, the slots live at
4,200-9,300; Kanana's: 8 x 32 x 4,096, live at 2,100-3,650; and Kimi's with
4 slots of 128 live (the reference check's engine). A call's least time is
its attended positions' r + p = 576 bf16 values read once at the HBM's peak
(`benchmarks/chip/families/kanana.py` `mla_attend_cost`, which the cells'
`mla_attend_roofline_pct` divides by the scope's time).

The trade a block length makes: a grid step costs its own time, slots x T /
block of them a call whether the slot's position is reached or not; a slot
reads half a block past its position on average; and a longer block is a
longer first wait of every slot (the pipeline holds two).

Measured on a v5e (PR 41, 200 calls in one program; ms a call, the share of
the roofline, positions read over positions attended):

    block   Kimi 128 x 10,240      Kanana 32 x 4,096    Kimi, 4 of 128 live
    plain   5.028  24.4%  1.50     0.396  33.2%  1.40   5.028   0.7%
    256     2.821  43.4%  1.02     0.323  40.6%  1.05   0.799   4.6%
    512     1.992  61.5%  1.04     0.240  54.8%  1.09   0.428   8.6%
    1,024   1.673  73.2%  1.08     0.209  62.8%  1.22   0.246  15.1%
    1,280   1.676  73.1%  1.09                          0.216  17.1%
    2,048   1.766  69.4%  1.17     0.211  62.3%  1.40   0.166  22.3%
    2,560   1.784  68.7%  1.18                          0.149  24.8%
    4,096                          0.213  61.7%  1.40
    5,120   1.917  63.9%  1.37                          0.122  30.2%

The least are 1.225, 0.131 and 0.037 ms. A grid step that does nothing
costs 0.144 us (the last column: 5,120 steps against 1,280), one that works
~0.35. The kernel's result lies within 0.0024 of the plain form's where the
plain form's values have an r.m.s. of 0.09-0.11. `ops/mla_attend.BLOCK` is
1,024.

Writes `chiprun_out/mla_attend_blocks.json`. One process, which holds the
chip.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks", "chip")]

H, R, P = 32, 512, 64
SCALE = 1.0 / math.sqrt(128 + P)
SHAPES = {   # name: (layers, slots, T, live slots, positions from .. to)
    "kimi": (2, 128, 10240, 128, 4200, 9300),
    "kanana": (8, 32, 4096, 32, 2100, 3650),
    "kimi-check": (2, 128, 10240, 4, 4200, 9300),
}
BLOCKS = (256, 512, 1024, 1280, 2048, 2560, 4096, 5120)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from harness import spec

    op = importlib.import_module("ray_tpu.ops.mla_attend")
    out = {"device": jax.devices()[0].device_kind, "default_block": op.BLOCK}
    peak = spec.peaks()[out["device"]]["hbm_bytes_per_s"]
    bf = jnp.bfloat16
    for name in args.shapes.split(","):
        L, B, T, n_live, lo, hi = SHAPES[name]
        ks = jax.random.split(jax.random.key(0), 4)
        q_abs = jax.random.normal(ks[0], (B, H, R), jnp.float32).astype(bf)
        q_r = jax.random.normal(ks[1], (B, H, P), jnp.float32).astype(bf)
        lat = jax.random.normal(ks[2], (L, B, T, R), bf)
        kr = jax.random.normal(ks[3], (L, B, T, P), bf)
        rng = np.random.default_rng(0)
        pos = jnp.asarray(rng.integers(lo, hi, size=B), jnp.int32)
        live = jnp.asarray(np.arange(B) % (B // n_live) == 0)
        attended = int(jnp.sum(jnp.where(live, pos + 1, 0)))
        least = attended * (R + P) * 2 / peak
        rows = {}
        forms = [("plain", None)] + [
            (str(b), b) for b in BLOCKS if b <= T and T % b == 0]
        want = None
        for label, block in forms:
            if block is None:
                fn = functools.partial(op.mla_attend, kernel=False)
            else:
                fn = lambda *a, block=block: op._attend_kernel(  # noqa: E731
                    *a, block, False)

            # the calls are one program's loop, as the layers' loop is (a
            # call dispatched alone costs the host 0.6 ms, more than
            # Kanana's takes), and the leaves are its arguments (closed
            # over they are 3 GB of constants in every program)
            def calls(lat, kr, n, fn=fn):
                return lax.fori_loop(0, n, lambda i, _: fn(
                    q_abs, q_r, lat, kr, i % L, pos, live, SCALE),
                    jnp.zeros((B, H, R), jnp.float32))

            step = functools.partial(jax.jit(calls), lat, kr)
            try:
                got = jax.block_until_ready(step(L))
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                rows[label] = {"refused": str(e)[:300]}
                continue
            t0 = time.perf_counter()
            jax.block_until_ready(step(args.calls))
            seconds = (time.perf_counter() - t0) / args.calls
            got = np.asarray(got)[np.asarray(live)]
            if want is None:
                want = got
            read = (attended if block is None else int(jnp.sum(jnp.where(
                live, (pos // block + 1) * block, 0))))
            rows[label] = {
                "ms_a_call": seconds * 1e3,
                "roofline_pct": 100 * least / seconds,
                "read_over_attended": (B * T if block is None else read)
                / attended,
                "grid_steps": 0 if block is None else B * (T // block),
                "max_abs_from_plain": float(np.abs(got - want).max()),
                "plain_rms": float(np.sqrt(np.mean(want * want)))}
            print(name, label, json.dumps(rows[label]), flush=True)
        out[name] = {"layers": L, "slots": B, "T": T, "live": n_live,
                     "attended_positions": attended,
                     "least_ms": least * 1e3, "forms": rows}
        del lat, kr
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "mla_attend_blocks.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
