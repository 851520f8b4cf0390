#!/usr/bin/env python3
"""Once, on the chip: `ops/mla_attend.py` alone at the two serving cells'
shapes, the kernel at every block length against the plain form, the calls
one program's loop as the layers' loop is.

    chiprun -- python benchmarks/mla_attend_blocks.py [--calls 200]

Kimi's cell: 2 layers x 128 slots x 10,240 positions, the slots live at
4,200-9,300; Kanana's: 8 x 32 x 4,096, live at 2,100-3,650; Kimi's with
4 slots of 128 live (the reference check's engine); and LongCat's, 8 x 128 x
3,072 at 64 heads where the others have 32. A call's least time is
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
plain form's values have an r.m.s. of 0.09-0.11. `ops/slot_rows.BLOCK` is
1,024.

LongCat's cell (PR 55, `--shapes longcat`: 8 sublayers x 128 slots x 3,072
positions, live at 1,100-2,900, 64 heads; 100 calls; least 0.364 ms, the
rows' bytes: at 120 operations a byte the products are still under them):

    block   ms a call   roofline   read / attended
    plain   1.514       24.0%      1.52
    256     0.989       36.8%      1.06
    512     0.755       48.2%      1.14
    1,024   0.674       54.0%      1.28   (`_block(3072)`: the op's own)
    1,536   0.635       57.2%      1.36
    3,072   0.643       56.6%      1.52

At 64 heads a grid step that works costs ~1.4 us where 32 heads' costs 0.35
(`[64, 1024]` float32 scores, their exponentials and a `[64, 512]`
accumulator on the VPU beside two products of 64 rows): the kernel's time
is the steps' own work, not the rows' DMA, and a longer block buys 6% for a
third more rows read. `BLOCK` stays 1,024 for every caller (ROADMAP S24).

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

R, P = 512, 64
SCALE = 1.0 / math.sqrt(128 + P)
SHAPES = {   # name: (layers, slots, T, live slots, positions from .. to, H)
    "kimi": (2, 128, 10240, 128, 4200, 9300, 32),
    "kanana": (8, 32, 4096, 32, 2100, 3650, 32),
    "kimi-check": (2, 128, 10240, 4, 4200, 9300, 32),
    # LongCat's cell (PR 55): 8 sublayers x 128 slots x 3,072, live at
    # 1,100-2,900, 64 heads: `[64, block]` float32 scores and a `[64, 512]`
    # accumulator a grid step, 120 operations a byte where Kanana's are 60
    # and the chip's ridge is 240 (the table is in PERF.md section 6)
    "longcat": (8, 128, 3072, 128, 1100, 2900, 64),
}
BLOCKS = (256, 512, 1024, 1280, 1536, 2048, 2560, 3072, 4096, 5120)


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

    from ray_tpu.ops import slot_rows

    op = importlib.import_module("ray_tpu.ops.mla_attend")
    out = {"device": jax.devices()[0].device_kind,
           "default_block": slot_rows.BLOCK}
    peaks = spec.peaks()[out["device"]]
    bf = jnp.bfloat16
    for name in args.shapes.split(","):
        L, B, T, n_live, lo, hi, H = SHAPES[name]
        ks = jax.random.split(jax.random.key(0), 4)
        q_abs = jax.random.normal(ks[0], (B, H, R), jnp.float32).astype(bf)
        q_r = jax.random.normal(ks[1], (B, H, P), jnp.float32).astype(bf)
        lat = jax.random.normal(ks[2], (L, B, T, R), bf)
        kr = jax.random.normal(ks[3], (L, B, T, P), bf)
        rng = np.random.default_rng(0)
        pos = jnp.asarray(rng.integers(lo, hi, size=B), jnp.int32)
        live = jnp.asarray(np.arange(B) % (B // n_live) == 0)
        attended = int(jnp.sum(jnp.where(live, pos + 1, 0)))
        # `mla_attend_cost`: the rows' bytes, or the absorbed form's
        # operations, whichever bounds (the bytes, at 32 heads and at 64)
        least = attended * max(
            (R + P) * 2 / peaks["hbm_bytes_per_s"],
            2.0 * H * (2 * R + P) / peaks["bf16_flops_per_s"])
        rows = {}
        forms = [("plain", None)] + [
            (str(b), b) for b in BLOCKS if b <= T and T % b == 0]
        want = None
        for label, block in forms:
            if block is None:
                fn = functools.partial(op.mla_attend, kernel=False)
            else:
                def fn(q_abs, q_r, lat, kr, layer, pos, live, scale,
                       block=block):
                    return slot_rows.attend(
                        op.rows_kernel(q_abs, q_r, lat, kr, scale), layer,
                        pos, live, block=block)

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
