#!/usr/bin/env python3
"""Once, on the chip: `ops/gqa_attend.py` alone at Solar's cell's shape, the
kernel at every block length against the plain form (`lm.gqa_attend`), the
calls one program's loop as the layers' loop is; and (`--shapes`) at
K-EXAONE's cell's two shapes, the global layers' rows and the sliding
layers' rings.

    chiprun -- python benchmarks/gqa_attend_blocks.py [--calls 100] \
        [--shapes solar,solar-check,kexaone,kexaone-ring] \
        [--shapes gpt2,gpt2-chat,granite --blocks 128,256,512,1024]

The cell: 1 softmax layer x 40 slots x 8 key-value heads x 25,600 positions
of 128 lanes, a float32 q of 8 queries a head (two bf16 pieces), the slots
live at 16.4k-25.2k; and the same with 4 of 40 slots live (the reference
check's engine). A call's least time is its attended positions' keys and
values by the 8 heads, 4,096 B of bf16 a position, read once at the HBM's
peak (`benchmarks/chip/families/solar.py` `gqa_attend_cost`, which the
cell's `gqa_attend_roofline_pct` divides by the scope's time).

`benchmarks/mla_attend_blocks.py` has the trade a block length makes. A
grid step here moves 4 KB a position, seven times the latent rows' 576
values, so the steps' own time weighs less and the half block read past a
slot's position more.

Measured on a v5e (PR 52, 100 calls in one program; ms a call, the share of
the roofline, positions read over positions attended):

    block   Solar 40 x 8 x 25,600    4 of 40 live
    plain   9.092  45.6%  1.235      9.091   4.9%  11.55
    512     4.643  89.3%  1.013      0.710  62.4%  1.010
    1,024   4.704  88.2%  1.028      0.618  71.7%  1.028
    2,048   4.881  85.0%  1.055      0.566  78.4%  1.039
    2,560   4.904  84.5%  1.068      0.584  75.9%  1.068

The least are 4.146 and 0.444 ms (829,062 and 88,692 attended positions).
The kernel is bound by the HBM at every block length: what separates them
is the half block read past a slot's position (512 against 1,024: 1.3%)
and, where few slots are live, the grid steps that do nothing (0.11 us
each: 1,800 of them at 512, 900 at 1,024). 2,048 does not divide 25,600
and its last block hangs over. The kernel's values lie within 2.6e-7 of
the plain form's, whose r.m.s. is 0.017: the two pieces carry the float32
q and the probabilities through both. `ops/slot_rows.BLOCK` is 1,024:
within 1.3% of the best where every slot is live, 13% better than 512
where four are, and `mla_attend`'s.

K-EXAONE's cell (PR 59): `kexaone`, 2 global layers x 64 slots x 8 heads x
10,240 positions, the slots live at 6.2k-10.0k (a call a layer: the loop
turns over both); `kexaone-ring`, 6 sliding layers x 64 slots x 8 heads x a
ring of 128 rows (`ring=True`: one block a slot, 64 grid steps a call, each
moving 512 KB; the plain form is `lm.gqa_attend_band` over the whole layer;
the block lengths do not apply and one kernel row is timed). Its table is
PERF.md section 6, PR 59.

Leaves with the positions on the lanes (PR 61: `_lanes_body`, whose block
is `gqa_attend.BLOCK_LAST`): `gpt2`, GPT-2 XL's serving cells' cache, 48
layers x 8 slots x 25 heads x 64 x 1,024 positions, one bf16 query a head
with the step's own row handed over, the slots live at 16-320 (the decode
cell's positions); `gpt2-chat`, the same with 1 of 8 slots live at 300-1,000
(the chat cell's decode steps); the plain form is `models/gpt2.py`'s own
lines (`_decode_attend` where no kernel runs). `granite`, its cell's
first lanes since PR 63 (`granite._attention_first`): 4 layers x 48 slots x
8 heads x 64 x 8,192 positions, four bf16 queries a head, live at
3,100-7,200, against `lm.gqa_attend`. Such shapes get a last row, `rule`:
the op's own entry as the models call it, at `block_last(T)`. Measured
on a v5e (PR 61, 480 calls in one program; us a call, the share of the
attended rows' bytes at the HBM's peak, positions read over attended):

    block   gpt2, 8 live        gpt2-chat, 1 live    granite, 48 live
    plain   79.2   9.4%  8.57   79.0   8.9%  9.14    1,113  53.8%  1.64
    128     33.6  22.2%  1.61   22.8  30.6%  1.00    1,374  43.6%  1.01
    256     39.5  18.9%  2.41   20.4  34.3%  1.14      937  63.9%  1.02
    512     51.0  14.7%  4.28   17.7  39.6%  1.14      771  77.6%  1.06
    1,024   76.2   9.8%  8.57   17.2  40.7%  1.14      802  74.6%  1.12
    2,048                                              845  70.9%  1.20
    rule (PR 63: 512)                                  772  77.5%  1.06

A grid step that works moves its rows at 85-95% of the HBM's pace; what
keeps GPT-2's calls at a fifth to two fifths of their roofline is a call's
own ~7 us, the grid steps that do nothing (0.14 us each) and the half block
past a position, as much again as the ~170 positions before it. The values
lie within 1.5e-3 of the plain form's (r.m.s. 0.2: one bf16 piece, the
probabilities rounded before the division where the plain form rounds
after). `BLOCK_LAST` is 128, GPT-2's, and `block_last` follows the leaf's
length above it, a 16th: granite's 512 (PR 63's run of the granite column
read 1,114 | 936 / 772 / 802 at 256 / 512 / 1,024 and 772 under the rule:
the least is 0.598 ms, 239,340 attended positions of 2,048 B, so the kernel
alone stands at 77.5% of its rows' bytes; in the cell's decode step the call
takes 730 us at ~39 live lanes, 66.6%).

MiMo's cell (PR 62): `mimo`, 2 global layers x 64 slots x 4 heads x 24,576
positions, keys [.., 192, T] beside values [.., T, 128], 16 float32 queries
a head (32 rows as two pieces), the slots live at 1.0k-24.4k as the mixed
queue leaves them; `mimo-ring`, 5 sliding layers x 64 slots x 8 heads x a
ring of 128 positions, keys [.., 192, 128], with a sink a head as the fold's
start. The least is a position's 4 (or 8) x (192 + 128) lanes of bf16. Its
table is PERF.md section 6, PR 62 (no metric file reads the ring's share:
BENCHMARK.json is full).

Writes `chiprun_out/gqa_attend_blocks.json`. One process, which holds the
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

# rows by head: 8 heads of 128 lanes, a float32 q of 8 queries a head
ROWS = dict(G=8, R=8, D=128, q="float32", last=False, own=False)
# GPT-2 XL's cache as the chip holds it: 25 heads of 64 on the sublanes, one
# bf16 query a head, the step's own row beside the leaves
GPT2 = dict(G=25, R=1, D=64, q="bfloat16", last=True, own=True)
# name: (layers, slots, T, live slots, where the live slots stand, a ring,
# the heads: G key-value heads of D lanes, R queries each, q's dtype, the
# positions on the lanes, the step's own row handed over)
SHAPES = {"solar": (1, 40, 25600, 40, (16400, 25200), False, ROWS),
          "solar-check": (1, 40, 25600, 4, (16400, 25200), False, ROWS),
          "kexaone": (2, 64, 10240, 64, (6200, 10000), False, ROWS),
          "kexaone-ring": (6, 64, 128, 64, (6200, 10000), True, ROWS),
          "gpt2": (48, 8, 1024, 8, (16, 320), False, GPT2),
          "gpt2-chat": (48, 8, 1024, 1, (300, 1000), False, GPT2),
          "granite": (4, 48, 8192, 48, (3100, 7200), False, dict(
              G=8, R=4, D=64, q="bfloat16", last=True, own=False)),
          # keys of 192 lanes on the sublanes beside values of 128 by the row
          "mimo": (2, 64, 24576, 64, (1040, 24400), False, dict(
              G=4, R=16, D=192, Dv=128, q="float32", last=True, own=False)),
          "mimo-ring": (5, 64, 128, 64, (1040, 24400), True, dict(
              G=8, R=8, D=192, Dv=128, q="float32", last=True, own=False,
              sink=True))}
BLOCKS = (512, 1024, 2048, 2560)


def gpt2_plain(q, ck, cv, layer, pos, live, scale, own):
    """`models/gpt2.py`'s plain lines, as the decode step runs them where
    no kernel does, over the leaves as the model holds them."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt2

    del scale                                   # the model's: 1 / sqrt(Dh)
    cache = {"k": jnp.swapaxes(ck, 3, 4), "v": jnp.swapaxes(cv, 3, 4)}
    kernels, gpt2._rows_kernels = gpt2._rows_kernels, lambda *a: False
    try:
        return gpt2._decode_attend(q[:, :, 0], *own, cache, layer, pos,
                                   live)[:, :, None]
    finally:
        gpt2._rows_kernels = kernels


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--blocks", default=",".join(str(b) for b in BLOCKS))
    ap.add_argument("--shapes", default="solar,solar-check")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from harness import spec

    from ray_tpu.ops import slot_rows

    op = importlib.import_module("ray_tpu.ops.gqa_attend")
    out = {"device": jax.devices()[0].device_kind,
           "default_block": slot_rows.BLOCK, "block_last": op.BLOCK_LAST}
    peak = spec.peaks()[out["device"]]["hbm_bytes_per_s"]
    ks = jax.random.split(jax.random.key(0), 5)
    made = None
    for name in args.shapes.split(","):
        L, B, T, n_live, positions, ring, heads = SHAPES[name]
        G, R, D, last = (heads[n] for n in ("G", "R", "D", "last"))
        Dv = heads.get("Dv", D)             # values of another width: rows
        scale = 1.0 / math.sqrt(D)
        sink = (2.0 + jax.random.normal(ks[3], (G, R), jnp.float32)
                if heads.get("sink") else None)
        if made != (L, B, T, G, D, last):
            rows = (L, B, G, D, T) if last else (L, B, G, T, D)
            q = jax.random.normal(ks[0], (B, G, R, D), jnp.float32).astype(
                heads["q"])
            ck = jax.random.normal(ks[1], rows, jnp.bfloat16)
            cv = jax.random.normal(
                ks[2], rows if Dv == D else (L, B, G, T, Dv), jnp.bfloat16)
            own = tuple(jax.random.normal(k, (B, G, D), jnp.bfloat16)
                        for k in ks[3:]) if heads["own"] else ()
            made = (L, B, T, G, D, last)
        pos = jnp.asarray(np.random.default_rng(0).integers(
            *positions, size=B), jnp.int32)
        live = jnp.asarray(np.arange(B) % (B // n_live) == 0)
        # a ring's rows: the last T positions
        attended = int(jnp.sum(jnp.where(live, jnp.minimum(pos + 1, T)
                                         if ring else pos + 1, 0)))
        least = attended * G * (D + Dv) * 2 / peak
        rows, want = {}, None
        forms = [("plain", None)] + [(b, int(b)) for b in (
            [T] if ring else args.blocks.split(","))]
        if last and Dv == D and not ring:
            # the op's own entry, as the models call it: `block_last`'s block
            forms.append(("rule", op.block_last(T)))
        for label, block in forms:
            if block is None and own:
                fn = functools.partial(gpt2_plain, own=own)
            elif block is None:
                fn = functools.partial(op.gqa_attend, kernel=False, ring=ring,
                                       sink=sink)
            elif ring:
                fn = functools.partial(op.gqa_attend, ring=True, sink=sink)
            elif label == "rule":
                fn = functools.partial(op.gqa_attend, own=own)
            else:
                def fn(q, ck, cv, layer, pos, live, scale, block=block,
                       last=last, own=own, sink=sink, Dv=Dv, D=D):
                    return slot_rows.attend(
                        op.rows_kernel(q, ck, cv, scale, last=last, own=own,
                                       values_last=last and Dv == D,
                                       sink=sink),
                        layer, pos, live, block=block)

            # the calls are one program's loop, as the layers' loop is, the
            # leaves its arguments (`mla_attend_blocks.py` has why); a call
            # takes the one before it into its q, or the compiler would
            # lift the one layer's call out of the loop
            def calls(ck, cv, n, fn=fn, q=q, pos=pos, live=live, L=L,
                      scale=scale, Dv=Dv):
                return lax.fori_loop(0, n, lambda i, y: fn(
                    (q + 1e-6 * y[..., :1]).astype(q.dtype), ck, cv, i % L,
                    pos, live, scale),
                    jnp.zeros(q.shape[:-1] + (Dv,), jnp.float32))

            step = functools.partial(jax.jit(calls), ck, cv)
            try:
                got = jax.block_until_ready(step(1))
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                rows[label] = {"refused": str(e)[:300]}
                print(name, label, rows[label], flush=True)
                continue
            t0 = time.perf_counter()
            jax.block_until_ready(step(args.calls))
            seconds = (time.perf_counter() - t0) / args.calls
            got = np.asarray(got)[np.asarray(live)]
            if want is None:
                want = got
            read = (B * T if block is None else int(jnp.sum(jnp.where(
                live, jnp.minimum((pos // block + 1) * block, T), 0))))
            rows[label] = {
                "block": block,
                "ms_a_call": seconds * 1e3,
                "roofline_pct": 100 * least / seconds,
                "read_over_attended": read / attended,
                "grid_steps": 0 if block is None else B * -(-T // block),
                "max_abs_from_plain": float(np.abs(got - want).max()),
                "plain_rms": float(np.sqrt(np.mean(want * want)))}
            print(name, label, json.dumps(rows[label]), flush=True)
        out[name] = {"layers": L, "slots": B, "T": T, "live": n_live,
                     "heads": heads, "attended_positions": attended,
                     "least_ms": least * 1e3, "forms": rows}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "gqa_attend_blocks.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
