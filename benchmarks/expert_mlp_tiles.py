#!/usr/bin/env python3
"""Once, on the chip: `ops/expert_mlp.py` alone at the shapes Kimi's serving
cell gives it, the kernel at every tiling against the three grouped matmuls
it replaced, the calls one program's loop as the layers' loop is.

    chiprun -- python benchmarks/expert_mlp_tiles.py [--calls 200]

The shapes (a stack of 8 layers x 64 held experts of `[2304, 1024]` bf16,
float32 rows, a layer of the stack a call): `decode`, every slot's one lane:
128 tokens x 8 experts = 1,024 rows of which 250 fall on 43 of the layer's
64 held experts (35 on the busiest; seeded routing with a shared preference,
as the cell's `moe_held_rows_pct` ~24 and ~44 experts touched); `chunk`, one
slot's 127 further lanes, 1,016 rows: a last block that hangs over the rows'
end (the cell's chunk program pads the lanes to 128 and hands over 1,024 like
the decode program); `check`, the reference check's engine: 4 of 128 slots
with routing of their own, the others' rows all alike: 259 rows on 10
experts, 126 on one. A call's least time is `moe_experts_decode_cost`
(`benchmarks/chip/families/kanana.py`: each touched expert's three matrices
and each held row in and out once at the HBM's peak), which
`moe_experts_decode_roofline_pct` (one entry for every cell with routed
experts since PR 45: Kanana's, Kimi's and Keye's) divides by the scope's
time.

Measured on a v5e (PR 42, 200 calls in one program; ms a call and the share
of that cost; a tiling is rows a block : rows a product : columns of F):

    tiling        decode          chunk           check
    three gmm     1.448  51.5%    1.592  46.9%    0.576  30.5%
    256:64:256    0.882  84.6%    0.886  84.2%    0.256  68.7%
    128:64:256    0.901  82.8%    0.886  84.2%    0.265  66.3%
    512:64:256    0.886  84.2%    0.889  83.9%    0.248  70.8%
    256:32:256    0.878  85.0%    0.882  84.6%    0.254  69.2%
    256:128:256   0.928  80.4%    0.911  81.9%    0.272  64.5%
    128:128:256   0.931  80.1%    0.910  82.0%    0.272  64.7%
    256:64:128    0.873  85.4%    0.876  85.2%    0.259  67.8%
    256:64:512    0.830  89.9%    0.830  89.8%    0.245  71.7%
    256:64:1024   0.834  89.5%    0.833  89.6%    0.244  72.1%
    256:128:512   0.877  85.0%    0.859  86.8%    0.265  66.4%
    128:64:512    0.849  87.9%    0.830  89.9%    0.252  69.7%
    128:32:512    0.844  88.4%    0.826  90.4%    0.251  69.9%

The least are 0.746, 0.746 and 0.176 ms. What the sweep says: the call is the
touched matrices' DMA (a step of F 512 moves 7.1 MB, 8.6 us) plus ~1.2 us a
grid step and ~0.03 ms a call (the plan's dozen small operations, the first
tiles' wait); the MXU's work hides under the DMA at 64 rows a product (128
piece-rows: 4.6 us a step) and shows at 128 (+0.05 ms); wider tiles of F
mean fewer steps until 1,024 holds 28 MB of weights' buffers for nothing
more; a block of 256 rows is crossed by fewer experts than one of 128, and
512 buys nothing. With the plan's four gathers (4.75 us each) in place of one
fusion of masked sums the default read 0.847 / 0.848 / 0.264. The kernel's
result lies within 8e-6 of the three grouped matmuls' where theirs has an
r.m.s. of 0.59: both make the same two pieces of a row and of h. In the
cell's decode step, in the traced run that was read operation by operation,
the same call took 0.95-0.97 ms and the parent's three grouped matmuls with
what stands round them 1.71: PERF.md PR 42. `ops/expert_mlp`'s tiles are
256:64:512.

Writes `chiprun_out/expert_mlp_tiles.json`. One process, which holds the
chip.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmarks", "chip")]

# Kimi-Linear-48B-A3B as the cell holds it: 8 expert layers' 64 held experts
# of 256 in one stack, 8 experts a token
D, F, E, HELD, LAYERS, K = 2304, 1024, 256, 64, 8, 8
GATE = True                     # a SwiGLU's three matrices
FIRST = 64                      # the held experts are FIRST..+HELD of the E
# how far a token-independent preference skews the seeded routing: at 1.6 a
# step's 1,024 pairs touch ~43 of the 64 held experts (the cell's
# `experts_touched` a layer) where even routing would touch 63
SKEW = 1.6
SHAPES = {   # name: (tokens, of which with routing of their own)
    "decode": (128, 128),       # every slot's one lane
    "chunk": (127, 127),        # one slot's further lanes
    "check": (128, 4),          # the reference check's engine: 4 slots live,
                                # the dead ones' rows all alike
}
# (rows a block, rows a product, columns of F a grid step)
TILINGS = ((256, 64, 256), (128, 64, 256), (512, 64, 256), (256, 32, 256),
           (256, 128, 256), (128, 128, 256), (256, 64, 128), (256, 64, 512),
           (256, 64, 1024), (256, 128, 512), (128, 64, 512), (128, 32, 512))
# `--model solar`: Solar-Open2-250B as its cell holds it, 4 layers' 40 held
# experts of 320 in one stack, d 4,096 and F 1,280 = 2.5 column tiles of 512:
# the overhang (three steps, 1,536 columns computed) against the tiles that
# divide it. Measured on a v5e (PR 49, 100 calls in one program; ms a call
# and the share of the cost; decode: 40 tokens, 30 held rows on 18 experts,
# least 0.692 ms; chunk: 128 tokens, 96 held rows on 29 experts, least 1.116):
#     tiling        decode          chunk
#     three gmm     1.037  66.8%    1.782  62.6%
#     256:64:512    0.815  84.9%    1.300  85.9%   (the overhang: 3 steps)
#     256:64:640    0.794  87.1%    1.256  88.8%   (`_column_tile`'s choice)
#     256:64:256    0.788  87.8%    1.253  89.1%
#     256:64:128    0.782  88.5%    1.250  89.2%
#     256:32:640    0.787  87.9%    1.250  89.3%
#     128:64:640    0.787  88.0%    1.252  89.1%
# A tile that divides F takes 2.6-3.4% off the overhang; among those that do
# the call is the touched matrices' DMA whatever the tile (a step of 128
# columns moves 3.1 MB, and at d 4,096 its ~1.2 us hide under it).
SOLAR = dict(D=4096, F=1280, E=320, HELD=40, LAYERS=4, FIRST=0,
             SHAPES={"decode": (40, 40), "chunk": (128, 128)},
             TILINGS=((256, 64, 512), (256, 64, 640), (256, 64, 256),
                      (256, 64, 128), (256, 32, 640), (128, 64, 640)))

# `--model nemotron`: Nemotron-3-Super-120B-A12B's routed experts as its cell
# holds them, 5 layers' 128 held experts of 512 in one stack, the two-matrix
# relu^2 form inside the latent: rows of 1,024, F 2,688 = 21 lane tiles = 3 x
# 896, 22 experts a token (2,816 rows a step of which a quarter are held).
# Measured on a v5e (PR 53, 100 calls in one program; ms a call and the share
# of the cost; 709 held rows on 100 of the layer's 128 held experts, 41 on
# the busiest, least 1.348 ms; `decode` and `chunk` are one shape here):
#     tiling        decode
#     two gmm       2.816  47.9%
#     256:64:384    1.545  87.2%
#     256:64:512    1.697  79.4%   (the overhang: 6 steps, 3,072 columns)
#     256:64:896    1.526  88.3%
#     256:64:2688   1.510  89.3%   (`_column_tile`'s choice: an expert a step)
#     256:32:2688   1.496  90.1%
#     256:32:896    1.513  89.1%
#     128:64:896    1.565  86.1%
#     512:64:896    1.513  89.1%
#     256:128:896   1.649  81.8%
# An expert is 11 MB, 13.4 us of DMA: a grid step's ~1.2 us shows at three
# steps an expert (896) and at seven (384), and the overhang computes a
# seventh of its columns for nothing.
NEMOTRON = dict(D=1024, F=2688, E=512, HELD=128, LAYERS=5, FIRST=0, K=22,
                GATE=False,
                SHAPES={"decode": (128, 128), "chunk": (128, 128)},
                TILINGS=((256, 64, 384), (256, 64, 896), (256, 64, 2688),
                         (256, 64, 512), (256, 32, 896), (128, 64, 896),
                         (512, 64, 896), (256, 128, 896), (256, 32, 2688)))

# `--model longcat`: LongCat-Flash-Chat's routed experts as its cell holds
# them, 4 double layers' 16 held experts of 512 in one stack beside 256
# zero-compute outputs the router scores too (E = 768, 12 a token: 1,536
# pairs a step of which ~2% are held), the widest row the kernel meets: d
# 6,144, F 2,048 = 4 column tiles of 512, whose weight tiles' buffers are
# 37.7 MB of `WEIGHT_TILES_BYTES` 40 and the float32 row and output blocks of
# 256 rows 6.3 MB each. Measured on a v5e (PR 55, 100 calls in one program;
# ms a call and the share of the cost; 15 held rows on 6 of the layer's 16
# held experts, 5 on the busiest, least 0.554 ms; `decode` and `chunk` are
# one shape here):
#     tiling        decode
#     three gmm     1.235  44.8%
#     256:64:512    0.662  83.6%   (the op's own: `TILE_F` divides 2,048)
#     128:64:512    0.658  84.1%
#     64:64:512     0.653  84.7%
#     256:64:256    0.660  83.8%
#     128:64:256    0.659  84.1%
#     256:32:512    0.657  84.3%
#     256:64:128    0.642  86.2%
#     128:64:1024   refused: VMEM (75 MB of weights' buffers)
# An expert is 75.5 MB, 92 us of DMA, and six of them a call: whatever the
# tile the call is their bytes plus ~0.1 ms (the plan, the first tiles' wait,
# the 1,536 x 6,144 float32 output written once), and the tiles stay.
LONGCAT = dict(D=6144, F=2048, E=768, HELD=16, LAYERS=4, FIRST=0, K=12,
               SHAPES={"decode": (128, 128), "chunk": (128, 128)},
               TILINGS=((256, 64, 512), (128, 64, 512), (64, 64, 512),
                        (256, 64, 256), (128, 64, 256), (256, 32, 512),
                        (256, 64, 128), (128, 64, 1024)))


def _routing(np, tokens: int, own: int, seed: int):
    """experts [tokens, K] over the E: a preference all tokens share plus a
    token's own noise, the first `own` tokens' own and the rest like the
    last of them."""
    rng = np.random.default_rng(seed)
    logits = SKEW * rng.standard_normal(E) + rng.gumbel(size=(tokens, E))
    logits[own:] = logits[own - 1]
    return np.argsort(-logits, axis=1)[:, :K]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=200)
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tilings", default="",
                    help="rows:sub:f,... in place of the sweep")
    ap.add_argument("--model", default="kimi", choices=("kimi", "solar", "nemotron", "longcat"))
    args = ap.parse_args()
    if args.model != "kimi":
        globals().update({"solar": SOLAR, "nemotron": NEMOTRON,
                          "longcat": LONGCAT}[args.model])
        args.shapes = ",".join(s for s in args.shapes.split(",")
                               if s in SHAPES)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from families.kanana import moe_experts_decode_cost
    from families.nemotron import moe_experts_cost
    from harness import spec
    from ray_tpu.models import moe

    op = importlib.import_module("ray_tpu.ops.expert_mlp")
    tilings = [tuple(int(n) for n in t.split(":"))
               for t in args.tilings.split(",") if t] or TILINGS
    out = {"device": jax.devices()[0].device_kind,
           "default": [op.TILE_ROWS, op.SUB_ROWS, op.TILE_F]}
    peaks = spec.peaks()[out["device"]]
    stack = HELD * LAYERS
    ks = jax.random.split(jax.random.key(0), 4)
    bf = jnp.bfloat16

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def matrices(key, a, b):
        # a layer's experts at a time: the float32 normals of the whole
        # stack would be 4.8 GB
        return lax.map(lambda k: (jax.random.normal(k, (HELD, a, b))
                                  / a ** 0.5).astype(bf),
                       jax.random.split(key, LAYERS)).reshape(stack, a, b)

    wg, wu, wd = (matrices(ks[0], D, F) if GATE else None,
                  matrices(ks[1], D, F), matrices(ks[2], F, D))

    for name in args.shapes.split(","):
        tokens, own = SHAPES[name]
        local = _routing(np, tokens, own, 0).reshape(-1) - FIRST
        held = (local >= 0) & (local < HELD)
        order = np.argsort(np.where(held, local, stack), kind="stable")
        # layer j's groups of the stack's 512 and the one past its end
        sizes = np.zeros((LAYERS, stack + 1), np.int32)
        for j in range(LAYERS):
            np.add.at(sizes[j], np.where(held, j * HELD + local, stack), 1)
        rows_held = int(held.sum())
        touched = int((sizes[0, :stack] > 0).sum())
        cost = (moe_experts_decode_cost(
            {"hidden_size": D, "moe_intermediate_size": F}, rows_held,
            touched) if GATE else moe_experts_cost(
            {"moe_latent_size": D, "moe_intermediate_size": F}, rows_held,
            touched))
        least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                    cost["flops"] / peaks["bf16_flops_per_s"])
        x = jax.random.normal(ks[3], (tokens, D), jnp.float32)
        xs = jnp.repeat(x, K, axis=0)[order]
        xs = jnp.where(jnp.arange(tokens * K)[:, None] < rows_held, xs, 0)
        sizes = jnp.asarray(sizes)
        forms = [("three-gmm", None)] + [
            (":".join(str(n) for n in t), t) for t in tilings]
        shape = {"rows": tokens * K, "rows_held": rows_held,
                 "experts_touched": touched,
                 "most_rows_an_expert": int(sizes[0, :stack].max()),
                 "least_ms": least * 1e3, "forms": {}}
        print(name, {k: v for k, v in shape.items() if k != "forms"},
              flush=True)
        want = None
        for label, tiling in forms:
            if tiling is None:
                fn = lambda *a: moe._three_products(  # noqa: E731
                    *a, jnp.int32(0))
            else:
                fn = lambda *a, t=tiling: op.expert_mlp(  # noqa: E731
                    *a, jnp.int32(0), tiles=t)

            # one program's loop, a layer of the stack a call (a call
            # dispatched alone measures the host), the stacks its arguments
            def calls(xs, wg, wu, wd, sizes, n, fn=fn):
                return lax.fori_loop(
                    0, n, lambda i, _: fn(xs, wg, wu, wd, sizes[i % LAYERS]),
                    jnp.zeros_like(xs))

            step = functools.partial(jax.jit(calls), xs, wg, wu, wd, sizes)
            try:
                got = jax.block_until_ready(step(LAYERS))
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                shape["forms"][label] = {"refused": str(e)[:300]}
                print(name, label, "refused", str(e)[:300], flush=True)
                continue
            t0 = time.perf_counter()
            jax.block_until_ready(step(args.calls))
            seconds = (time.perf_counter() - t0) / args.calls
            got = np.asarray(got)[:rows_held]
            if want is None:
                want = got
            shape["forms"][label] = {
                "ms_a_call": seconds * 1e3,
                "pct_of_cost": 100 * least / seconds,
                "max_abs_from_three_gmm": float(np.abs(got - want).max()),
                "three_gmm_rms": float(np.sqrt(np.mean(want * want)))}
            print(name, label, json.dumps(shape["forms"][label]), flush=True)
        out[name] = shape
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    name = "expert_mlp_tiles" + ("" if args.model == "kimi"
                                 else "_" + args.model)
    with open(os.path.join(REPO, "chiprun_out", name + ".json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
