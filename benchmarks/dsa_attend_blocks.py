#!/usr/bin/env python3
"""Once, on the chip: `ops/dsa_attend.py` alone at Keye's cell's shape, the
kernel at every block length against the plain form (a gather of the chosen
rows, `dsa.attend_selected` over the copy), the calls one program's loop as
the layers' loop is; the choice in its two forms; and what it costs to
fetch a slot's chosen rows one by one from inside a kernel.

    chiprun -- python benchmarks/dsa_attend_blocks.py [--calls 100]

The cell: 32 slots x 13,312 positions of 4 x 128 lanes a leaf, a bf16 q of 8
queries a key-value head, the slots live at 8.2k-12.9k and 2,048 of each
slot's rows chosen (the k largest of uniform scores: one row in five,
anywhere); and the same with 4 of 32 slots live (the reference check's
engine). Two least times: the set's rows of k and v, 2,048 B a chosen row,
read once at the HBM's peak (`benchmarks/chip/families/keye.py`
`dsa_attend_cost`, which the cell's `dsa_attend_roofline_pct` divides by the
scope's time), and the dense bytes, every row to a slot's position, which
is what the kernel moves.

Measured on a v5e (PR 54, 100 calls in one program; ms a call, the share
of the chosen rows' roofline, of the dense bytes', positions read over
positions attended):

    block   Keye 32 x 13,312, 2,048 chosen    4 of 32 live
    plain   2.285   7.2%  37.4%  0.192        2.285   0.9%   4.6%  0.193
    512     1.111  14.8%  76.9%  1.022        0.244   8.4%  43.5%  1.013
    1,024   1.047  15.7%  81.6%  1.046        0.189  10.8%  56.1%  1.013
    2,048   1.110  14.8%  77.0%  1.094        0.169  12.1%  62.7%  1.061

The least are 0.164 ms (65,536 chosen rows) and 0.854 ms (341,675 attended
positions) with every slot live, 0.020 and 0.106 ms with four. The plain
form takes the same time whatever is live (it gathers 2,048 rows of every
slot) and moves a chosen row at 85 GB/s; the kernel reads five times the
bytes in under half the time, at 82% of the HBM's peak, where
`gqa_attend`'s 16 query rows a head read 88% and these 8 rows find the
128-lane slices of a `[block, 512]` tile a little dearer. 2,048 does not
divide 13,312 and its last block hangs over. The kernel's values lie
within 4.2e-4 of the plain form's, whose r.m.s. is 0.038: the unnormalised
probabilities rounded to bf16 where the plain form rounds the normalised
ones. `ops/slot_rows.BLOCK` is 1,024: the best where every slot is live,
12% behind 2,048 where four are, and `mla_attend`'s and `gqa_attend`'s.

The choice alone, `[32, 13312]` float32 scores: by index (`select_rows`)
0.355 ms a call, as a mask (`select_mask`: the same `top_k`, a compare and
a prefix count) 0.415; in Keye's decode program the scope `dsa_select`
reads 2.135 ms a step with the one and 2.136 with the other (PERF.md PR
54): the sort is all of it.

Fetching by row (`rows_by_dma`: one leaf's 32 x 2,048 chosen rows, a DMA
each, 256 in flight): **2.92 ms a call, 44.5 ns a copy**, where XLA's
gather moves the same rows in 0.85 ms (13 ns a row) and the kernel reads
both leaves densely in 1.05. A copy cannot name one row: Mosaic refuses a
slice of fewer than 8 positions of the bf16 leaf, so each moves 8 KB (184
GB/s in all) for the 1 KB it wants. The descriptors' cost alone (65,536 x
44.5 ns a leaf, twice a layer: 5.8 ms) is five times the dense read:
ROADMAP S22b's by-row form starts to pay only where a slot holds more than
~36 rows for each chosen one (44.5 ns against the 1.25 ns a dense row's 1
KB takes at the peak), T above ~73k at a topk of 2,048, which no cell has.

Writes `chiprun_out/dsa_attend_blocks.json`. One process, which holds the
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

L, B, G, R, D, T, TOPK = 2, 32, 4, 8, 128, 13312, 2048
SCALE = 1.0 / math.sqrt(D)
LIVE = {"keye": 32, "keye-check": 4}    # name: live slots
POSITIONS = (8200, 12900)
BLOCKS = (512, 1024, 2048)


WAVE, TILE = 256, 8


def rows_by_dma(idx, leaf):
    """What S22b's by-row form would pay before it multiplies anything:
    idx [B, K] and one layer of a leaf [B, T, F] -> for every named row a
    DMA of its own, HBM -> VMEM, a slot a grid step, `WAVE` copies started
    and then awaited at a time. A copy takes the `TILE` positions the row
    lies among: Mosaic refuses a slice of fewer ("must be aligned to tiling
    (8)"; the bf16 leaf lies in HBM in tiles of (8, 128)(2, 1)), so one row
    is not a thing a DMA can name. The sum of the last wave's tiles comes
    back, so that the copies are not dead."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, F = idx.shape[1], leaf.shape[2]

    def kernel(idx_ref, leaf_ref, o_ref, buf, sem):
        b = pl.program_id(0)

        def copy(i, row):
            return pltpu.make_async_copy(
                leaf_ref.at[b, pl.ds(pl.multiple_of(row // TILE * TILE, TILE),
                                     TILE)],
                buf.at[pl.ds(pl.multiple_of(i * TILE, TILE), TILE)], sem)

        def wave(w, _):
            lax.fori_loop(0, WAVE, lambda i, _: copy(
                i, idx_ref[b, w * WAVE + i]).start(), None)
            lax.fori_loop(0, WAVE, lambda i, _: copy(i, 0).wait(), None)

        lax.fori_loop(0, K // WAVE, wave, None)
        o_ref[0] = jnp.sum(buf[...].astype(jnp.float32), axis=0,
                           keepdims=True)

    return pl.pallas_call(
        kernel, grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(idx.shape[0],),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, F), lambda b, idx: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((WAVE * TILE, F), leaf.dtype),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], 1, F), jnp.float32),
        name="rows_by_dma")(idx, leaf)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--blocks", default=",".join(str(b) for b in BLOCKS))
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from harness import spec
    from ray_tpu.ops import dsa

    from ray_tpu.ops import slot_rows

    op = importlib.import_module("ray_tpu.ops.dsa_attend")
    out = {"device": jax.devices()[0].device_kind,
           "default_block": slot_rows.BLOCK,
           "shape": {"layers": L, "slots": B, "kv_heads": G, "queries": R,
                     "lanes": D, "T": T, "topk": TOPK}}
    peak = spec.peaks()[out["device"]]["hbm_bytes_per_s"]
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, G, R, D), jnp.float32).astype(
        jnp.bfloat16)
    ck = jax.random.normal(ks[1], (L, B, T, G * D), jnp.bfloat16)
    cv = jax.random.normal(ks[2], (L, B, T, G * D), jnp.bfloat16)
    pos = jnp.asarray(np.random.default_rng(0).integers(
        *POSITIONS, size=B), jnp.int32)
    scores = jnp.where(jnp.arange(T) <= pos[:, None],
                       jax.random.uniform(ks[3], (B, T)), -jnp.inf)
    by_index = jax.jit(lambda s: dsa.select_rows(s, TOPK))(scores)
    as_mask = jax.jit(lambda s: dsa.select_mask(s, TOPK))(scores)

    def timed(step):
        """ms a call of `step(n)`, n calls in one program; its one call's
        value."""
        got = jax.block_until_ready(step(1))
        t0 = time.perf_counter()
        jax.block_until_ready(step(args.calls))
        return (time.perf_counter() - t0) / args.calls * 1e3, got

    for name, n_live in LIVE.items():
        live = jnp.asarray(np.arange(B) % (B // n_live) == 0)
        attended = int(jnp.sum(jnp.where(live, pos + 1, 0)))
        chosen = int(jnp.sum(jnp.where(live, jnp.minimum(pos + 1, TOPK), 0)))
        least = {"chosen_rows": chosen * 2 * G * D * 2 / peak,
                 "dense": attended * 2 * G * D * 2 / peak}
        rows, want = {}, None
        forms = [("plain", None)] + [(b, int(b))
                                     for b in args.blocks.split(",")]
        for label, block in forms:
            if block is None:
                fn, chose = op.dsa_attend, by_index
            else:
                chose = as_mask
                def fn(q, ck, cv, layer, pos, live, keep, scale,
                       block=block):
                    return slot_rows.attend(
                        op.rows_kernel(q, ck, cv, keep, scale), layer, pos,
                        live, block=block)

            # the calls are one program's loop, as the layers' loop is, the
            # leaves and the set its arguments (`mla_attend_blocks.py` has
            # why); a call takes the one before it into its q, or the
            # compiler would lift a layer's call out of the loop
            def calls(ck, cv, chose, n, fn=fn):
                return lax.fori_loop(0, n, lambda i, y: fn(
                    (q + 1e-6 * y).astype(q.dtype), ck, cv, i % L, pos, live,
                    chose, SCALE), jnp.zeros((B, G, R, D), jnp.float32))

            step = functools.partial(jax.jit(calls), ck, cv, chose)
            try:
                ms, got = timed(step)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                rows[label] = {"refused": str(e)[:300]}
                print(name, label, rows[label], flush=True)
                continue
            got = np.asarray(got)[np.asarray(live)]
            if want is None:
                want = got
            read = (chosen if block is None else int(jnp.sum(jnp.where(
                live, jnp.minimum((pos // block + 1) * block, T), 0))))
            rows[label] = {
                "ms_a_call": ms,
                "chosen_rows_roofline_pct": 1e5 * least["chosen_rows"] / ms,
                "dense_roofline_pct": 1e5 * least["dense"] / ms,
                "read_over_attended": read / attended,
                "grid_steps": 0 if block is None else B * -(-T // block),
                "max_abs_from_plain": float(np.abs(got - want).max()),
                "plain_rms": float(np.sqrt(np.mean(want * want)))}
            print(name, label, json.dumps(rows[label]), flush=True)
        out[name] = {"live": n_live, "attended_positions": attended,
                     "chosen_rows": chosen,
                     "least_ms": {k: v * 1e3 for k, v in least.items()},
                     "forms": rows}

    # the choice in its two forms, alone: [32, 13312] float32 scores
    out["select"] = {}
    for label, select in (
            # every index and every flag into the value: asked for the first
            # index alone the compiler makes an argmax of the `top_k`
            ("by_index", lambda s: jnp.sum(jnp.where(
                *dsa.select_rows(s, TOPK)[::-1], 0), axis=1, keepdims=True)),
            ("as_mask", lambda s: jnp.sum(dsa.select_mask(s, TOPK), axis=1,
                                          keepdims=True, dtype=jnp.int32))):
        def calls(scores, n, select=select):
            return lax.fori_loop(0, n, lambda i, y: select(
                scores + 1e-30 * y.astype(jnp.float32)),
                jnp.zeros((B, 1), jnp.int32))

        ms, _ = timed(functools.partial(jax.jit(calls), scores))
        out["select"][label] = {"ms_a_call": ms}
        print("select", label, json.dumps(out["select"][label]), flush=True)

    # the by-row form's copies alone: 32 x 2,048 of one leaf, each the
    # tile of 8 positions x 1 KB its row lies in
    idx = by_index[0]

    def copies(leaf, idx, n):
        return lax.fori_loop(0, n, lambda i, y: rows_by_dma(
            idx + (y[:, 0, :1] > 1e30).astype(jnp.int32), leaf),
            jnp.zeros((B, 1, G * D), jnp.float32))

    try:
        ms, got = timed(functools.partial(jax.jit(copies), ck[0], idx))
        last = (idx[:, -WAVE:, None] // TILE * TILE
                + jnp.arange(TILE)).reshape(B, -1)
        want = jnp.sum(ck[0][jnp.arange(B)[:, None], last].astype(
            jnp.float32), axis=1)
        out["rows_by_dma"] = {
            "ms_a_call": ms, "copies": B * TOPK,
            "bytes_a_copy": TILE * G * D * 2,
            "ns_a_copy": ms * 1e6 / (B * TOPK),
            "gb_per_s": B * TOPK * TILE * G * D * 2 / ms / 1e6,
            "max_abs_from_gather": float(jnp.abs(got[:, 0] - want).max())}
    except Exception as e:  # noqa: BLE001 - the compiler's refusal
        out["rows_by_dma"] = {"refused": str(e)[:600]}
    print("rows_by_dma", json.dumps(out["rows_by_dma"]), flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "dsa_attend_blocks.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
