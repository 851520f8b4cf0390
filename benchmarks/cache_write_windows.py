#!/usr/bin/env python3
"""Once, on the chip: what GPT-2 XL's decode step pays to put a step's new
rows into its by-head cache `[48, 8, 25, 1024, 64]` bf16 (T along the lanes,
`models/gpt2.py` `_cache_write`), by the form the write takes.

    chiprun -- python benchmarks/cache_write_windows.py [--repo .scratch/parent]

Both leaves, `--calls` steps' writes in one program (a `fori_loop` that
carries the leaves; the rows differ a call, so nothing is lifted out), the
program run `--runs` times and the best taken; ms a step's write:

  (a) `in-loop`: a `lax.scan` over the 48 layers that carries the leaves,
      a window `[1,1,25,128,64]` a slot a leaf a layer: 768 a step, each
      chained to the one before it (the decode step's form until PR 56,
      the chunk program's still);
  (b) `after`: a window `[48,1,25,128,64]` a slot a leaf, chained: 16 a step
      (`decode_step` from PR 56 to PR 58, and still where no kernel runs);
  (c) `one-op`: the eight windows of a leaf gathered, blended and put back
      by one gather and one scatter a leaf;
  (d) `stream`: for scale, an elementwise pass that reads and writes 0.315
      GB in place: the 0.63 GB the windows move, as fast as a fusion
      streams them (0.77 ms at the HBM's published 819 GB/s);
  (e) `kernel-*`: `ops/rows_write.py`'s Pallas kernel on
      `jnp.swapaxes(leaf, 3, 4)`, which is the leaf's own bytes (`[.., 64,
      T]` by default is how the chip holds `[.., T, 64]`: the compiled
      decode step shows two bitcasts and no copy), after the loop:
      `kernel-loop` a `fori_loop` of 48 turns a leaf, each the one-layer
      call granite makes (two custom calls in the program); `kernel-calls`
      the same 96 calls unrolled; `kernel-grid-column` one call a leaf on a
      (layer, slot) grid with a grid step's rows as the one-layer form
      takes them, `[H, Dh, 1]`; `kernel-grid-N` the same grid with the rows
      as `[L, B, Dh, H]`, a head a lane, N layers a grid step
      (`rows_write._write_every`: `decode_step`'s since PR 58, N = 2);

the plain forms with the windows on a tile's edge (a start on a multiple of
128: what a lone row's window always has) and off it (37 positions on: a
chunk's window), and with 8, 4 and 1 slots active (an inactive slot's window
is read, blended with nothing and written back, as `_cache_write` does; the
kernel reads and writes a slot's tile whether it is active or not).
`--repo` then times the whole `gpt2.decode_step` of that checkout beside
this tree's, 8 slots at XL widths with seeded weights, calls dispatched
back to back on a donated cache. `--layers 4 --slots 2 --calls 2 --step 0`
rehearses on the CPU (the kernels interpreted).

Measured on a v5e (PR 58, my chip run, call 1; ms a step's write, best of
3; the plain rows read what PR 56's run read to 0.01 ms, and 8, 4 and 1
slots active the same to 0.006 ms in every row):

    form                operations a step        on a tile's edge   off it
    in-loop             768 x 0.41 MB            12.34              12.31
    after               16 x 19.7 MB              6.32               9.53
    one-op              2 gathers, 2 scatters    16.29              16.26
    stream              2 fusions, 0.63 GB        0.97 (650 GB/s)
    kernel-loop         96 calls of 8 steps       1.49
    kernel-calls        the same, unrolled        1.89
    kernel-grid-column  2 calls of 384 steps      1.89
    kernel-grid-1       2 calls of 384 steps      1.03
    kernel-grid-2       2 calls of 192 steps      0.97
    kernel-grid-4       2 calls of 96 steps       0.97

    the whole decode step, pos 100.. / 640..:  10.32 / 10.32 ms this tree
    (at one layer a grid step), 15.71 / 15.68 the parent's (PR 56's 16
    windows; 17.32 / 17.25 PR 56's parent, 768 windows)

A window all the layers deep moves no faster a tile than a layer's: a
traced step's `dynamic-update-slice` of 19.7 MB takes 303 us, 63 ns a 4 KB
tile (65 GB/s), where a layer's took 7.3 us for 100 (73 ns). What went with
the 752 operations is their own cost and a part of the blending fusions',
not the tiles' pace: the compiler fuses an update into an in-place loop
fusion (the `stream` row's 650 GB/s) only where the window's start along
T, the lanes, is a constant; a start that is computed is a DMA from VMEM a
tile at a time, 128-aligned or not, and a `lax.switch` over the eight
constant starts re-lays and copies the leaf (compiled for a described v5e:
38 copies, 31 GB accessed). Off a tile's edge the deep window pays half as
much again (two tiles a row of the window), as PR 24's did. Why the
scatter's form is the slowest was not looked into.

The kernel's forms differ by what the rows cost, not the tiles. A grid
step's rows as `[H, Dh, 1]` are a tile of their own a head in HBM (the
compiled programs: `bf16[48,8,25,64,1]{..T(8,128)(2,1)}`, 157 MB a leaf,
written by a `copy` and read back by the kernel; a layer's `[8,25,64,1]`,
3.3 MB, in the loop): as many bytes as the tiles they go into. With a head
a lane the rows are 16 KB a grid step, the program holds no temporary, and
the write runs at the pace of the in-place fusion over the same bytes; two
layers a grid step halve the grid steps' own cost (~0.15 us each) and four
add nothing.

Writes `chiprun_out/cache_write_windows.json`. One process, which holds
the chip. Imported by no cell.
"""

from __future__ import annotations

import argparse
import importlib
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
W = 128                                       # gpt2._WRITE_WINDOW
FORMS = ("in-loop", "after", "one-op", "stream", "kernel-loop",
         "kernel-calls", "kernel-grid-column", "kernel-grid-1",
         "kernel-grid-2", "kernel-grid-4")


def build(form: str, shape, calls: int):
    """jit(k, v, rows [2,L,B,H,Dh], start [B], lane [B], ok [B]) -> (k, v)
    after `calls` steps' writes of `form`."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    L, B, H, T, Dh = shape
    w = min(W, T)

    def blend(old, row, lane, ok):
        # old [l,1,H,w,Dh] takes row [l,1,H,Dh] at window lane `lane`
        take = (jnp.arange(w) == lane) & ok
        return jnp.where(take[:, None], row[:, :, :, None, :], old)

    def chained(c, l, depth, rows, start, lane, ok):
        for b in range(B):
            at = (l, b, 0, start[b], 0)
            old = lax.dynamic_slice(c, at, (depth, 1, H, w, Dh))
            c = lax.dynamic_update_slice(
                c, blend(old, rows[:, b:b + 1], lane[b], ok[b]), at)
        return c

    def in_loop(k, v, rows, start, lane, ok):
        def body(carry, scanned):
            l, rk, rv = scanned
            return (chained(carry[0], l, 1, rk[None], start, lane, ok),
                    chained(carry[1], l, 1, rv[None], start, lane, ok)), None

        (k, v), _ = lax.scan(body, (k, v), (jnp.arange(L), rows[0], rows[1]))
        return k, v

    def after(k, v, rows, start, lane, ok):
        return (chained(k, 0, L, rows[0], start, lane, ok),
                chained(v, 0, L, rows[1], start, lane, ok))

    def one_op(k, v, rows, start, lane, ok):
        index = jnp.stack([jnp.arange(B), start], axis=1)             # [B,2]
        gather = lax.GatherDimensionNumbers(
            offset_dims=(1, 2, 3, 4), collapsed_slice_dims=(1,),
            start_index_map=(1, 3))
        scatter = lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2, 3, 4), inserted_window_dims=(1,),
            scatter_dims_to_operand_dims=(1, 3))

        def leaf(c, r):
            old = lax.gather(c, index, gather, (L, 1, H, w, Dh),
                             indices_are_sorted=True, unique_indices=True,
                             mode="promise_in_bounds")         # [B,L,H,w,Dh]
            take = (jnp.arange(w)[None, :] == lane[:, None]) & ok[:, None]
            new = jnp.where(take[:, None, None, :, None],
                            r.transpose(1, 0, 2, 3)[:, :, :, None, :], old)
            return lax.scatter(c, index, new, scatter,
                               indices_are_sorted=True, unique_indices=True,
                               mode="promise_in_bounds")

        return leaf(k, rows[0]), leaf(v, rows[1])

    def stream(k, v, rows, start, lane, ok):
        # 16 windows' bytes of each leaf's head, read and written in place
        n = min(L, max(1, L * 8 * w // (B * T)))

        def leaf(c, r):
            part = lax.dynamic_slice(c, (0, 0, 0, 0, 0), (n, B, H, T, Dh))
            part = part + r[:n, :, :, None, :]
            return lax.dynamic_update_slice(c, part, (0, 0, 0, 0, 0))

        return leaf(k, rows[0]), leaf(v, rows[1])

    def on_view(write):
        # [L,B,H,Dh,T]: the bytes as the chip holds them, no copy
        def both(k, v, rows, start, lane, ok):
            return tuple(jnp.swapaxes(write(jnp.swapaxes(c, 3, 4), r,
                                            start + lane, ok), 3, 4)
                         for c, r in ((k, rows[0]), (v, rows[1])))
        return both

    def looped(view, r, pos, ok):
        return lax.fori_loop(0, L, lambda l, view: rw.rows_write(
            view, l, lax.dynamic_index_in_dim(r, l, 0, False), pos, ok), view)

    def unrolled(view, r, pos, ok):
        for l in range(L):
            view = rw.rows_write(view, jnp.int32(l), r[l], pos, ok)
        return view

    def grid_column(view, r, pos, ok):
        # the (layer, slot) grid with a grid step's rows as `rows_write`'s
        # one-layer form takes them, [H, Dh, 1]
        def body(tile_ref, lane_ref, c_ref, val_ref, out_ref):
            old = c_ref[0, 0]
            at = lax.broadcasted_iota(jnp.int32, old.shape, 2)
            out_ref[0, 0] = jnp.where(
                at == lane_ref[pl.program_id(1)],
                jnp.broadcast_to(val_ref[0, 0], old.shape), old)

        def tile(layer, slot, tiles, lanes):
            return layer, slot, 0, 0, tiles[slot]

        return pl.pallas_call(
            body, out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(L, B),
                in_specs=[pl.BlockSpec((1, 1, H, Dh, w), tile),
                          pl.BlockSpec((1, 1, H, Dh, 1),
                                       lambda l, b, *_: (l, b, 0, 0, 0))],
                out_specs=pl.BlockSpec((1, 1, H, Dh, w), tile)),
            input_output_aliases={2: 0}, name="rows_write_column",
            interpret=interpret,
        )(pos // w, jnp.where(ok, pos % w, -1), view, r[..., None])

    def grid_of(depth):
        return lambda view, r, pos, ok: rw._write_every(
            view, r, pos, ok, interpret, depth)

    kernels = {"kernel-loop": looped, "kernel-calls": unrolled,
               "kernel-grid-column": grid_column,
               **{f"kernel-grid-{n}": grid_of(n) for n in (1, 2, 4)}}
    if form.startswith("kernel"):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        rw = importlib.import_module("ray_tpu.ops.rows_write")
        interpret = jax.default_backend() != "tpu"     # a CPU rehearsal
        write = on_view(kernels[form])
    else:
        write = {"in-loop": in_loop, "after": after, "one-op": one_op,
                 "stream": stream}[form]

    def program(k, v, rows, start, lane, ok):
        def call(i, kv):
            return write(*kv, rows + i.astype(rows.dtype), start, lane, ok)

        return lax.fori_loop(0, calls, call, (k, v))

    return jax.jit(program, donate_argnums=(0, 1))


def time_forms(args) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    L, B, H, T, Dh = shape = (args.layers, args.slots, 25, 1024, 64)
    key = jax.random.key(0)
    k = jax.random.normal(key, shape, jnp.bfloat16)
    v = k + 1
    rows = jax.random.normal(jax.random.key(1), (2, L, B, H, Dh),
                             jnp.bfloat16)
    w = min(W, T)
    aligned = (np.arange(B) * w) % (T - w + 1) // w * w
    lines = []
    for form in args.forms.split(","):
        fn = build(form, shape, args.calls)
        # `stream` has no window, the kernel no mask that a slot's being
        # active changes
        plain = form != "stream" and not form.startswith("kernel")
        edges = (("on", 0), ("off", 37))[:2 if plain else 1]
        actives = [B] if not plain else sorted(
            {B, max(1, B // 2), 1}, reverse=True)
        for (edge, off), n_active in itertools.product(edges, actives):
            start = np.clip(aligned + off, 0, T - w).astype(np.int32)
            ops = (rows, jnp.asarray(start), jnp.full(B, 5, jnp.int32),
                   jnp.arange(B) < n_active)
            t0 = time.perf_counter()
            k, v = fn(k, v, *ops)
            jax.block_until_ready((k, v))
            first = time.perf_counter() - t0
            best = float("inf")
            for _ in range(args.runs):
                t0 = time.perf_counter()
                k, v = fn(k, v, *ops)
                jax.block_until_ready((k, v))
                best = min(best, time.perf_counter() - t0)
            line = {"form": form, "edge": edge, "active": n_active,
                    "ms_a_step": 1e3 * best / args.calls,
                    "first_call_s": first}
            print(json.dumps(line), flush=True)
            lines.append(line)
    return lines


def time_step(repo: str, args) -> dict:
    """ms a call of `repo`'s `gpt2.decode_step` at XL widths, 8 slots."""
    import jax
    import jax.numpy as jnp

    for name in [m for m in sys.modules if m.split(".")[0] == "ray_tpu"]:
        del sys.modules[name]
    sys.path.insert(0, repo)
    try:
        gpt2 = importlib.import_module("ray_tpu.models.gpt2")
    finally:
        sys.path.remove(repo)
    B, T = args.slots, 1024
    cfg = gpt2.GPT2Config.preset(args.preset, max_seq_len=T,
                                 vocab_size=50304, n_layer=args.layers)
    params = gpt2.resident_params(
        jax.jit(lambda: gpt2.init_params(jax.random.key(0), cfg))(), cfg)
    cache = jax.tree.map(
        lambda a: jax.random.normal(jax.random.key(2), a.shape, a.dtype),
        gpt2.init_cache(cfg, B, T))
    step = jax.jit(lambda p, c, t, pos, a: gpt2.decode_step(
        p, c, t, pos, a, cfg), donate_argnums=(1,))
    tokens = jnp.arange(B, dtype=jnp.int32)
    active = jnp.ones(B, jnp.bool_)
    out = {"repo": repo}
    for name, first in (("pos_100..", 100), ("pos_640..", 640)):
        pos = jnp.arange(B, dtype=jnp.int32) * 37 + first
        logits, cache = step(params, cache, tokens, pos, active)
        jax.block_until_ready(logits)
        best = float("inf")
        for _ in range(args.runs):
            t0 = time.perf_counter()
            for _ in range(args.calls):
                logits, cache = step(params, cache, tokens, pos, active)
            jax.block_until_ready(logits)
            best = min(best, time.perf_counter() - t0)
        out[name] = 1e3 * best / args.calls
    print(json.dumps(out), flush=True)
    del params, cache
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=48)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--calls", type=int, default=100)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--step", type=int, default=1,
                    help="0: the windows alone, no decode_step")
    ap.add_argument("--preset", default="gpt2-1.5b")
    ap.add_argument("--repo", default=None,
                    help="another checkout whose decode_step to time too")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind},
           "args": vars(args), "windows": time_forms(args), "steps": []}
    if args.step:
        for repo in [REPO] + ([os.path.abspath(args.repo)]
                              if args.repo else []):
            out["steps"].append(time_step(repo, args))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "cache_write_windows.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
