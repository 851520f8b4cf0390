#!/usr/bin/env python3
"""On the chip: the experts' three grouped products at the serving programs'
row counts (a decode step: 32 slots x 6 = 192 rows over 128 groups; a chunk
step: 32 x C x 6), megablox `gmm` at several tiles against
`jax.lax.ragged_dot` and against a plain gather of each row's matrices and
an `einsum`. Prints one JSON line a variant: the three products' time and
what the touched experts' weights alone would take at the chip's
bandwidth. What `ops/grouped_matmul.py` chooses for few rows a group is
read off this (PERF.md, PR 29).

    chiprun -- python benchmarks/chip/rehearse/gmm_few_rows.py
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

D, F, E, K = 2048, 768, 128, 6
HBM_BYTES_PER_S = 819e9


def routed(rng, tokens: int):
    experts = np.stack([rng.choice(E, K, replace=False)
                        for _ in range(tokens)]).reshape(-1)
    return np.sort(experts), np.bincount(experts, minlength=E).astype(
        np.int32)


def timed(fn, *args, n=30) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main() -> None:
    rng = np.random.default_rng(0)
    key = jax.random.key(0)
    wg, wu = (jax.random.normal(k, (E, D, F), jnp.bfloat16) * 0.02
              for k in jax.random.split(key, 2))
    wd = jax.random.normal(jax.random.fold_in(key, 2), (E, F, D),
                           jnp.bfloat16) * 0.02
    for tokens, up_tiles, down_tiles in (
            (32, [(64, 1024, 768), (32, 1024, 768), (16, 1024, 768),
                  (64, 2048, 768), (32, 2048, 768), (16, 2048, 768),
                  (64, 2048, 384), (64, 512, 768), (8, 2048, 768)],
             [(64, 768, 1024), (32, 768, 1024), (16, 768, 1024),
              (64, 768, 2048), (32, 768, 2048), (16, 768, 2048),
              (64, 768, 1024), (64, 768, 512), (8, 768, 2048)]),
            (32 * 128, [(512, 1024, 768), (256, 1024, 768), (128, 1024, 768),
                        (128, 2048, 768), (256, 2048, 768)],
             [(512, 768, 1024), (256, 768, 1024), (128, 768, 1024),
              (128, 768, 2048), (256, 768, 2048)]),
            (32 * 64, [(512, 1024, 768), (256, 1024, 768), (128, 1024, 768),
                       (128, 2048, 768), (64, 2048, 768)],
             [(512, 768, 1024), (256, 768, 1024), (128, 768, 1024),
              (128, 768, 2048), (64, 768, 2048)])):
        rows, sizes = routed(rng, tokens)
        m = len(rows)
        touched = int((sizes > 0).sum())
        floor_ms = touched * 3 * D * F * 2 / HBM_BYTES_PER_S * 1e3
        x = jax.random.normal(jax.random.fold_in(key, m), (m, D),
                              jnp.bfloat16)
        sizes_d, rows_d = jnp.asarray(sizes), jnp.asarray(rows)
        base = {"rows": m, "groups_touched": touched,
                "touched_weights_at_819GBps_ms": round(floor_ms, 3),
                "flops_at_197T_ms": round(m * 6 * D * F / 197e12 * 1e3, 3)}

        def three(product):
            @jax.jit
            def run(x, wg, wu, wd, sizes):
                h = jax.nn.silu(product(x, wg, sizes, 0)) \
                    * product(x, wu, sizes, 0)
                return product(h, wd, sizes, 1)
            return run

        for up, down in zip(up_tiles, down_tiles):
            if m % up[0]:
                continue

            def product(lhs, rhs, sizes, which, up=up, down=down):
                return gmm(lhs, rhs, sizes, lhs.dtype, down if which else up)

            try:
                ms = timed(three(product), x, wg, wu, wd, sizes_d)
                print(json.dumps({**base, "variant": "gmm", "up": up,
                                  "down": down, "ms": round(ms, 3)}),
                      flush=True)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                print(json.dumps({**base, "variant": "gmm", "up": up,
                                  "down": down,
                                  "refused": str(e)[:200]}), flush=True)

        ms = timed(three(lambda l, r, s, _: jax.lax.ragged_dot(l, r, s)),
                   x, wg, wu, wd, sizes_d)
        print(json.dumps({**base, "variant": "ragged_dot",
                          "ms": round(ms, 3)}), flush=True)
        if m <= 1024:
            @jax.jit
            def gathered(x, wg, wu, wd, rows):
                g = jnp.einsum("md,mdf->mf", x, wg[rows])
                u = jnp.einsum("md,mdf->mf", x, wu[rows])
                return jnp.einsum("mf,mfd->md", jax.nn.silu(g) * u, wd[rows])

            ms = timed(gathered, x, wg, wu, wd, rows_d)
            print(json.dumps({**base, "variant": "gather_einsum",
                              "ms": round(ms, 3)}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
