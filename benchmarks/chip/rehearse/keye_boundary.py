#!/usr/bin/env python3
"""Arithmetic, not a measurement of the chip: how many of a query's 2,048
chosen rows change sides when the indexer's keys, or its summed scores, go
through bfloat16, and how far attention's output moves for it. One layer of
the Keye configuration at its published widths with weights drawn as
`models/keye.py` draws them (every matrix N(0, 0.02), q's norm scaled by
`--q-scale`), 13,312 tokens of a seeded stream, 64 queries between
positions 8,192 and 13,311, everything float32 at `highest` on whatever
backend JAX has (the CPU takes about a minute and 3 GB).

It is why `serve-keye-longdoc`'s second limit cannot tell the reference with
`bfloat16_scores` from the program (`families/keye.py`, PERF.md section 6,
PR 46): the cache holds the indexer's key in bf16, which moves a score by as
much as rounding the sum does.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/keye_boundary.py \
        [--q-scale 2.0] [--seed 1]
"""

from __future__ import annotations

import argparse
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

T, D, H, G, HD, J, E, TOPK, THETA = 13312, 2048, 32, 4, 128, 16, 64, 2048, 1e7


def through_bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def rms_norm(x):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)


def rotate(x, pos, dim):
    inv = 1.0 / THETA ** (jnp.arange(0, dim, 2) / dim)
    ang = pos[:, None] * inv[None]
    c, s, h = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None], dim // 2
    return jnp.concatenate([x[..., :h] * c - x[..., h:] * s,
                            x[..., h:] * c + x[..., :h] * s], -1)


def chosen(scores):
    """The mask of each row's TOPK largest, ties to the lower index."""
    order = np.argsort(-scores, axis=-1, kind="stable")[:, :TOPK]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, -1)
    return mask


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--q-scale", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    jax.config.update("jax_default_matmul_precision", "highest")
    ks = jax.random.split(jax.random.key(args.seed), 3)
    u = rms_norm(through_bf16(0.3 * jax.random.normal(ks[0], (T, D))))
    qkv = u @ through_bf16(0.02 * jax.random.normal(ks[1],
                                                    (D, (H + 2 * G) * HD)))
    pos = jnp.arange(T, dtype=jnp.float32)
    q = rotate(rms_norm(qkv[:, :H * HD].reshape(T, H, HD)) * args.q_scale,
               pos, HD)
    k = rotate(rms_norm(qkv[:, H * HD:(H + G) * HD].reshape(T, G, HD)), pos,
               HD)
    v = qkv[:, (H + G) * HD:].reshape(T, G, HD)
    iq = u @ through_bf16(0.02 * jax.random.normal(ks[2], (D, J * E + E + J)))
    qi = rotate(iq[:, :J * E].reshape(T, J, E), pos, E)
    ki = iq[:, J * E:J * E + E]
    ki = ki - ki.mean(-1, keepdims=True)
    ki = rotate((ki / jnp.sqrt((ki * ki).mean(-1, keepdims=True) + 1e-6))[
        :, None], pos, E)[:, 0]
    w = iq[:, J * E + E:] / math.sqrt(J * E)
    at = np.arange(8192, T, 80)                               # 64 queries

    def scores(keys):
        dots = jnp.einsum("qje,se->qjs", qi[at], keys)
        index = jnp.sum(jax.nn.relu(dots) * w[at][:, :, None], 1)
        return np.asarray(jnp.where(jnp.arange(T)[None] <= at[:, None],
                                    index, -jnp.inf))

    exact = scores(ki)
    by_keys = scores(through_bf16(ki))
    by_sum = np.asarray(through_bf16(jnp.asarray(exact)))
    seen = np.isfinite(exact)
    sets = {name: chosen(s) for name, s in (
        ("float32", exact), ("bf16_keys", by_keys), ("bf16_scores", by_sum))}
    half = np.zeros(exact.shape, bool)
    np.put_along_axis(half, np.argsort(-exact, axis=-1, kind="stable")[
        :, :TOPK // 2], True, -1)

    def attend(mask, k_, v_):
        s = jnp.einsum("qgrd,sgd->qgrs", q[at].reshape(len(at), G, H // G,
                                                       HD), k_) / math.sqrt(HD)
        p = jax.nn.softmax(jnp.where(mask[:, None, None, :], s, -jnp.inf), -1)
        return (np.asarray(jnp.einsum("qgrs,sgd->qgrd", p, v_)).reshape(
            len(at), -1), np.asarray(p))

    def size(a):
        return float(np.sqrt((a ** 2).mean()))

    out, p = attend(sets["float32"], k, v)
    moved = {name: size(attend(m, k, v)[0] - out) / size(out)
             for name, m in (("bf16_keys", sets["bf16_keys"]),
                             ("bf16_scores", sets["bf16_scores"]),
                             ("dense_attend", seen), ("half_topk", half))}
    moved["bf16_rows_same_set"] = size(attend(
        sets["float32"], through_bf16(k), through_bf16(v))[0] - out) / size(out)
    print(json.dumps({
        "q_scale": args.q_scale, "seed": args.seed,
        "score_spread": float(np.std(exact[seen])),
        "score_moved_rms": {
            "bf16_keys": size(by_keys[seen] - exact[seen]),
            "bf16_scores": size(by_sum[seen] - exact[seen])},
        "rows_a_query_on_the_other_side": {
            name: float((sets["float32"] & ~sets[name]).sum(1).mean())
            for name in ("bf16_keys", "bf16_scores")},
        "attention_output_moved_share": moved,
        "rows_that_carry_a_heads_weight": float(
            (1 / (p ** 2).sum(-1)).mean())}, indent=1))


if __name__ == "__main__":
    main()
