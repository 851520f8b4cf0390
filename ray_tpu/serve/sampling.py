"""The next token of every slot, chosen on the device.

`LLMEngine` dispatches `select_tokens` after each step program, over the
step's `[B, V]` float32 logits: the ids it returns stay on the device and
are the next step's input, so the logits never cross to the host and the
engine loop never waits to learn a token before it dispatches again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kept_tokens(lg: jax.Array, top_k: jax.Array, top_p: jax.Array):
    """[B, V] bool: the tokens a sampling row may draw from.

    `lg` [B, V] are the logits already divided by the temperature. Top-k
    first (`0 < top_k < V`, else off): a token stays if fewer than k are
    strictly larger, so ties at the k-th value all stay. Then the nucleus
    over the softmax of what top-k left (`top_p < 1`, else off): in
    descending order a token stays while the mass before it is short of
    `top_p`, the one that crosses it included, and ties at the cut stay
    whole. One sort of the row finds both cuts."""
    V = lg.shape[-1]
    s = jnp.sort(lg, axis=-1, descending=True)

    def at(n):                      # s[b, n[b] - 1], as [B, 1]
        return jnp.take_along_axis(
            s, jnp.clip(n - 1, 0, V - 1)[:, None], axis=-1)

    kth = jnp.where(((top_k > 0) & (top_k < V))[:, None], at(top_k),
                    -jnp.inf)
    s = jnp.where(s >= kth, s, -jnp.inf)
    p = jnp.exp(s - s[:, :1])
    p = p / p.sum(axis=-1, keepdims=True)
    before = jnp.cumsum(p, axis=-1) - p          # non-decreasing
    n_kept = jnp.sum(before < top_p[:, None], axis=-1)
    cut = jnp.where((top_p < 1.0)[:, None], at(n_kept), -jnp.inf)
    return lg >= jnp.maximum(kth, cut)


def select_tokens(logits: jax.Array, prev: jax.Array, produce: jax.Array,
                  temperature: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, key: jax.Array) -> jax.Array:
    """[B] int32: each slot's newest token.

    logits [B, V] float32 of the step just dispatched; prev [B] int32, the
    ids before it; produce [B] bool, the lanes whose logits are a token's
    (a decode lane, or a prompt's last chunk): every other lane keeps its
    `prev`. A lane with `temperature <= 0` takes the first index of its
    row's maximum. A sampling lane divides by its temperature, keeps
    `kept_tokens` and makes one draw from the softmax over them (the
    maximum of the kept logits plus Gumbel noise), from `key` folded with
    the slot's index. The sort and the noise sit under a `lax.cond` on
    "some producing lane samples": a greedy batch pays for an argmax."""
    B, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    samples = produce & (temperature > 0)

    def draw(_):
        lg = logits / jnp.where(samples, temperature, 1.0)[:, None]
        kept = kept_tokens(lg, top_k, top_p)
        keys = jax.vmap(lambda slot: jax.random.fold_in(key, slot))(
            jnp.arange(B))
        noise = jax.vmap(lambda k: jax.random.gumbel(k, (V,), lg.dtype))(
            keys)
        drawn = jnp.argmax(jnp.where(kept, lg + noise, -jnp.inf), axis=-1)
        return jnp.where(samples, drawn.astype(jnp.int32), greedy)

    chosen = jax.lax.cond(samples.any(), draw, lambda _: greedy, None)
    return jnp.where(produce, chosen, prev)
