"""A wide activation as the narrow pieces that add up to it, at the XLA
level: what lets a product keep a float32 operand whole on an MXU that
multiplies bfloat16."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def pieces(x: jax.Array, dtype, n: int = 2, axis: int = 0) -> jax.Array:
    """x float32 -> its `n` pieces in `dtype`, stacked on a new axis `axis`:
    x's rounding, the rounding of what that left, and so on; the last piece
    is what is left. Two bfloat16 pieces hold 16 of a float32's 24
    significant bits (a product of them against a bfloat16 weight, summed,
    carries none of the activation's rounding that a reader of logits can
    see: PERF.md, PR 38), three hold all of a normal x.

    `reduce_precision`, not a pair of conversions: `x - x.astype(dtype)
    .astype(float32)` is the compiler's to simplify, and the low piece
    would be zero (PERF.md, PR 29). Inside a Pallas body the simplifier
    never sees the pair, and `ops/expert_mlp.py` splits that way."""
    bits = jnp.finfo(dtype)
    out = []
    for _ in range(n - 1):
        piece = lax.reduce_precision(x, exponent_bits=bits.nexp,
                                     mantissa_bits=bits.nmant)
        out.append(piece)
        x = x - piece
    return jnp.stack(out + [x], axis=axis).astype(dtype)
