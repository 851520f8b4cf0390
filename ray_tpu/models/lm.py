"""Shared language-model loss plumbing used by every model family."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def split_lm_batch(batch: dict):
    """{"tokens": [B,T+1]} or {"inputs","targets"} -> (inputs, targets)."""
    if "tokens" in batch:
        return batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    return batch["inputs"], batch["targets"]


def _logz_gold(logits: jax.Array, targets: jax.Array):
    """(float32 logits, their logsumexp, the target's logit): `token_nll`'s
    lines, for the fused loss to share."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logits, logz, gold


def token_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """logits [B,T,V], targets [B,T] -> each token's negative log
    likelihood [B,T] float32; logits upcast to f32 for the softmax."""
    _, logz, gold = _logz_gold(logits, targets)
    return logz - gold


def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy, for a caller that holds logits
    already (the pipeline's last stage, evaluation)."""
    with jax.named_scope("unembed_loss"):
        return jnp.mean(token_nll(logits, targets))


# The most float32 logits one device holds at once in the fused loss
# (`loss_chunks`; measured in `chunked_cross_entropy`'s docstring).
LOGITS_CHUNK_BYTES = 2 << 30


def loss_chunks(batch: int, seq_len: int, vocab: int) -> tuple:
    """(K, sp): the fused loss takes each device's piece of the sequence
    in K equal chunks, and the sequence lies over sp devices. Read from
    what the call can observe, never from a model's name or an option: the
    bytes of the float32 logits a device would hold whole (its tokens
    times its share of V, by the active mesh's rules for "batch", "seq",
    "vocab") against LOGITS_CHUNK_BYTES. K is the smallest divisor of the
    device's sequence that brings a chunk under the budget; where the
    length has none short of twice the count needed (a prime length, say)
    the sequence stays whole, as `cross_entropy` over whole logits holds
    it, and the compiler says whether that fits."""
    from ray_tpu.parallel.mesh import axis_size, current_mesh, logical_to_spec

    mesh, shards, sp = current_mesh(), 1, 1
    if mesh is not None:
        parts = [(p,) if isinstance(p, str) else tuple(p or ())
                 for p in logical_to_spec("batch", "seq", "vocab")]
        parts += [()] * (3 - len(parts))
        shards = axis_size(mesh, *(a for p in parts for a in p))
        sp = axis_size(mesh, *parts[1])
    if seq_len % sp:
        sp = 1
    need = -(-batch * seq_len * vocab * 4 // (shards * LOGITS_CHUNK_BYTES))
    local = seq_len // sp
    for k in range(max(need, 1), min(2 * need, local + 1)):
        if local % k == 0:
            return k, sp
    return 1, sp


def _chunks_first(a: jax.Array, K: int, sp: int) -> jax.Array:
    """[B, T, ...] -> [K, B, T/K, ...]: chunk k holds the k-th piece of
    every device's part of the sequence."""
    B, T = a.shape[:2]
    a = a.reshape(B, sp, K, T // (sp * K), *a.shape[2:])
    return jnp.moveaxis(a, 2, 0).reshape(K, B, T // K, *a.shape[4:])


def _chunks_last(a: jax.Array, sp: int) -> jax.Array:
    """`_chunks_first`'s inverse: [K, B, T/K, ...] -> [B, T, ...]."""
    K, B, C = a.shape[:3]
    a = a.reshape(K, B, sp, C // sp, *a.shape[3:])
    return jnp.moveaxis(a, 0, 2).reshape(B, K * C, *a.shape[4:])


def _fused_loss(x, head, targets, K: int, sp: int, with_grads: bool):
    """The loss, and with `with_grads` (loss, (d(loss)/d(x), d(loss)/
    d(head))), both made from each chunk's logits while they are there."""
    from jax import lax

    from ray_tpu.parallel.mesh import constrain

    B, T, _ = x.shape
    acc_dtype = jnp.float32 if K > 1 else head.dtype

    def chunk(xc, tc):
        """((what is summed over chunks), what is kept a chunk)."""
        logits = constrain(xc @ head, "batch", "seq", "vocab")
        f32, logz, gold = _logz_gold(logits, tc)
        nll = jnp.sum(logz - gold)
        if not with_grads:
            return (nll,), None
        # d(mean nll)/d(logits) = (softmax - onehot) / (B T), in float32,
        # rounded to the logits' dtype where autodiff's transpose of the
        # upcast rounds it
        p = jnp.exp(f32 - logz[..., None])
        hit = lax.broadcasted_iota(jnp.int32, p.shape, 2) == tc[..., None]
        p = (jnp.where(hit, p - 1.0, p) / (B * T)).astype(logits.dtype)
        p = constrain(p, "batch", "seq", "vocab")
        dx = constrain(p @ head.T, "batch", "seq", "embed")
        dhead = jnp.einsum("bcd,bcv->dv", xc, p,
                           preferred_element_type=acc_dtype)
        return (nll, constrain(dhead, "embed", "vocab")), dx

    if K == 1:
        sums, dx = chunk(x, targets)
    else:
        init = (jnp.float32(0.0),)
        if with_grads:
            init += (jnp.zeros(head.shape, acc_dtype),)

        def body(acc, xt):
            sums, dx = chunk(*xt)
            return jax.tree.map(jnp.add, acc, sums), dx

        sums, dx = lax.scan(body, init, (_chunks_first(x, K, sp),
                                         _chunks_first(targets, K, sp)))
    loss = sums[0] / (B * T)
    if not with_grads:
        return loss
    dx = dx if K == 1 else _chunks_last(dx, sp)
    return loss, (dx, sums[1].astype(head.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _unembed_loss(x, head, targets, K, sp):
    with jax.named_scope("unembed_loss"):
        return _fused_loss(x, head, targets, K, sp, with_grads=False)


def _unembed_loss_fwd(x, head, targets, K, sp):
    with jax.named_scope("unembed_loss"):
        return _fused_loss(x, head, targets, K, sp, with_grads=True)


def _unembed_loss_bwd(K, sp, grads, g):
    with jax.named_scope("unembed_loss"):
        return (*((d.astype(jnp.float32) * g).astype(d.dtype)
                  for d in grads), None)


_unembed_loss.defvjp(_unembed_loss_fwd, _unembed_loss_bwd)


def chunked_cross_entropy(x: jax.Array, head: jax.Array,
                          targets: jax.Array) -> jax.Array:
    """Fused unembedding + mean cross-entropy: x [B,T,D] the final hidden
    state (already normed), head [D,V], both in the compute dtype, targets
    [B,T]. The one copy every model family's `loss_fn` shares.

    The value is `cross_entropy(x @ head, targets)`'s: operands in the
    compute dtype, float32 accumulation, logsumexp and target logit in
    float32 (`token_nll`'s own lines). It has its own differentiation rule:
    under `jax.grad` the forward pass makes, a chunk of the sequence at a
    time and while that chunk's logits are there, p = (softmax - onehot) /
    (B T) in float32, rounded to the compute dtype where autodiff rounds
    d(logits), then d(x) = p @ head^T and d(head) += x^T @ p (summed over
    chunks in float32, rounded once). It keeps d(x) `[B,T,D]` and d(head)
    `[D,V]`; the backward pass multiplies both by the incoming cotangent
    and does nothing else. So the vocabulary head is passed over three
    times a step (logits, d(x), d(head)), which is the mathematics, and no
    `[B,T,V]` array exists forward or backward. Autodiff of a scan over
    chunks would have to keep every chunk's probabilities (3.3 GB in bf16
    at OLMoE's 32,768 tokens) or make each chunk's logits again.

    The chunk (`loss_chunks`): the float32 logits a device would hold at
    once stay under LOGITS_CHUNK_BYTES, 2 GiB. Measured
    (benchmarks/loss_crossover.py on a TPU v5e, PR 34: forward + backward
    of this function alone at the training cells' per-device shapes, V =
    50,304; ms, and the program's temporaries in GB; `parent` is autodiff
    of whole logits for GPT-2, of the checkpointed scan for OLMoE):

                      small-1k          xl-1k            olmoe-4k
                      B20 T1024 D768    B8 T1024 D1600   B8 T4096 D2048
                      tied              tied             untied
      parent          31.17  6.18       24.94  2.47      171.77  2.47
      1 chunk         30.26  6.18       23.71* 2.47      124.96  9.89
      2 chunks        30.42* 3.23       26.88  1.42      126.53  5.42
      4 chunks        33.15  1.69       28.02  0.81      128.80* 2.95
      8 chunks        31.10  0.84       26.27  0.39      132.81  1.71
      16 chunks       32.92  0.39       32.95  0.26      136.15  1.09
      (* what 2 GiB gives)

    A larger chunk is faster (the float32 sum of d(head) is read and
    written once a chunk, and the products stay large), so the budget is as
    large as memory allows: OLMoE's step fits its chip at 4 chunks (16.27
    of 16.91 GB compiled for the v5e) and at 2 the compiler refuses it
    (16.08 GiB of 15.75); a chip's
    8,192 tokens of GPT-2 XL stay whole, where a second chunk costs 3 ms;
    GPT-2 small's 20,480 tokens go in two, 0.16 ms over one, and its step
    needs 12.67 GB where whole float32 logits made it 16.22. The target's
    logit by a masked sum in place of the gather would spare the float32
    copy of a chunk's logits a gather needs (temporaries 2.95 -> 1.30 GB,
    128.70 -> 127.58 ms at OLMoE's shape) but the compiler then reads the
    product's float32 accumulator and not its rounding to bf16, and the
    loss moves in its sixth digit: not taken, `token_nll` stays as it was.
    """
    K, sp = loss_chunks(*targets.shape, head.shape[1])
    return _unembed_loss(x, head, targets, K, sp)


# the shortest sequence at which the flash kernel beat XLA's dense attention
# on the chip (`resolve_attn_impl`)
FLASH_MIN_SEQ_LEN = 512


def resolve_attn_impl(attn_impl: str, seq_len: int) -> str:
    """Shared auto attention-implementation policy for all model families.

    auto → ring when the active mesh shards the sequence axis; else the
    Pallas flash kernel (`ops/flash_attention.py`) on the `tpu` backend
    from T=512 wherever its tiles divide the sequence (`tiles_divide`: T a
    multiple of 128); XLA's dense attention, which writes `[B, H, T, T]`
    scores to HBM, for shorter and other lengths and on the CPU test
    backend. What the rule reads is what the call can observe: backend,
    mesh, T.

    The crossover is measured (benchmarks/flash_crossover.py on a TPU v5e,
    PR 31; forward + backward of one layer's attention at GPT-2 small's
    heads and 20,480 tokens, ms dense / flash): T=128 1.20 / 4.56, T=256
    2.65 / 3.63, T=512 5.13 / 3.15, T=1024 10.03 / 3.68, T=2048 19.16 /
    5.13; at OLMoE's T=4096 and 128-wide heads the dense path does not fit
    the chip and the kernel takes 15.27. Below 512 a head is a single tile
    and a grid step costs more than the scores it keeps out of HBM.
    """
    if attn_impl != "auto":
        return attn_impl
    import jax

    from ray_tpu.ops.flash_attention import tiles_divide
    from ray_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return "ring"
    if (jax.default_backend() == "tpu" and seq_len >= FLASH_MIN_SEQ_LEN
            and tiles_divide(seq_len)):
        return "flash"
    return "dense"
