"""What the model families share: the language-model loss and the attention
policy of the training families, and (from "The serving families" down) what
a family served by `serve/llm.LLMEngine` borrows: seeded weights made a
layer at a time into a stack, the product that keeps a float32 activation
whole, the short convolution, and the lanes of a chunk."""

from __future__ import annotations

import functools
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


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


# ---------------------------------------------------------------------------
# The serving families (`models/__init__.py` has the protocol)
# ---------------------------------------------------------------------------
#
# Plain functions that a family calls with its own layer as an argument:
# what deepseek, brumby, granite and kimi each had a copy of. A family
# module holds its config, its `_init_layer` bodies, its cache's leaves, its
# layers' arithmetic and its two programs.

Params = Any


# -- weights, a layer at a time ---------------------------------------------

def normal(key, shape, std, dtype):
    """N(0, std) drawn in float32, held in `dtype`."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def ones(n: int) -> Params:
    """A norm's scale, float32 (it is used in float32)."""
    return {"scale": jnp.ones((n,), jnp.float32)}


@functools.lru_cache(maxsize=None)
def layer_program(make: Callable, *static):
    """The one compiled program that makes a kind of layer, `make(key, l,
    *static)` with `static` what selects the kind (the config, `dense`, the
    mixer's name): wherever a layer is made it is made by this program (a
    sum fused another way may round another way), so a layer made alone is,
    to the bit, the layer in the family's `init_params` tree."""
    return jax.jit(lambda key, l: make(key, l, *static))


def empty_stack(like: Params, n: int) -> Params:
    """Zeros [n, ...] for every leaf of `like` (arrays or their shapes),
    made where they will lie by one program."""
    return jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros((n,) + a.shape, a.dtype), like))()


@partial(jax.jit, donate_argnums=(0,))
def put_layer(stack: Params, layer: Params, i) -> Params:
    """The stack with `layer` as its entry i, written where the stack lies
    (donated). A leaf of `layer` that has a leading axis of its own where
    the stack's leaf has none (Kimi's held experts, [E', ...] a layer in a
    stack [layers x E', ...]) is entries i E' .. (i + 1) E'."""
    def into(s, a):
        a = a.reshape((-1,) + s.shape[1:])
        return lax.dynamic_update_slice_in_dim(s, a, i * a.shape[0], 0)

    return jax.tree.map(into, stack, layer)


def stack_layers(make: Callable, n: int) -> Params:
    """`make(i)`, the tree of a stack's entry i (a family's `init_layer` at
    the layer that entry is), for i in 0..n-1, stacked on a leading axis.
    The stack is allocated once and each entry is written into it, donated,
    so the most that exists beside the tree is one layer: no float32 copy
    of the tree and no second copy of a stack, which is what lets a 20.3 GB
    model start on a 16.9 GB chip (PERF.md section 4, PR 29)."""
    stack = empty_stack(jax.eval_shape(lambda: make(0)), n)
    for i in range(n):
        stack = put_layer(stack, make(i), jnp.int32(i))
    return stack


def resident_params(params: Params, cfg) -> Params:
    """A family whose `init_params` makes the tree a replica holds has
    nothing to convert: its `resident_params` is this."""
    del cfg
    return params


def layer_weights(stack: Params, i, turn=None) -> Params:
    """Entry i of a stack: its weights sliced where they lie. `turn`, in the
    body of a loop whose every turn takes the same entry (`each_slot`'s b),
    makes the slice that turn's own: the compiler lifts a slice that no
    turn changes out of the loop, joins it with the first lanes' and copies
    every matrix out of the stack, 1.6 GB a chunk step of granite's."""
    if turn is not None:
        i, _ = lax.optimization_barrier((i, turn))
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def layers_in_runs(kinds: list, layer: Callable, carry):
    """The layers of a model whose layers are of several kinds, walked as
    runs of one kind: `layer(kind, l, carry) -> carry` for l = 0 .. in
    order, `kinds[l]` layer l's (anything hashable that sorts). One loop over
    the runs, whose body holds one loop a kind, and a kind's loop turns as
    many times as the run is long if the run is of that kind and not at all
    if it is not. No branch takes a layer's kind (a leaf that passes through
    a conditional untouched is copied on its way), the program holds a body a
    kind whatever the depth, and nothing of a layer stands outside the runs'
    loop. (`models/mimo.py`; `kimi.py`, `nemotron.py` and `exaone.py` each
    hold the lines this was written from: ROADMAP D28.)"""
    runs = []                             # [kind, first layer, layers]
    for l, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][2] += 1
        else:
            runs.append([kind, l, 1])
    bodies = sorted(set(kinds))
    first_layer = jnp.asarray([first_l for _, first_l, _ in runs])
    turns = {kind: jnp.asarray([n if k == kind else 0 for k, _, n in runs])
             for kind in bodies}

    def run(r, carry):
        start = first_layer[r]
        for kind in bodies:
            carry = lax.fori_loop(start, start + turns[kind][r],
                                  partial(layer, kind), carry)
        return carry

    return lax.fori_loop(0, len(runs), run, carry)


# -- a layer's arithmetic -----------------------------------------------------

def weight(p, dtype):
    """A weight as a product reads it: in the compute dtype (a conversion
    only where the replica holds it in another)."""
    with jax.named_scope("weights_cast"):
        return p.astype(dtype)


def dot(x, w, dtype):
    """x [..., K] float32 times the weight w [K, N] -> [..., N] float32. The
    operands are the compute dtype's, and x goes as the two pieces that add
    up to it (its rounding and what the rounding left: `ops/pieces.py`),
    side by side on the rows of one product: one pass of the weight, which
    is what a decode step's product costs, and none of the activation's
    rounding in the result. With that rounding in every product of 80
    sublayers granite's logits lay 1.1% of their spread from the
    reference's, as far as a state held in bfloat16 puts them (PERF.md, PR
    38). A float32 compute dtype is one product at full precision."""
    # here, not at the top: `ray_tpu.ops` brings Pallas in, a second of
    # every process's start that imports a model (gpt2 and llama import
    # this module)
    from ray_tpu.ops.pieces import pieces

    x = x.astype(jnp.float32)
    if dtype == jnp.float32:
        return jnp.dot(x, w.astype(jnp.float32),
                       precision=lax.Precision.HIGHEST)
    both = jnp.dot(pieces(x, dtype), weight(w, dtype),
                   preferred_element_type=jnp.float32)
    return both[0] + both[1]


def over_lanes(per_head, lanes: int):
    """[..., H] -> [..., H lanes]: a head's value over its lanes."""
    return jnp.repeat(per_head, lanes, axis=-1)


def short_conv(x, taps, window, ok, bias=None):
    """The causal depthwise convolution of x [B,M,F] behind `window`
    [B, (K-1) F], the K - 1 inputs before it side by side, by `taps` [K,F]
    (tap k multiplies the input K - 1 - k positions back) and `bias` [F] if
    the family has one, then silu: -> (out [B,M,F], the window left
    behind), the K - 1 inputs that end at each slot's last valid lane by
    `ok` [B,M]. A slot with no valid lane keeps its window bit for bit. One
    lane (M = 1: every slot's first lane, the decode program) moves the
    window on by one input; M lanes gather it. Call it under the family's
    own scope: the per-layer readers sum by scope."""
    K = taps.shape[0]
    B, M, F = x.shape
    if M == 1:
        ext = jnp.concatenate([window, x[:, 0]], axis=-1)          # [B, K F]
        out = sum(taps[k] * ext[:, k * F:(k + 1) * F] for k in range(K))
        new = jnp.where(ok, ext[:, F:], window)
        out = out[:, None]
    else:
        ext = jnp.concatenate([window.reshape(B, K - 1, F), x], axis=1)
        out = sum(taps[k] * ext[:, k:k + M] for k in range(K))
        at = ok.sum(axis=1)[:, None] + jnp.arange(K - 1)[None, :]  # [B,K-1]
        new = jnp.take_along_axis(ext, at[:, :, None], axis=1)
        new = jnp.where(ok.any(axis=1)[:, None],
                        new.reshape(B, (K - 1) * F), window)
    return jax.nn.silu(out if bias is None else bias + out), new


# -- the lanes of a chunk ----------------------------------------------------
#
# `prefill_chunk`'s contract, for every family (`gpt2.prefill_chunk` has the
# signature): tokens [B, C] (a left-aligned chunk a slot), pos0 [B] (the
# position of the chunk's first token), length [B] (valid tokens, 0..C),
# active [B] -> (logits [B, vocab] float32 at each slot's last valid lane,
# the cache). An inactive or zero-length slot leaves every leaf of the cache
# as it was, bit for bit (rows, state and window alike), and its logits are
# garbage. Recurrent state continues whatever the slot held: a new
# sequence's slot is the caller's to zero. pos0 + length <= T and C <= T are
# the caller's to keep. `decode_step` is the same program at one lane a slot.
#
# A program computes a lane only where the plan put a token (PERF.md, PRs 38
# and 39). Every slot's first lane goes through a layer all slots at once:
# that is the whole decode program, and in the chunk program every decode
# lane riding along and the first token of every chunk. The lanes after it
# go a slot at a time and only the slots that have any (`each_slot`), so a
# chunk step costs the decode program's time plus a term a slot that
# prefills, not B x C lanes whoever prefills.
#
# That holds for the half of a layer that mixes a sequence (attention, a
# state's pass, the cache writes). The half that knows nothing of slots (a
# router, routed and shared experts, a dense MLP) takes the first lanes and
# the valid further lanes of every slot as the rows of one call
# (`lane_rounds`, `pack_lanes`, `unpack_lanes`): its time at these few rows
# is the weights it reads, and a call a slot read them once more a slot that
# prefilled (PERF.md, PR 60).

def slots_first(has):
    """has [B] bool -> (the indices of the slots that have it first, in
    index order, in a [B] int32 array; how many they are): what `each_slot`
    turns over."""
    return (jnp.argsort(~has, stable=True).astype(jnp.int32),
            has.sum().astype(jnp.int32))


def split_lanes(x, ok, pad: bool):
    """x [B,C,D] and ok [B,C] (a lane is a token's) -> (first [B,1,D], on
    [B], rest, further, prefilling): every slot's first lane and whether it
    is valid; for C > 1 the lanes after it, rest [B,M,D] with further
    [B,M], and `slots_first` of the slots that have any (None, all three,
    at C = 1: the decode program never enters the loop). M is C - 1, or C
    with `pad`, the last lane padding: a family with routed experts pads,
    so that the rows a slot's experts sort come in whole tiles of the
    grouped matmul (`ops/grouped_matmul._tiling` halves a tile until it
    divides the rows: 127 lanes x 6 would be tiles of 2 rows)."""
    first, on = x[:, :1], ok[:, 0]
    if x.shape[1] == 1:
        return first, on, None, None, None
    rest, further = x[:, 1:], ok[:, 1:]
    if pad:
        rest = jnp.pad(rest, ((0, 0), (0, 1), (0, 0)))
        further = jnp.pad(further, ((0, 0), (0, 1)))
    with jax.named_scope("embed"):
        prefilling = slots_first(further.any(axis=1))
    return first, on, rest, further, prefilling


def join_lanes(first, rest, C: int):
    """`split_lanes`' inverse: x [B,C,D]."""
    if rest is None:
        return first
    return jnp.concatenate([first, rest[:, :C - 1]], axis=1)


def last_valid_lane(x, length):
    """x [B,C,D] -> [B,D]: each slot's lane length - 1 (lane 0 of a slot
    with none, whose logits are garbage)."""
    last = jnp.clip(length - 1, 0, x.shape[1] - 1)
    return jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]


def each_slot(slots, slot: Callable, carry):
    """`slot(b, carry) -> carry` for each slot b of `slots` =
    `slots_first(..)`, in index order: the loop turns as often as there are
    such slots and not at all when there are none, so a slot without lanes
    costs nothing and its part of every carried leaf is never touched. (A
    loop over all B slots with a `lax.cond` each cost three small
    operations a slot a layer, 3,500 a chunk step at Kimi's 128 slots:
    PERF.md, PR 40; and a leaf that passes through a conditional untouched
    may be copied on its way.)

    `slots` may be a stretch of them, (indices, start, stop): the slots of
    one round of `lane_rounds`.

    The body stays the family's, and it is the half of a layer that mixes a
    sequence (the token-wise half takes every slot's lanes in one call:
    `pack_lanes`), because how a layer's weights reach it is measured, and a
    new family reads this first. No loop slices a routed
    expert's matrix, a copy for its kernel: a body reads the stack of every
    layer's experts whole, as the first lanes do (PERF.md, PR 48). Where the
    layers' loop is a scan over the other weights, close over the layer as
    the scan holds it (deepseek; sliced again, the same). Where the layers'
    loop indexes the stack, slice the layer inside the body too (granite,
    kimi: `layer_weights`, with the body's b as its `turn` where the
    compiler would lift the slice out again): sliced once for both the
    first lanes and the loop, the compiler copies every matrix out of the
    stack, 6.4 GB a chunk step (PERF.md, PR 38).

    And what the loop writes in place, the first lanes have to have read:
    where nothing a body takes comes from the first lanes' pass (granite:
    no expert counts go in), the compiler cannot tell which is first and
    copies the leaf for the loop, 1.6 GB a leaf a layer. Tie them before
    the loop: `first, cache = lax.optimization_barrier((first, cache))`, or
    `(first, rest)` where a leaf's rows are narrower than 128 lanes: such a
    leaf comes out of a barrier in the default layout, re-laid on its way in
    and out of the layers' loop (Kimi's `k_rope`, 0.5 GB: PERF.md, PR 60),
    and the loop waits for its lanes as well as for its leaves."""
    indices, *stretch = slots
    start, stop = stretch if len(stretch) == 2 else (0, *stretch)
    return lax.fori_loop(
        start, stop, lambda n, carry: slot(
            lax.dynamic_index_in_dim(indices, n, 0, keepdims=False), carry),
        carry)


def slot_lanes(b, rest, ok, pos):
    """What a body of `each_slot` takes of the further lanes: slot b's own
    xb [1,M,D], okb [1,M] and the position of the first of them, at [1]."""
    M, D = rest.shape[1:]
    return (lax.dynamic_slice(rest, (b, 0, 0), (1, M, D)),
            lax.dynamic_slice(ok, (b, 0), (1, M)),
            lax.dynamic_slice(pos, (b,), (1,)))


def put_lanes(rest, xb, b):
    """`rest` with slot b's lanes xb [1,M,D] back in their place."""
    return lax.dynamic_update_slice(rest, xb, (b, 0, 0))


# -- every lane of a step as the rows of one call ------------------------------

def lanes_a_dispatch(B: int, C: int) -> int:
    """N, the rows a token-wise block takes in one call: the engine's
    default `max_num_batched_tokens` (`serve/llm.py`), so that every slot's
    first lane and B or C further lanes, whichever is more, ride together."""
    return max(2 * B, B + C)


def lane_rounds(further, prefilling):
    """How a step's lanes go through a layer's token-wise half: what
    `pack_lanes` reads, from `split_lanes`' further [B,M] and prefilling.
    Round 0 is every slot's first lane and, behind them in slot order, the
    valid further lanes of the leading prefilling slots whose lanes all fit
    the N - B rows left; each prefilling slot after those is a round of its
    own (what it cost before the lanes were packed). A step the engine's
    default budget plans with every slot busy is one round, and so is every
    step whose valid further lanes are N - B at most. A slot's valid further
    lanes are its leading ones (a chunk is left-aligned), and M is C: the
    families that pack pad (`split_lanes`). None in the decode program,
    which has no further lane."""
    if further is None:
        return None
    B, M = further.shape
    indices, count = prefilling
    with jax.named_scope("embed"):
        lanes = further.sum(axis=1).astype(jnp.int32)                  # [B]
        before = jnp.cumsum(lanes) - lanes
        fits = (lanes > 0) & (before + lanes <= lanes_a_dispatch(B, M) - B)
        return {"indices": indices, "lanes": lanes, "before": before,
                "together": fits.sum().astype(jnp.int32),
                "rows": jnp.max(jnp.where(fits, before + lanes, 0)),
                "count": 1 + count - fits.sum().astype(jnp.int32)}


def round_slots(rounds, g):
    """Round g's prefilling slots, for `each_slot`."""
    alone = rounds["together"] + g - 1
    return (rounds["indices"], jnp.where(g == 0, 0, alone),
            jnp.where(g == 0, rounds["together"], alone + 1))


def _round_rows(rounds, g):
    """(the row of the packed further lanes that round g starts at, how many
    they are)."""
    B = rounds["lanes"].shape[0]
    s = rounds["indices"][jnp.clip(rounds["together"] + g - 1, 0, B - 1)]
    return (jnp.where(g == 0, 0, rounds["before"][s]),
            jnp.where(g == 0, rounds["rows"], rounds["lanes"][s]))


def pack_lanes(first, on, rest, rounds, g):
    """Round g's lanes as the rows of one call: first [B,1,D] with on [B]
    and rest [B,M,D] -> (rows [1,N,D], ok [1,N]), N `lanes_a_dispatch`'s
    of the program's shapes. The B first lanes, then
    the round's slots' valid further lanes in slot order; a row past them
    holds some lane's values or zeros and is not `ok`, nor are the first
    lanes in a round after 0. A slot's lanes come as one window of M rows,
    the next slot's laid over its invalid tail: no row is gathered."""
    B, M, D = rest.shape
    N = lanes_a_dispatch(B, M)
    start, many = _round_rows(rounds, g)

    def slot(b, rows):
        xb = lax.dynamic_slice(rest, (b, 0, 0), (1, M, D))[0]
        return lax.dynamic_update_slice(
            rows, xb, (B + rounds["before"][b] - start, 0))

    rows = jnp.concatenate(
        [first[:, 0], jnp.zeros((N - B + M, D), first.dtype)])
    rows = each_slot(round_slots(rounds, g), slot, rows)[:N]
    ok = jnp.concatenate([on & (g == 0), jnp.arange(N - B) < many])
    return rows[None], ok[None]


def unpack_lanes(first, rest, further, rows, rounds, g):
    """`pack_lanes`' inverse: the block's rows [1,N,D] back in `first` (in
    round 0) and in the valid further lanes of the round's slots; no other
    lane of `rest` changes."""
    B, M, D = rest.shape
    start, _ = _round_rows(rounds, g)
    rows = jnp.concatenate([rows[0], jnp.zeros((M, D), rows.dtype)])

    def slot(b, rest):
        new = lax.dynamic_slice(
            rows, (B + rounds["before"][b] - start, 0), (M, D))[None]
        xb = lax.dynamic_slice(rest, (b, 0, 0), (1, M, D))
        okb = lax.dynamic_slice(further, (b, 0), (1, M))
        return put_lanes(rest, jnp.where(okb[:, :, None], new, xb), b)

    return (jnp.where(g == 0, rows[:B, None], first),
            each_slot(round_slots(rounds, g), slot, rest))


def row_buckets(B: int, M: int) -> tuple:
    """The rows a call may be cut to, ascending: the first lanes and one to
    four quarters of the N - B rows behind them. For a family whose
    token-wise half costs by the row and not by the weights it reads
    (LongCat's two-piece products of 6,144 x 24,576 are the MXU's time at
    128 rows already): a branch a bucket, a round takes the smallest that
    holds its rows (`round_bucket`)."""
    cap = lanes_a_dispatch(B, M) - B
    return tuple(sorted({B + -(-cap * k // 4) for k in (1, 2, 3, 4)}))


def round_bucket(rounds, g, buckets: tuple):
    """The index of the smallest of `buckets` that holds round g's rows."""
    rows = rounds["lanes"].shape[0] + _round_rows(rounds, g)[1]
    return jnp.sum(rows > jnp.asarray(buckets[:-1])).astype(jnp.int32)


def all_lanes(block, first, on, rest, further, rounds, carry):
    """A layer's token-wise half over every valid lane of a chunk step:
    `block(x [1,N,D], ok [1,N], g, carry) -> (x, carry)`, called once a
    round (`lane_rounds`: once a step the default budget plans), its rows
    put back. g is the round, for the body's own slice of its weights
    (`layer_weights`' `turn`). -> (first, rest, carry)."""
    def one(g, state):
        first, rest, carry = state
        x, ok = pack_lanes(first, on, rest, rounds, g)
        x, carry = block(x, ok, g, carry)
        return (*unpack_lanes(first, rest, further, x, rounds, g), carry)

    return lax.fori_loop(0, rounds["count"], one, (first, rest, carry))


# -- grouped-head attention over rows by head --------------------------------
#
# What four families' softmax layers share (`models/granite.py`, `kimi.py`
# for Solar, `nemotron.py`, `exaone.py`): keys and values by the G key-value
# heads. A head of 64 lanes: leaves [layers, slots, G, d, T], the positions
# on the lanes, which is how the TPU's compiler lays a `[.., T, 64]` array
# out anyway (granite; `ops/rows_write.py` writes a decode step's one
# position a slot); where it has 128 they are [layers, slots, G, T, d], a
# position a row of 128 lanes (Solar: handed leaves with the positions last,
# the compiler re-laid both, 2.1 GB each, on the way into every chunk step
# and out of it, because the further lanes slice the positions by the
# block). Which way round a leaf lies is read off its shape against the
# head's d (`ops/rows_write.positions_last`). The caller keeps its named
# scopes (`gqa_project`, `kv_update`, `gqa_attend`): the per-layer readers
# sum by them.

GQA_BLOCK = 1024             # positions a turn of `gqa_attend_blocks` reads
_MASKED = -1e30


def gqa_qkv(u, p, G: int, R: int, d: int, dtype, q_dtype=None):
    """The normed input u [B,M,D] float32 -> q [B,M,G,R,d], k, v [B,M,G,d]
    in the compute dtype, by `p`'s `wq`, `wk`, `wv`; q in `q_dtype` where
    the family keeps it whole (float32)."""
    B, M, _ = u.shape
    return tuple(
        dot(u, p[name], dtype).astype(to).reshape(B, M, *shape)
        for name, shape, to in (("wq", (G, R, d), q_dtype or dtype),
                                ("wk", (G, d), dtype), ("wv", (G, d), dtype)))


def _row_pieces(x, dtype):
    """x [..., Q, n] -> the rows a product with cached rows of `dtype`
    takes: x itself where it is of that dtype, else its two pieces of it
    (`ops/pieces.py`'s arithmetic) stacked on the rows, [..., 2 Q, n]: the
    cached rows pass once, and the result's halves add up
    (`_rows_added`)."""
    if x.dtype == dtype:
        return x
    from ray_tpu.ops.pieces import pieces    # not at the top: `dot` has why

    return jnp.concatenate(list(pieces(x, dtype)), axis=-2)


def _rows_added(y, Q: int):
    return y if y.shape[-2] == Q else y[..., :Q, :] + y[..., Q:, :]


def _softmax(scores, sink=None):
    """Over the last axis; with `sink` [...], a score a query that takes its
    share of the probability and has no entry of its own (MiMo's sliding
    layers: it weighs no value)."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    sink = sink[..., None]
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    p = jnp.exp(scores - m)
    return p / (jnp.sum(p, axis=-1, keepdims=True) + jnp.exp(sink - m))


def gqa_attend(q, k, v, at, scale: float, dtype, sink=None):
    """q [..., Q, d] at positions `at` [..., Q] over the cached rows k, v
    [..., d, T] (or [..., T, d]; or keys [..., d, T] beside values
    [..., T, n] of another width, which the result then has) of its
    key-value head -> [..., Q, d]
    float32: scores times `scale`, causal softmax, weighted values. Every
    one of the T positions is read, whatever `at` is. q in the rows' dtype
    and the probabilities rounded to it go as one piece (granite); a float32
    q and its probabilities as the two pieces that add up to them (Solar,
    which states a float32 q: as one piece its logits lie 0.005-0.009 from
    the reference's where the two pieces' lie 0.0001-0.0007, PERF.md PR 49)."""
    from ray_tpu.ops.rows_write import positions_last  # `dot` has why here

    last = positions_last(k.shape, q.shape[-1])
    rows = "dt" if last else "td"
    values = "nt" if last and v.shape == k.shape else "tn"
    T = k.shape[-1 if last else -2]
    Q, whole = q.shape[-2], q.dtype != dtype
    scores = _rows_added(jnp.einsum(
        f"...qd,...{rows}->...qt", _row_pieces(q, dtype), k,
        preferred_element_type=jnp.float32), Q)
    seen = jnp.arange(T) <= at[..., None]
    probs = _softmax(jnp.where(seen, scores * scale, _MASKED), sink)
    probs = _row_pieces(probs, dtype) if whole else probs.astype(dtype)
    return _rows_added(jnp.einsum(f"...qt,...{values}->...qn", probs, v,
                                  preferred_element_type=jnp.float32), Q)


def gqa_blocks(last, T: int):
    """How many turns `gqa_attend_blocks` takes to reach position `last`,
    and the positions a turn reads."""
    block = min(GQA_BLOCK, T)
    return last // block + 1, block


def gqa_attend_blocks(q, ck, cv, l, slot, at, last, scale: float, dtype):
    """`gqa_attend` for one slot's queries q [G, Q, d] at positions `at`
    [G, Q] against layer l of the leaves ck, cv [L,B,G,T,d], a block of
    positions at a time and only as far as position `last` (the slot's
    furthest query): the block's scores [G, Q, block] float32, the running
    maximum, sum and weighted values, one division at the end. The plain
    form's scores for a chunk's Q = R M queries are [G, Q, T] floats, 0.84
    GB at 8 x 128 queries and 25,600 positions; a block's are 34 MB (twice
    that for a float32 q's two pieces). The precision is the plain form's:
    q and the probabilities as one piece or as two, by q's dtype. Keys that
    hold the positions on the lanes, ck [L,B,G,d,T], stand beside values
    cv [L,B,G,T,n] of their own width, which is the result's."""
    from ray_tpu.ops.rows_write import positions_last  # `dot` has why here

    G, Q, d = q.shape
    keys_last = positions_last(ck.shape, d)
    T, n = cv.shape[3:]
    turns, block = gqa_blocks(last, T)
    whole = q.dtype != dtype
    q = _row_pieces(q, dtype)

    def turn(j, carry):
        m, s, acc = carry
        # the last block of a T that no block divides starts early: the
        # positions before j block were the turn before's
        start = jnp.minimum(j * block, T - block)
        if keys_last:
            k = lax.dynamic_slice(ck, (l, slot, 0, 0, start),
                                  (1, 1, G, d, block))[0, 0]
        else:
            k = lax.dynamic_slice(ck, (l, slot, 0, start, 0),
                                  (1, 1, G, block, d))[0, 0]
        v = lax.dynamic_slice(cv, (l, slot, 0, start, 0),
                              (1, 1, G, block, n))[0, 0]
        t = start + jnp.arange(block)
        scores = _rows_added(jnp.einsum(
            "gqd,gdt->gqt" if keys_last else "gqd,gtd->gqt", q, k,
            preferred_element_type=jnp.float32), Q) * scale
        seen = (t >= j * block) & (t <= at[..., None])
        scores = jnp.where(seen, scores, _MASKED)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # a block that holds nothing a query may see leaves it as it was
        probs = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
        grown = jnp.exp(m - m_new)
        s = grown * s + jnp.sum(probs, axis=-1)
        probs = _row_pieces(probs, dtype) if whole else probs.astype(dtype)
        acc = grown[..., None] * acc + _rows_added(jnp.einsum(
            "gqt,gtd->gqd", probs, v, preferred_element_type=jnp.float32), Q)
        return m_new, s, acc

    _, s, acc = lax.fori_loop(
        0, turns, turn, (jnp.full((G, Q), _MASKED, jnp.float32),
                         jnp.zeros((G, Q), jnp.float32),
                         jnp.zeros((G, Q, n), jnp.float32)))
    return acc / jnp.maximum(s, 1e-30)[..., None]


def gqa_write_slot(c, l, slot, val, pos, ok):
    """Layer l of the carried leaf c [L,B,G,d,T] (or [L,B,G,T,d]) takes val
    [M,G,d] at positions pos.. of slot `slot` where ok [M]: one window of
    W >= M positions read, blended and written in place
    (`dynamic_update_slice` clamps its start near the end of the sequence,
    so an unmasked block write would smear garbage lanes over valid earlier
    positions). The lanes are moved by a 0/1 matrix: exact, one product of
    1 a lane."""
    from ray_tpu.ops.rows_write import TILE, positions_last  # as above

    G = c.shape[2]
    last = positions_last(c.shape, val.shape[-1])
    d, T = c.shape[3:] if last else c.shape[3:][::-1]
    M = val.shape[0]
    W = min(T, max(M, TILE))
    start = jnp.clip(pos, 0, T - W)
    hit = ((jnp.arange(W)[:, None] - (pos - start)) == jnp.arange(M)) & ok
    moved = jnp.einsum("wm,mgd->gdw" if last else "wm,mgd->gwd",
                       hit.astype(val.dtype), val,
                       precision=lax.Precision.HIGHEST)
    at = (l, slot, 0, 0, start) if last else (l, slot, 0, start, 0)
    old = lax.dynamic_slice(c, at, (1, 1, G) + moved.shape[1:])
    written = hit.any(axis=-1)
    new = jnp.where(written if last else written[:, None], moved,
                    old[0, 0])
    return lax.dynamic_update_slice(c, new[None, None], at)


# -- a sliding window's rows, a ring a slot -----------------------------------
#
# A softmax layer that attends the last W positions alone (K-EXAONE's
# sliding layers, `models/exaone.py`) keeps a slot's keys and values as a
# ring, a leaf [layers, slots, G, W, d]: position p at row p mod W. A ring
# whose newest position is `newest` holds position newest - ((newest - r)
# mod W) at row r, and the row is live iff that is not negative: what a slot
# held before (zeros, another request's rows) lies at the rows the sequence
# has not reached. A decode step's one token a slot goes through
# `ops/gqa_attend.py` (`ring=True`); these are the plain forms, the chunk
# program's further lanes and the CPU's decode step.

def ring_positions(newest, W: int):
    """The position each of a ring's W rows holds when the newest position
    written is `newest` [...] -> [..., W]; negative: the row is not the
    sequence's."""
    newest = jnp.asarray(newest)[..., None]
    return newest - (newest - jnp.arange(W)) % W


def gqa_attend_band(q, k, v, t, at, window: int, scale: float, dtype,
                    sink=None):
    """q [..., Q, d] at positions `at` [..., Q] over rows k [..., S, d] and
    v [..., S, n] that hold the positions t [..., S] -> [..., Q, n] float32:
    a query sees the rows with at - window < t <= at and t >= 0, wherever
    they lie, and its `sink` [..., Q] where it has one (`_softmax`). The
    precision is `gqa_attend`'s, piece for piece."""
    Q, whole = q.shape[-2], q.dtype != dtype
    scores = _rows_added(jnp.einsum(
        "...qd,...td->...qt", _row_pieces(q, dtype), k,
        preferred_element_type=jnp.float32), Q)
    t, at = t[..., None, :], at[..., None]
    seen = (t >= 0) & (t <= at) & (t > at - window)
    probs = _softmax(jnp.where(seen, scores * scale, _MASKED), sink)
    probs = _row_pieces(probs, dtype) if whole else probs.astype(dtype)
    return _rows_added(jnp.einsum("...qt,...td->...qd", probs, v,
                                  preferred_element_type=jnp.float32), Q)


def gqa_attend_ring(q, ck, cv, l, slot, k, v, at, pos, scale: float, dtype,
                    sink=None):
    """One slot's queries q [G, Q, d] at positions `at` [G, Q], a chunk's
    further lanes whose first stands at `pos`, against layer l of the rings
    ck, cv [L,B,G,W,d] as the lane before them left them (newest position
    pos - 1) and the chunk's own keys and values k, v [M,G,d] at pos ..: a
    band of W over both. The ring is read here and written after
    (`ring_write_slot`): a chunk's lanes overwrite rows that lanes before
    them still read. A ring of keys that holds the positions on the lanes,
    ck [L,B,G,d,W] (`ops/rows_write.ring_positions_last`), is turned as it
    is read, one slot's; `sink` [G, Q] is `gqa_attend_band`'s."""
    from ray_tpu.ops.rows_write import ring_positions_last  # as above

    G, W = cv.shape[2:4]
    M = k.shape[0]
    old_k, old_v = (lax.dynamic_slice(c, (l, slot, 0, 0, 0),
                                      (1, 1, G) + c.shape[3:])[0, 0]
                    for c in (ck, cv))
    if ring_positions_last(ck.shape, k.shape[-1]):
        old_k = jnp.swapaxes(old_k, 1, 2)
    t = jnp.concatenate([ring_positions(pos - 1, W), pos + jnp.arange(M)])
    rows_k = jnp.concatenate([old_k, jnp.swapaxes(k, 0, 1)], axis=1)
    rows_v = jnp.concatenate([old_v, jnp.swapaxes(v, 0, 1)], axis=1)
    return gqa_attend_band(q, rows_k, rows_v, t, at, W, scale, dtype, sink)


def ring_write_slot(c, l, slot, val, pos, n):
    """Layer l of the rings c [L,B,G,W,d] takes the first n of val [M,G,d],
    a chunk's lanes at positions pos .., at rows (pos + m) mod W of slot
    `slot`: row r takes the last of them that falls on it (the lane at the
    position the ring holds there once position pos + n - 1 is its newest),
    and keeps what it has where none does. The lanes are moved by a 0/1
    matrix (`gqa_write_slot`: exact). A ring [L,B,G,d,W] takes them
    along its lanes."""
    from ray_tpu.ops.rows_write import ring_positions_last  # as above

    last = ring_positions_last(c.shape, val.shape[-1])
    G, W = c.shape[2], c.shape[4 if last else 3]
    M = val.shape[0]
    lane = ring_positions(pos + n - 1, W) - pos                       # [W]
    hit = (lane[:, None] == jnp.arange(M)) & (lane >= 0)[:, None]
    moved = jnp.einsum("wm,mgd->gdw" if last else "wm,mgd->gwd",
                       hit.astype(val.dtype), val,
                       precision=lax.Precision.HIGHEST)
    at = (l, slot, 0, 0, 0)
    old = lax.dynamic_slice(c, at, (1, 1, G) + c.shape[3:])
    written = hit.any(axis=-1)
    new = jnp.where(written if last else written[:, None], moved, old[0, 0])
    return lax.dynamic_update_slice(c, new[None, None], at)
