"""GPT-2 family in pure JAX, designed TPU-first.

Capability target: the reference's north-star config "Ray Train GPT-2-125M
data-parallel" (/root/repo/BASELINE.json) — but built the XLA way rather than
as a torch port:

- layers are *stacked* (leading `n_layer` dim on every block param) and the
  forward pass is a single `lax.scan` over them: one compiled block, O(1)
  compile time in depth, and XLA can pipeline HBM prefetch of layer weights;
- compute in bfloat16 (MXU-native), params + softmax/loss in float32;
- every activation is annotated with logical axes (`batch`/`seq`/`embed`/...)
  so the same code runs dp/fsdp/tp/sp sharded under any mesh from
  `ray_tpu.parallel.mesh.build_mesh` — XLA inserts the ICI collectives;
- `jax.checkpoint` (remat) around each block trades FLOPs for HBM;
- every part of a step program sits in a `jax.named_scope` (`embed`, `ln`,
  `attn`, `mlp`, `unembed_loss`, `layers`, `kv_update`, `weights_cast`):
  metadata only, it names each compiled operation's origin (`tf_op` in a
  device trace), which `benchmarks/chip/metrics/_scopes.py` sums device
  time by.

No dropout in round 1 (the reference benchmark config trains without it).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm
from ray_tpu.parallel.mesh import constrain, logical_to_spec

Params = Any  # nested dict pytree


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304          # GPT-2's 50257 padded up to a 128 multiple (MXU tiling)
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True
    # rematerialization policy: "full" recomputes everything in the bwd
    # pass; "dots" saves matmul outputs (jax dots_with_no_batch_dims
    # policy) — most of remat=False's speed at a fraction of the memory
    remat_policy: str = "full"
    # attention implementation: auto | dense | flash (pallas) | ring | ulysses
    # auto: ring when the active mesh has sp>1, flash on TPU, dense otherwise
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @classmethod
    def preset(cls, name: str, **overrides) -> "GPT2Config":
        presets = {
            "gpt2-125m": dict(n_layer=12, n_head=12, d_model=768, d_ff=3072),
            "gpt2-350m": dict(n_layer=24, n_head=16, d_model=1024, d_ff=4096),
            "gpt2-774m": dict(n_layer=36, n_head=20, d_model=1280, d_ff=5120),
            "gpt2-1.5b": dict(n_layer=48, n_head=25, d_model=1600, d_ff=6400),
            "gpt2-tiny": dict(n_layer=2, n_head=4, d_model=128, d_ff=512,
                              vocab_size=512, max_seq_len=128),
        }
        return cls(**{**presets[name], **overrides})


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(key: jax.Array, cfg: GPT2Config) -> Params:
    """GPT-2 init: N(0, 0.02), residual projections scaled by 1/sqrt(2*n_layer)."""
    k_wte, k_wpe, k_blocks = jax.random.split(key, 3)
    std = 0.02
    resid_std = std / math.sqrt(2 * cfg.n_layer)
    pd = cfg.param_dtype

    def norm(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(pd)

    def init_block(k):
        ks = jax.random.split(k, 4)
        return {
            "ln1": {"scale": jnp.ones((cfg.d_model,), pd),
                    "bias": jnp.zeros((cfg.d_model,), pd)},
            "attn": {
                "wqkv": norm(ks[0], (cfg.d_model, 3 * cfg.d_model), std),
                "bqkv": jnp.zeros((3 * cfg.d_model,), pd),
                "wo": norm(ks[1], (cfg.d_model, cfg.d_model), resid_std),
                "bo": jnp.zeros((cfg.d_model,), pd),
            },
            "ln2": {"scale": jnp.ones((cfg.d_model,), pd),
                    "bias": jnp.zeros((cfg.d_model,), pd)},
            "mlp": {
                "wi": norm(ks[2], (cfg.d_model, cfg.d_ff), std),
                "bi": jnp.zeros((cfg.d_ff,), pd),
                "wo": norm(ks[3], (cfg.d_ff, cfg.d_model), resid_std),
                "bo": jnp.zeros((cfg.d_model,), pd),
            },
        }

    blocks = jax.vmap(init_block)(jax.random.split(k_blocks, cfg.n_layer))
    return {
        "wte": norm(k_wte, (cfg.vocab_size, cfg.d_model), std),
        "wpe": norm(k_wpe, (cfg.max_seq_len, cfg.d_model), std / 2),
        "blocks": blocks,
        "ln_f": {"scale": jnp.ones((cfg.d_model,), pd),
                 "bias": jnp.zeros((cfg.d_model,), pd)},
    }


def param_logical_axes(cfg: GPT2Config) -> Params:
    """Logical axis names per param leaf (same tree structure as init_params).

    Resolve to PartitionSpecs with `param_specs`. Conventions: `embed` is the
    ZeRO/fsdp-sharded hidden axis, `mlp`/`heads`-shaped output dims shard over
    tp, `vocab` over tp (tied embedding => logits matmul is tp-sharded).
    """
    del cfg
    block = {
        "ln1": {"scale": ("embed",), "bias": ("embed",)},
        "attn": {
            "wqkv": ("embed", "heads"),   # 3*d_model output dim, megatron col-parallel
            "bqkv": ("heads",),
            "wo": ("heads", "embed"),     # row-parallel back to hidden
            "bo": ("embed",),
        },
        "ln2": {"scale": ("embed",), "bias": ("embed",)},
        "mlp": {
            "wi": ("embed", "mlp"),
            "bi": ("mlp",),
            "wo": ("mlp", "embed"),
            "bo": ("embed",),
        },
    }
    # stacked layer dim is logical axis "layers" (unsharded by default)
    block = jax.tree.map(lambda axes: ("layers",) + axes, block,
                         is_leaf=lambda x: isinstance(x, tuple))
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": block,
        "ln_f": {"scale": ("embed",), "bias": ("embed",)},
    }


def param_specs(cfg: GPT2Config, rules=None) -> Params:
    """PartitionSpec pytree for the params under the active (or given) rules."""
    return jax.tree.map(
        lambda axes: logical_to_spec(*axes, rules=rules),
        param_logical_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def resident_specs(cfg: GPT2Config, rules=None) -> Params:
    """`param_specs` for the tree `resident_params` makes: the unembedding
    is the table with its axes reversed."""
    table = param_logical_axes(cfg)["wte"]
    return {**param_specs(cfg, rules),
            "unembed": logical_to_spec(*reversed(table), rules=rules)}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer_norm(x, p, eps=1e-5):
    with jax.named_scope("ln"):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)


def _attention(x, p, cfg: GPT2Config):
    B, T, D = x.shape
    H, Dh = cfg.n_head, cfg.head_dim
    qkv = x @ lm.weight(p["wqkv"], cfg.dtype) \
        + lm.weight(p["bqkv"], cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    k = k.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    v = v.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)
    q = constrain(q, "batch", "heads", "seq", None)
    k = constrain(k, "batch", "heads", "seq", None)
    v = constrain(v, "batch", "heads", "seq", None)

    impl = lm.resolve_attn_impl(cfg.attn_impl, T)
    if impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention_on_mesh

        out = flash_attention_on_mesh(q, k, v, True)
    elif impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        out = ring_attention(q, k, v, causal=True)
    elif impl == "ulysses":
        from ray_tpu.ops.ring_attention import ulysses_attention

        out = ulysses_attention(q, k, v, causal=True)
    else:
        # fp32 softmax for stability; scores computed on MXU in bf16 inputs.
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32)
        scores = scores / math.sqrt(Dh)
        causal = jnp.tril(jnp.ones((T, T), jnp.bool_))
        scores = jnp.where(causal[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    out = out.transpose(0, 2, 1, 3).reshape(B, T, D)
    out = out @ lm.weight(p["wo"], cfg.dtype) + lm.weight(p["bo"], cfg.dtype)
    return out


def _mlp(x, p, cfg: GPT2Config):
    h = x @ lm.weight(p["wi"], cfg.dtype) + lm.weight(p["bi"], cfg.dtype)
    h = constrain(h, "batch", "seq", "mlp")
    h = jax.nn.gelu(h, approximate=True)
    return h @ lm.weight(p["wo"], cfg.dtype) + lm.weight(p["bo"], cfg.dtype)


def _block(x, bp, cfg: GPT2Config):
    # each residual add belongs to the scope of what it adds
    with jax.named_scope("attn"):
        x = x + _attention(_layer_norm(x, bp["ln1"]), bp["attn"], cfg)
        x = constrain(x, "batch", "seq", "embed")
    with jax.named_scope("mlp"):
        x = x + _mlp(_layer_norm(x, bp["ln2"]), bp["mlp"], cfg)
        x = constrain(x, "batch", "seq", "embed")
    return x


def embed(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B,T] int32 -> embeddings [B,T,D] (compute dtype)."""
    T = tokens.shape[1]
    # lookup against an explicitly replicated table view: gathering from a
    # ZeRO-sharded (embed->fsdp) table makes the output inherit the
    # table's layout and forces the partitioner into an involuntary full
    # rematerialization when re-sharding to the batch layout; an upfront
    # all-gather of the table (the ZeRO-3 prefetch pattern) is the cheap
    # and intended collective
    with jax.named_scope("embed"):
        wte = constrain(params["wte"], None, None)
        x = wte[tokens] + params["wpe"][:T][None]
        return constrain(x.astype(cfg.dtype), "batch", "seq", "embed")


def final_hidden(params: Params, x: jax.Array, cfg: GPT2Config) -> tuple:
    """(the final norm of x, the tied unembedding matrix [D, V] in the
    compute dtype): what `unembed` multiplies and the fused loss takes
    apart."""
    with jax.named_scope("unembed_loss"):
        return (_layer_norm(x, params["ln_f"]),
                lm.weight(params["wte"].T, cfg.dtype))


def unembed(params: Params, x: jax.Array, cfg: GPT2Config) -> jax.Array:
    """final hidden [B,T,D] -> logits [B,T,vocab] (tied embeddings)."""
    x, head = final_hidden(params, x, cfg)
    with jax.named_scope("unembed_loss"):
        return constrain(x @ head, "batch", "seq", "vocab")


def hidden_states(params: Params, tokens: jax.Array,
                  cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> final hidden [B, T, D] (pre-unembed)."""
    x = embed(params, tokens, cfg)

    block_fn = partial(_block, cfg=cfg)
    if cfg.remat:
        from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

        # a Pallas call is not a dot: the policies that save matmul
        # outputs save the attention kernel's two residuals by name too,
        # or its forward would run again in the backward pass
        cp = jax.checkpoint_policies
        named = cp.save_only_these_names(*RESIDUAL_NAMES)
        policies = {
            "dots": cp.save_from_both_policies(
                cp.dots_with_no_batch_dims_saveable, named),
            "dots_all": cp.save_from_both_policies(cp.dots_saveable, named),
        }
        policy = policies.get(cfg.remat_policy)
        block_fn = (jax.checkpoint(block_fn, policy=policy) if policy
                    else jax.checkpoint(block_fn))

    def scan_body(carry, bp):
        return block_fn(carry, bp), None

    with jax.named_scope("layers"):     # the scan's own slices and stacks
        x, _ = lax.scan(scan_body, x, params["blocks"])
    return x


def forward(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, vocab] (compute dtype)."""
    return unembed(params, hidden_states(params, tokens, cfg), cfg)


def loss_fn(params: Params, batch: dict, cfg: GPT2Config) -> jax.Array:
    """Next-token cross-entropy. batch = {"tokens": [B,T+1] int32} or
    {"inputs": [B,T], "targets": [B,T]}."""
    inputs, targets = lm.split_lm_batch(batch)
    x = hidden_states(params, inputs, cfg)
    return lm.chunked_cross_entropy(*final_hidden(params, x, cfg), targets)


# ---------------------------------------------------------------------------
# KV-cache decode (serving path)
# ---------------------------------------------------------------------------

def resident_params(params: Params, cfg: GPT2Config) -> Params:
    """The tree a serving replica keeps on the device: what `decode_step`
    and `prefill_chunk` read, with the conversions they would make in every
    step made once.

    The blocks' matrices and biases (`attn.{wqkv,bqkv,wo,bo}`,
    `mlp.{wi,bi,wo,bo}`) are in `cfg.dtype`, and a new leaf `unembed` holds
    the table transposed for the logits, [D, V] in `cfg.dtype`. `wte`, `wpe`
    and the norms' `scale`/`bias` stay as they are: the embedding is a
    float32 sum of two gathered rows rounded once and the norms compute in
    float32, so a converted table would round twice and give another
    result. Rounding is deterministic, so the programs give on this tree,
    to the bit, what they give on `params`.

    `params` may be a tree of `init_params`' kind or a resident one. One
    jitted program converts the leaves that are not in `cfg.dtype` yet and
    always makes `unembed` again from `wte` (a merge into the table reaches
    the logits that way); every other leaf is handed on as the same array,
    not a copy, so a tree that shares leaves with another keeps sharing
    them."""
    dt = jnp.dtype(cfg.dtype)
    blocks = params["blocks"]
    stale = {part: {k: v for k, v in blocks[part].items() if v.dtype != dt}
             for part in ("attn", "mlp")}

    @jax.jit
    def convert(wte, stale):
        return lm.weight(wte.T, cfg.dtype), jax.tree.map(
            lambda v: lm.weight(v, cfg.dtype), stale)

    unembed, fresh = convert(params["wte"], stale)
    return {**params, "unembed": unembed,
            "blocks": {**blocks,
                       "attn": {**blocks["attn"], **fresh["attn"]},
                       "mlp": {**blocks["mlp"], **fresh["mlp"]}}}


def _unembedding(params: Params, cfg: GPT2Config) -> jax.Array:
    """[D, V] in the compute dtype: a resident tree's own leaf, else the
    table transposed and converted here, in the step. The barrier keeps
    that a value of its own, as the leaf is: XLA's CPU backend otherwise
    folds the transpose into the product and sums in another order, and
    the two kinds of tree would differ in the logits' last bit."""
    if "unembed" in params:
        return lm.weight(params["unembed"], cfg.dtype)
    return lax.optimization_barrier(lm.weight(params["wte"].T, cfg.dtype))


# the cache's leaves that hold a value a token, and the axis that counts
# the tokens: what a prefix pool keeps a block of (`serve/kv_cache.py`)
CACHE_TOKEN_AXIS = {"k": 3, "v": 3}


def init_cache(cfg: GPT2Config, batch: int, max_len: Optional[int] = None):
    """KV cache of all layers: {"k","v"}: [n_layer, B, H, T, Dh] (compute
    dtype). `prefill_chunk` carries it whole through its loop over the
    layers and writes a layer's new rows into it there (`_cache_write`);
    `decode_step` only reads it in its loop and writes all layers' rows
    once, after it (`_decode_write`: a Pallas call a leaf on the TPU).
    Either update is in place only where the caller donates the cache to
    the jitted step (`donate_argnums`), otherwise the program copies it
    once on entry."""
    T = max_len or cfg.max_seq_len
    shape = (cfg.n_layer, batch, cfg.n_head, T, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


# the narrowest stretch of positions a cache write touches. On the TPU the
# cache [.., T, Dh] with Dh = 64 lies with T along the 128 lanes of a tile; a
# narrower update prefers another layout, and the compiler then re-lays the
# whole cache to suit it (tests/test_tpu_compile.py). A window whose start
# along T is computed moves a 4 KB tile at a time, ~65 ns each however deep it
# is (benchmarks/cache_write_windows.py has the table): the chunk program
# writes such windows, and so does the decode step wherever no kernel runs.
# That layout is also, byte for byte, `[.., 64, T]` in its default layout,
# which is what lets `_decode_write` hand the leaf to `ops/rows_write.py`
_WRITE_WINDOW = 128


def _cache_write(c, l, val, pos0, ok):
    """The cache c [L,B,H,T,Dh] takes val: lane i of slot b goes to position
    pos0[b] + i where ok[b, i]; nothing else changes. With a layer's index l
    val is that layer's [B,H,C,Dh] (`prefill_chunk`, inside its loop); with
    l None it is every layer's, [L,B,H,C,Dh] (`_decode_write`, after
    `decode_step`'s loop, where no kernel runs), and a slot's window is all
    L layers deep. Per slot one window of W >= C positions is read, blended
    and written back in place:
    dynamic_update_slice clamps its start near the end of the sequence, so
    an unmasked block write would smear garbage lanes over valid earlier
    positions."""
    L, B, H, T, Dh = c.shape
    if l is None:
        l = 0                               # from the first layer, L deep
    else:
        val, L = val[None], 1
    C = val.shape[3]
    W = min(T, max(C, _WRITE_WINDOW))
    # a lone row's window starts on a multiple of W, the edge of a tile
    # there: such a window is written in 7.7 us against 12.5 (PERF.md, PR 24)
    start = jnp.clip(pos0 // W * W if C == 1 else pos0, 0, T - W)
    # window lane w (at position start + w) takes val lane w - (pos0 - start)
    src = jnp.arange(W)[None, :] - (pos0 - start)[:, None]            # [B, W]
    hit = (src[:, :, None] == jnp.arange(C)) & ok[:, None, :]      # [B, W, C]
    # a chunk's lanes are moved by a 0/1 matrix: exact (one product of 1 a
    # lane), and a product's result takes the layout its consumer has, where
    # a gather or a reshape would hand val's own layout on to the whole
    # cache. A lone row is broadcast along its window where it is blended:
    # moved for all slots first, it is a window a slot written out and read
    # back, as many bytes again as the write moves
    moved = val if C == 1 else jnp.einsum(
        "bwc,lbhcd->lbhwd", hit.astype(val.dtype), val,
        precision=lax.Precision.HIGHEST)
    take = hit.any(axis=-1)                                           # [B, W]
    for b in range(B):
        at = (l, b, 0, start[b], 0)
        old = lax.dynamic_slice(c, at, (L, 1, H, W, Dh))
        new = jnp.where(take[b][:, None], moved[:, b:b + 1], old)
        c = lax.dynamic_update_slice(c, new, at)
    return c


def _rows_kernels(T: int, Dh: int, interpret: bool = False) -> bool:
    """Whether the decode step's Pallas kernels take this cache's leaves:
    where a kernel runs (`ops.slot_state.use_kernel`: the TPU), the head is
    narrower than a tile's 128 lanes and T is whole tiles. `[.., Dh, T]` in
    its default layout is then byte for byte how the chip holds
    `[.., T, Dh]` (`_WRITE_WINDOW`): the axes swapped are the leaf's own
    bytes, `ops/rows_write.py`'s and `ops/gqa_attend.py`'s leaf with the
    positions on the lanes."""
    from ray_tpu.ops.rows_write import TILE             # `lm.dot` has why
    from ray_tpu.ops.slot_state import use_kernel

    return use_kernel(None, interpret) and Dh < TILE and T % TILE == 0


def _a_shard_each(fn, *specs):
    """`fn` as every shard of a mesh that shards the heads over `tp` runs it
    on its own heads (`specs`: the arguments', then the result's): the TPU's
    compiler partitions no Pallas call."""
    from ray_tpu.parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None or mesh.shape.get("tp", 1) == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=specs[:-1],
                         out_specs=specs[-1], check_vma=False)


def rows_read_block(cache, interpret: bool = False) -> int:
    """The positions of a slot's rows that a decode step's attention reads
    at a time: the kernel's block where `_decode_attend` goes through it
    (a live slot's rows to its position rounded up to one), all T where
    the plain lines run. What `serve/llm.py` counts `positions_read` by."""
    T, Dh = cache["k"].shape[3:]
    if not _rows_kernels(T, Dh, interpret):
        return T
    from ray_tpu.ops.gqa_attend import read_block

    view = cache["k"].shape[:3] + (Dh, T)       # as `_decode_attend` views it
    return read_block(view, view, Dh, interpret=interpret)


def _decode_write(c, rows, pos, on, interpret: bool = False):
    """The cache c [L,B,H,T,Dh] takes every layer's new row, rows
    [L,B,H,Dh], at position pos[b] of every slot that is `on` [B]; nothing
    else changes: `decode_step`'s write, after its loop.

    Where the kernels take the leaf (`_rows_kernels`), through
    `ops/rows_write.py`: one call a leaf reads a tile `[H, Dh, 128]` a
    layer and slot, blends the row's lane and writes it where it read it.
    Everywhere else `_cache_write`'s windows. Both leave the same bits.
    Under a mesh that shards the heads over `tp` every shard writes its own
    heads."""
    T, Dh = c.shape[3:]
    if not _rows_kernels(T, Dh, interpret):
        return _cache_write(c, None, rows[:, :, :, None], pos, on[:, None])
    from ray_tpu.ops.rows_write import rows_write

    def write(c, rows, pos, on):
        view = rows_write(jnp.swapaxes(c, 3, 4), None, rows, pos, on,
                          interpret=interpret)
        return jnp.swapaxes(view, 3, 4)

    heads, whole = jax.P(None, None, "tp"), jax.P()
    return _a_shard_each(write, heads, heads, whole, whole, heads)(
        c, rows, pos, on)


def _decode_attend(q, k, v, cache, l, pos, on, interpret: bool = False):
    """A decode step's one token a slot, q [B,H,Dh] with its own new row k,
    v [B,H,Dh], against layer l of the cache as it was before the step
    -> [B,H,Dh] float32: the rows before pos[b] and the own row at pos[b];
    what the cache holds at pos[b] and beyond never reaches the result.

    Where the kernels take the leaves (`_rows_kernels`), through
    `ops/gqa_attend.py` on the leaves' own bytes viewed [.., Dh, T]: a slot
    that is `on` reads its rows a block of positions at a time and only as
    far as its position, one that is not reads nothing (and its values are
    garbage, as its logits are). Everywhere else the plain lines, which
    read all T positions of every slot and are what the kernel is tested
    against. Under a mesh that shards the heads over `tp` every shard
    attends with its own heads."""
    T, Dh = cache["k"].shape[3:]
    if _rows_kernels(T, Dh, interpret):
        from ray_tpu.ops.gqa_attend import gqa_attend

        def attend(q, k, v, ck, cv, l, pos, on):
            return gqa_attend(
                q[:, :, None], jnp.swapaxes(ck, 3, 4), jnp.swapaxes(cv, 3, 4),
                l, pos, on, 1.0 / math.sqrt(Dh), own=(k, v),
                interpret=interpret)[:, :, 0]

        row, heads, whole = jax.P(None, "tp"), jax.P(None, None, "tp"), \
            jax.P()
        return _a_shard_each(attend, row, row, row, heads, heads, whole,
                             whole, whole, row)(
            q, k, v, cache["k"], cache["v"], l, pos, on)
    t_idx = jnp.arange(T)[None, None, :]
    own = t_idx == pos[:, None, None]                         # [B, 1, T]
    before = t_idx < pos[:, None, None]
    scores = jnp.einsum("bhd,bhtd->bht", q, cache["k"][l],
                        preferred_element_type=jnp.float32)
    # the new row's own score where the row will lie: the same
    # [B,H,T] columns as if it had been written first
    scores = jnp.where(own, jnp.einsum(
        "bhd,bhd->bh", q, k,
        preferred_element_type=jnp.float32)[:, :, None], scores)
    scores = scores / math.sqrt(Dh)
    scores = jnp.where(before | own, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    # the cache's values before pos, the new row's at pos: summed
    # in float32 and rounded once, as one product over T would be
    attn = jnp.einsum("bht,bhtd->bhd", jnp.where(before, probs, 0),
                      cache["v"][l], preferred_element_type=jnp.float32)
    p_own = jnp.sum(jnp.where(own, probs, 0), axis=-1,
                    dtype=jnp.float32)                            # [B, H]
    return attn + p_own[:, :, None] * v.astype(jnp.float32)


def _cached_layers(layer, x, params: Params, cache):
    """x through `layer(x, ck, cv, bp, l) -> (x, ck, cv)` for each block
    bp = params["blocks"][l], ck/cv the whole caches. Returns (x, cache).
    `prefill_chunk`'s loop; `decode_step` has its own, which carries x
    alone."""
    def body(carry, scanned):
        l, bp = scanned
        return layer(*carry, bp, l), None

    # the loop CARRIES the hidden state and the whole k and v caches
    # [L,B,H,T,Dh] and scans the layer's index and weights. A carry is one
    # buffer from layer to layer, so a layer writes its rows into it and
    # reads its own slice of it, and with the cache donated by the caller
    # the step copies and rewrites nothing else of it: scanned in and
    # stacked out, the caches would be two buffers and every layer's slice
    # rewritten whole. What the loop does itself (a layer's weights sliced
    # in, the carries) is `layers`; the layer's own operations keep their
    # inner scopes
    with jax.named_scope("layers"):
        (x, ck, cv), _ = lax.scan(
            body, (x, cache["k"], cache["v"]),
            (jnp.arange(cache["k"].shape[0]), params["blocks"]))
    return x, {"k": ck, "v": cv}


def decode_step(params: Params, cache, tokens: jax.Array, pos: jax.Array,
                active: jax.Array, cfg: GPT2Config):
    """One decode step for a continuous batch.

    tokens [B] int32 (current input token per slot), pos [B] int32 (its
    position), active [B] bool (slots whose cache should advance). Returns
    (logits [B, vocab] f32, new_cache). Inactive slots' caches are untouched
    and their logits are garbage — the engine masks them.

    The loop over the layers carries the hidden state alone and only reads
    the cache: a layer attends to its own [B,H,T,Dh] slice as it was before
    this step, for the positions before pos[b], and to its new row directly
    (`_decode_attend`: on the TPU a Pallas call a layer that reads an active
    slot's rows only as far as its position, elsewhere plain XLA over all T
    positions of every slot). The new rows leave the loop stacked,
    [L,B,H,Dh] a leaf, and go into the cache once, after it
    (`_decode_write`): on the TPU one Pallas call a leaf that reads and
    writes a slot's tile of 128 positions a layer in place, elsewhere one
    window all the layers deep a slot a leaf. The caller must donate `cache`
    for that to happen in place.
    """
    B = tokens.shape[0]
    H, Dh = cfg.n_head, cfg.head_dim
    L = cache["k"].shape[0]
    wte = params["wte"]
    with jax.named_scope("embed"):
        x = wte[tokens] + params["wpe"][
            jnp.clip(pos, 0, cfg.max_seq_len - 1)]
        x = x.astype(cfg.dtype)                               # [B, D]

    def layer(x, scanned):
        l, bp = scanned
        with jax.named_scope("attn"):
            h = _layer_norm(x, bp["ln1"])
            qkv = h @ lm.weight(bp["attn"]["wqkv"], cfg.dtype) + \
                lm.weight(bp["attn"]["bqkv"], cfg.dtype)
            q, k, v = (a.reshape(B, H, Dh) for a in jnp.split(qkv, 3, -1))
            attn = _decode_attend(q, k, v, cache, l, pos, active)
            attn = attn.astype(cfg.dtype).reshape(B, H * Dh)
            attn = attn @ lm.weight(bp["attn"]["wo"], cfg.dtype) + \
                lm.weight(bp["attn"]["bo"], cfg.dtype)
            x = x + attn
        with jax.named_scope("mlp"):
            x = x + _mlp(_layer_norm(x, bp["ln2"]), bp["mlp"], cfg)
        return x, {"k": k, "v": v}

    # the loop closes over the caches (read only) and stacks each layer's
    # new k and v row as its output, [L,B,H,Dh] a leaf
    with jax.named_scope("layers"):
        x, rows = lax.scan(layer, x, (jnp.arange(L), params["blocks"]))
    with jax.named_scope("kv_update"):
        cache = {name: _decode_write(cache[name], row, pos, active)
                 for name, row in rows.items()}
    with jax.named_scope("unembed_loss"):
        x = _layer_norm(x, params["ln_f"])
        logits = (x @ _unembedding(params, cfg)).astype(jnp.float32)
    return logits, cache


def prefill_chunk(params: Params, cache, tokens: jax.Array, pos0: jax.Array,
                  length: jax.Array, active: jax.Array, cfg: GPT2Config):
    """Process up to C prompt tokens per slot in ONE fused step (chunked
    prefill for the continuous-batching engine: a long prompt advances C
    positions per engine tick instead of 1, while decode slots ride along
    as length-1 lanes).

    tokens [B, C] int32 (left-aligned chunk per slot), pos0 [B] int32 (the
    chunk's first cache position), length [B] int32 (valid tokens in the
    chunk, 0..C), active [B] bool. Returns (logits [B, vocab] taken at
    each slot's LAST valid chunk token, new_cache). Inactive/zero-length
    slots' caches are untouched and their logits are garbage. Callers
    guarantee pos0 + length <= T and C <= T.

    As in `decode_step` the cache is the carry of the loop over the layers:
    a layer writes the valid lanes of each active slot's [H, C, Dh] chunk
    into it (`_cache_write`) and reads its own slice once. The caller must
    donate `cache` for that to happen in place.
    """
    B, C = tokens.shape
    H, Dh = cfg.n_head, cfg.head_dim
    T = cache["k"].shape[3]
    wte = params["wte"]
    lane = jnp.arange(C)
    pos = pos0[:, None] + lane[None, :]                           # [B, C]
    ok = (lane[None, :] < length[:, None]) & active[:, None]      # [B, C]
    with jax.named_scope("embed"):
        x = wte[tokens] + params["wpe"][
            jnp.clip(pos, 0, cfg.max_seq_len - 1)]
        x = x.astype(cfg.dtype)                                   # [B, C, D]

    def layer(x, ck, cv, bp, l):                          # ck/cv [L,B,H,T,Dh]
        with jax.named_scope("attn"):
            h = _layer_norm(x, bp["ln1"])
            qkv = h @ lm.weight(bp["attn"]["wqkv"], cfg.dtype) + \
                lm.weight(bp["attn"]["bqkv"], cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, C, H, Dh).transpose(0, 2, 1, 3)  # [B,H,C,Dh]
            k = k.reshape(B, C, H, Dh).transpose(0, 2, 1, 3)
            v = v.reshape(B, C, H, Dh).transpose(0, 2, 1, 3)
            with jax.named_scope("kv_update"):
                ck = _cache_write(ck, l, k, pos0, ok)
                cv = _cache_write(cv, l, v, pos0, ok)
            # chunk lanes attend to everything written up to their own
            # position (the chunk's k/v are already in the cache, so this
            # is causal intra-chunk attention + full attention to the
            # prefix)
            scores = jnp.einsum("bhcd,bhtd->bhct", q, ck[l],
                                preferred_element_type=jnp.float32)
            scores = scores / math.sqrt(Dh)
            t_idx = jnp.arange(T)[None, None, None, :]
            scores = jnp.where(t_idx <= pos[:, None, :, None], scores,
                               -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            attn = jnp.einsum("bhct,bhtd->bhcd", probs, cv[l])
            attn = attn.transpose(0, 2, 1, 3).reshape(B, C, H * Dh)
            attn = attn @ lm.weight(bp["attn"]["wo"], cfg.dtype) + \
                lm.weight(bp["attn"]["bo"], cfg.dtype)
            x = x + attn
        with jax.named_scope("mlp"):
            x = x + _mlp(_layer_norm(x, bp["ln2"]), bp["mlp"], cfg)
        return x, ck, cv

    x, cache = _cached_layers(layer, x, params, cache)
    with jax.named_scope("unembed_loss"):
        last = jnp.clip(length - 1, 0, C - 1)
        x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
        x_last = _layer_norm(x_last, params["ln_f"])
        logits = (x_last @ _unembedding(params, cfg)).astype(jnp.float32)
    return logits, cache


def num_params(cfg: GPT2Config) -> int:
    d, f, L, V, S = cfg.d_model, cfg.d_ff, cfg.n_layer, cfg.vocab_size, cfg.max_seq_len
    per_block = (3 * d * d + 3 * d) + (d * d + d) + (2 * d * f + f + d) + 4 * d
    return V * d + S * d + L * per_block + 2 * d


# ---------------------------------------------------------------------------
# Checkpoint IO (serve real weights: the reference's serve.llm loads HF
# checkpoints into its engines; here trained params round-trip through an
# npz so Serve replicas host what the trainer produced, not random init)
# ---------------------------------------------------------------------------

_CFG_FIELDS = ("vocab_size", "n_layer", "n_head", "d_model", "d_ff",
               "max_seq_len")


def save_params(path: str, params: Params, cfg: GPT2Config) -> str:
    """Write params + the architecture fields needed to rebuild them.
    One npz (path-keyed flat pytree) + a json sidecar; no orbax needed
    for single-host serving checkpoints."""
    import json
    import os

    import numpy as np

    os.makedirs(path, exist_ok=True)
    flat = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in kp)
        flat[key] = np.asarray(leaf)
    tmp = os.path.join(path, "params.npz.tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **flat)
    os.replace(tmp, os.path.join(path, "params.npz"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump({k: getattr(cfg, k) for k in _CFG_FIELDS}, f)
    return path


def load_params(path: str, cfg: Optional[GPT2Config] = None
                ) -> Tuple[Params, GPT2Config]:
    """Load a save_params checkpoint; architecture comes from the sidecar
    (runtime knobs like remat/attn_impl come from `cfg` when given)."""
    import json
    import os

    import numpy as np

    with open(os.path.join(path, "config.json")) as f:
        arch = json.load(f)
    base = cfg or GPT2Config()
    cfg = dataclasses.replace(base, **arch)
    template = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    leaves_kp = jax.tree_util.tree_flatten_with_path(template)[0]
    with np.load(os.path.join(path, "params.npz")) as z:
        loaded = []
        for kp, leaf in leaves_kp:
            key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                           for k in kp)
            arr = z[key]
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(f"checkpoint leaf {key}: shape "
                                 f"{arr.shape} != expected {leaf.shape}")
            loaded.append(jnp.asarray(arr, dtype=leaf.dtype))
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, loaded), cfg


# ---------------------------------------------------------------------------
# LoRA adapters
# ---------------------------------------------------------------------------
def apply_lora(params: Params, adapter: dict) -> Params:
    """Merge low-rank adapters into a COPY of `params`.

    `adapter`: {"dotted.path": {"A": [..., D, r], "B": [..., r, K],
    "alpha": float}} — delta = (alpha / r) * A @ B, the standard LoRA
    scaling. Stacked scanned-layer params ([L, D, K]) take stacked
    A/B ([L, D, r], [L, r, K]) via batched matmul. Serving keeps the
    BASE params shared; each adapter costs only its merged copies of the
    targeted leaves (reference: multi-LoRA serving behind serve.llm).

    The delta is computed in float32 whatever the leaf's dtype, and the
    sum is rounded once, to the leaf's dtype. On float32 leaves that is
    `leaf + delta`. On a tree of `resident_params`, where a serving
    replica keeps no float32 master, the merged weight is the rounding of
    (the base already rounded to the compute dtype + the float32 delta):
    one rounding more than a merge into float32 masters rounded
    afterwards. A merge into `wte` reaches the logits when the merged tree
    goes through `resident_params` again, which `LLMEngine` does."""
    out = jax.tree.map(lambda x: x, params)  # shallow structural copy
    for path, spec in adapter.items():
        keys = path.split(".")
        node = out
        for k in keys[:-1]:
            node[k] = dict(node[k]) if isinstance(node[k], dict) else node[k]
            node = node[k]
        leaf = node[keys[-1]]
        A = jnp.asarray(spec["A"], jnp.float32)
        B = jnp.asarray(spec["B"], jnp.float32)
        r = A.shape[-1]
        alpha = float(spec.get("alpha", r))
        delta = (alpha / r) * (A @ B)
        if delta.shape != leaf.shape:
            raise ValueError(
                f"LoRA delta shape {delta.shape} != param {leaf.shape} "
                f"at {path!r}")
        node[keys[-1]] = (leaf.astype(jnp.float32) + delta).astype(leaf.dtype)
    return out


def load_lora_npz(path: str) -> dict:
    """Adapter file: npz with `<dotted.path>.A`, `<dotted.path>.B` and
    optional `<dotted.path>.alpha` entries (local path or fsspec URI)."""
    import numpy as _np

    from ray_tpu.utils import fs as _fs

    with _fs.open(path, "rb") as f:
        data = _np.load(f)
        adapter: dict = {}
        for name in data.files:
            base, _, kind = name.rpartition(".")
            if kind not in ("A", "B", "alpha"):
                continue
            adapter.setdefault(base, {})[kind] = data[name]
    missing = [k for k, v in adapter.items() if "A" not in v or "B" not in v]
    if missing:
        raise ValueError(f"LoRA entries missing A/B pairs: {missing}")
    return adapter
