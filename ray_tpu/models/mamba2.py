"""The Mamba-2 mixer of the serving families that have one (`models/
granite.py`: 64 heads, one group of B and C; `models/nemotron.py`: 128
heads in 8 groups), in its two forms and no third.

With H heads of P lanes (I = H P), G groups of B and C [N] (head h reads
group h // (H / G)) and u the layer's normed input:

    [z, xBC, dt] = u W_in        (held as w_zx [d, I + F] and w_dt [d, H],
                                  F = I + 2 G N what the convolution takes)
    xBC = silu(conv1d_causal(xBC; w [K, F], b)), the last K positions
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log), a head
    S_t = exp(dt A) S_{t-1} + dt x_t B_t^T;   y_t = S_t C_t + D x_t
    y = RMSNorm_{I/G}(y * silu(z)) * g: the gate first, then the norm over
        each group's I / G lanes (all I of them where G = 1);  W_out

The recurrence, one token a slot through the kernel `ops/ssm_update.py`, is
`first`: a family's `decode_step` whole and, in its `prefill_chunk`, every
slot's first lane. A chunk's further lanes, M of them after position s, go
through the SSD form (`further`), with a_i = sum_{s<m<=i} dt_m A:

    y_i = e^{a_i} S_s C_i + sum_{s<j<=i} e^{a_i - a_j} (C_i . B_j) dt_j x_j
    S_{s+M} = e^{a_{s+M}} S_s + sum_j e^{a_{s+M} - a_j} dt_j x_j B_j^T

a slot at a time and only for the slots that prefill (`models/lm.py`, "The
lanes of a chunk"); C_i . B_j is taken within a head's group. A lane past a
slot's length has dt = 0: it decays nothing and adds nothing; a slot with no
valid lane keeps its state and its window bit for bit, in both forms.

A family hands over the normed input and takes the mixer's output (its norm,
its residual and its scale on it are its own), the layer's weights `p`
(`init`'s), its config (`ssm_heads`, `ssm_head_dim`, `ssm_state`,
`ssm_groups`, `ssm_conv`, `norm_eps`, `dtype`, `param_dtype`) and its cache,
whose leaves `ssm` [Mamba layers, slots, N, I] and `conv` [Mamba layers,
slots, (K - 1) F] are float32 (`ops/ssm_update.py` has the state's layout).
The scopes are the per-layer readers': `ssm_project`, `ssm_conv`,
`ssm_update`, `ssm_chunk`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import lm
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops.ssm_update import ssm_update

_HIGHEST = lax.Precision.HIGHEST

# The Mamba-2 layer's own seeded parameters as its reference implementation
# initialises them: A = U(1, 16), dt = exp(U(log 0.001, log 0.1)) through
# the inverse of softplus into `dt_bias`, D = 1, the convolution U(-1/2,
# 1/2) (a depthwise window of 4). With the projection's part added dt A lies
# about 0.0005 to 3: a memory of one to two thousand tokens, so that a fault
# in carrying state across chunks, snapshots and slots cannot hide.
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def inner(cfg) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_width(cfg) -> int:
    """What goes through the convolution: x, and B and C of every group."""
    return inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def init(ks, cfg, out_std: float = 0.02):
    """A Mamba-2 layer's weights from the seven keys `ks`: the matrices
    N(0, 0.02) (W_out `out_std`) in the dtype the replica holds them, the
    rest float32 as above."""
    pd, D = cfg.param_dtype, cfg.d_model
    I, F, Hm, K = inner(cfg), conv_width(cfg), cfg.ssm_heads, cfg.ssm_conv
    dt = jnp.exp(jax.random.uniform(ks[2], (Hm,), jnp.float32,
                                    math.log(DT_RANGE[0]),
                                    math.log(DT_RANGE[1])))
    edge = 1.0 / math.sqrt(K)
    return {
        # W_in's columns for z and xBC (a whole number of lane tiles), and
        # its H for dt apart, float32: beside them the minor axis would be
        # no whole number of tiles, and the TPU's compiler copies the whole
        # stack into another layout on every step (1.26 GB of granite's;
        # PERF.md, PR 38)
        "w_zx": lm.normal(ks[0], (D, I + F), 0.02, pd),
        "w_dt": lm.normal(ks[6], (D, Hm), 0.02, jnp.float32),
        "w_out": lm.normal(ks[1], (I, D), out_std, pd),
        # softplus(dt_bias) = dt
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(jax.random.uniform(ks[3], (Hm,), jnp.float32,
                                            *A_RANGE)),
        "d": jnp.ones((Hm,), jnp.float32),
        # tap k of the window multiplies the input 3 - k positions back
        "conv_w": jax.random.uniform(ks[4], (K, F), jnp.float32, -edge, edge),
        "conv_b": jax.random.uniform(ks[5], (F,), jnp.float32, -edge, edge),
        "norm": lm.ones(I)}


def num_params(cfg) -> int:
    I, Hm = inner(cfg), cfg.ssm_heads
    return (cfg.d_model * (I + conv_width(cfg) + Hm) + I * cfg.d_model
            + 3 * Hm + (cfg.ssm_conv + 1) * conv_width(cfg) + I)


def init_cache(cfg, layers: int, batch: int) -> dict:
    """{"ssm" [layers, B, N, I], "conv" [layers, B, (K - 1) F]} float32 (the
    K - 1 inputs side by side on the lanes: as [.., K - 1, F] the compiler
    re-lays the leaf round the layers' loop), zero, which is what a sequence
    starts from."""
    return {"ssm": jnp.zeros((layers, batch, cfg.ssm_state, inner(cfg)),
                             jnp.float32),
            "conv": jnp.zeros(
                (layers, batch, (cfg.ssm_conv - 1) * conv_width(cfg)),
                jnp.float32)}


def _project_in(u32, p, cfg):
    """The norm's output u32 [B,M,D] float32 -> z [B,M,I], xBC [B,M,F]
    before the convolution, dt [B,M,H] after softplus, all float32."""
    I = inner(cfg)
    with jax.named_scope("ssm_project"):
        proj = lm.dot(u32, p["w_zx"], cfg.dtype)
        dt = jnp.dot(u32, p["w_dt"], precision=_HIGHEST)
        return proj[..., :I], proj[..., I:], \
            jax.nn.softplus(dt + p["dt_bias"])


def _project_out(y, z, p, cfg):
    """The gate before the norm, a group of I / G lanes at a time, then
    W_out."""
    G = cfg.ssm_groups
    with jax.named_scope("ssm_project"):
        by_group = y.shape[:-1] + (G, y.shape[-1] // G)
        y = rms_norm((y * jax.nn.silu(z)).reshape(by_group),
                     {"scale": p["norm"]["scale"].reshape(by_group[-2:])},
                     cfg.norm_eps).reshape(y.shape)
        return lm.dot(y, p["w_out"], cfg.dtype)


def _conv(xbc, p, window, ok):
    with jax.named_scope("ssm_conv"):
        return lm.short_conv(xbc, p["conv_w"], window, ok, p["conv_b"])


def _split_xbc(xbc, cfg):
    """xBC [..., F] -> x [..., I], B and C [..., G, N] by group."""
    I, N, G = inner(cfg), cfg.ssm_state, cfg.ssm_groups
    x, b, c = xbc[..., :I], xbc[..., I:I + G * N], xbc[..., I + G * N:]
    b, c = (t.reshape(*t.shape[:-1], G, N) for t in (b, c))
    return x, b, c


def _ssd(x, b, c, dt, p, s, ok, cfg):
    """The SSD form for M lanes a slot: x [B,M,I], b, c [B,M,G,N] by group,
    dt [B,M,H], the state s [B,N,I] before them, ok [B,M] -> (y [B,M,I], the
    state after them). A group's L = I / G lanes read the group's B and C,
    a group's stretch of lanes at a time and the state where it lies ([N,
    lanes]): by a reshape to [N, G, L] the compiler re-lays the whole leaf,
    N last, round every layer (2.76 GB at Nemotron-H's sizes)."""
    B, M = ok.shape
    H, P, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups
    L = H * P // G

    def by_group(spec, cols, lanes):
        return jnp.concatenate([
            jnp.einsum(spec, cols[:, :, g], lanes[..., g * L:(g + 1) * L],
                       precision=_HIGHEST) for g in range(G)], axis=-1)

    with jax.named_scope("ssm_chunk"):
        dt = jnp.where(ok[:, :, None], dt, 0.0)
        a = jnp.cumsum(-dt * jnp.exp(p["a_log"]), axis=1)        # [B, M, H]
        dtx = (lm.over_lanes(dt, P) * x).reshape(B, M, H, P)
        # what the state held: e^{a_i} S_s C_i
        y = lm.over_lanes(jnp.exp(a), P) * by_group("bin,bnf->bif", c, s)
        # within the chunk: (C_i . B_j) e^{a_i - a_j} dt_j x_j, j <= i
        lane = jnp.arange(M)
        seen = (lane[None, :] <= lane[:, None])[None, :, :, None]  # [1,i,j,1]
        within = jnp.where(seen, jnp.exp(jnp.where(
            seen, a[:, :, None, :] - a[:, None, :, :], 0.0)), 0.0)  # [B,i,j,H]
        weight = within * jnp.repeat(
            jnp.einsum("bign,bjgn->bijg", c, b, precision=_HIGHEST),
            H // G, axis=-1)
        y = y + jnp.einsum("bijh,bjhp->bihp", weight, dtx,
                           precision=_HIGHEST).reshape(B, M, H * P)
        y = y + lm.over_lanes(p["d"], P) * x
        # the state at the chunk's end
        total = a[:, -1]                                           # [B, H]
        out_of = jnp.exp(total[:, None, :] - a)[..., None] * dtx   # [B,M,H,P]
        s_new = lm.over_lanes(jnp.exp(total), P)[:, None, :] * s \
            + by_group("bjn,bjf->bnf", b, out_of.reshape(B, M, H * P))
        return y, jnp.where(ok.any(axis=1)[:, None, None], s_new, s)


def first(u, p, cfg, cache, l, on):
    """Mamba-2 layer l of the cache's leaves over every slot's first lane,
    the normed input u [B,1,D] float32, by the recurrence: -> (the mixer's
    output [B,1,D], cache). `on` [B]: the slots whose lane is valid; the
    others keep their state and window bit for bit."""
    P = cfg.ssm_head_dim
    z, xbc, dt = _project_in(u, p, cfg)
    with jax.named_scope("ssm_conv"):
        window = lax.dynamic_index_in_dim(cache["conv"], l, 0,
                                          keepdims=False)
    xbc, window = _conv(xbc, p, window, on[:, None])
    with jax.named_scope("ssm_conv"):
        conv = lax.dynamic_update_index_in_dim(cache["conv"], window, l, 0)
    xs, b, c = _split_xbc(xbc[:, 0], cfg)
    with jax.named_scope("ssm_update"):
        dt = lm.over_lanes(dt[:, 0], P)                            # [B, I]
        decay = jnp.exp(-dt * lm.over_lanes(jnp.exp(p["a_log"]), P))
        ssm, y = ssm_update(cache["ssm"], l, decay, dt * xs, b, c, on)
        y = y + lm.over_lanes(p["d"], P) * xs
    return (_project_out(y[:, None], z, p, cfg),
            {**cache, "ssm": ssm, "conv": conv})


def further(u, p, cfg, cache, l, slot, ok):
    """The same mixer over one slot's further lanes, u [1,M,D], by the SSD
    form from the state its first lane left: -> (output [1,M,D], cache)."""
    N, I = cfg.ssm_state, inner(cfg)
    W = cache["conv"].shape[-1]
    z, xbc, dt = _project_in(u, p, cfg)
    with jax.named_scope("ssm_conv"):
        window = lax.dynamic_slice(cache["conv"], (l, slot, 0), (1, 1, W))[0]
    xbc, window = _conv(xbc, p, window, ok)
    with jax.named_scope("ssm_conv"):
        conv = lax.dynamic_update_slice(cache["conv"], window[None],
                                        (l, slot, 0))
    xs, b, c = _split_xbc(xbc, cfg)
    with jax.named_scope("ssm_chunk"):
        s = lax.dynamic_slice(cache["ssm"], (l, slot, 0, 0),
                              (1, 1, N, I))[0]
    y, s = _ssd(xs, b, c, dt, p, s, ok, cfg)
    with jax.named_scope("ssm_chunk"):
        ssm = lax.dynamic_update_slice(cache["ssm"], s[None],
                                       (l, slot, 0, 0))
    return (_project_out(y, z, p, cfg),
            {**cache, "ssm": ssm, "conv": conv})
