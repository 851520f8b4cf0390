"""`ops/mla_attend.py`: the decode kernel, interpreted, against the plain
form at the published head count and widths; and where the two families
call it."""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek, kimi, mla
from ray_tpu.ops import slot_rows

op = importlib.import_module("ray_tpu.ops.mla_attend")

H, R, P = 32, 512, 64
SCALE = 1.0 / math.sqrt(128 + P)
BLOCK = 128


def _operands(B, T, L=1, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (B, H, R), jnp.float32).astype(bf),
            jax.random.normal(ks[1], (B, H, P), jnp.float32).astype(bf),
            jax.random.normal(ks[2], (L, B, T, R), jnp.float32).astype(bf),
            jax.random.normal(ks[3], (L, B, T, P), jnp.float32).astype(bf))


def _both(monkeypatch, T, pos, live, L=1, layer=0, block=BLOCK):
    """(the kernel's mixed, the plain form's) [B, H, r] as numpy."""
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.asarray(live, bool)
    args = (*_operands(len(pos), T, L), jnp.int32(layer), pos, live)
    got = jax.jit(lambda *a: op.mla_attend(*a, SCALE, interpret=True))(*args)
    want = jax.jit(lambda *a: op.mla_attend(*a, SCALE, kernel=False))(*args)
    return np.asarray(got), np.asarray(want)


# the plain form rounds the normalised probabilities to bf16 and the kernel
# the unnormalised ones, a block at a time: 2^-9 of a weighted sum of
# unit-variance latents either way
TOLERANCE = dict(rtol=0, atol=2e-2)


@pytest.mark.parametrize("pos", [
    [0, 0, 0], [BLOCK - 1] * 3, [BLOCK] * 3, [4 * BLOCK - 1] * 3,
    [0, BLOCK - 1, BLOCK], [3 * BLOCK + 5, 17, 4 * BLOCK - 1]],
    ids=["first", "a-blocks-last", "a-blocks-first", "the-leafs-last",
         "ragged-at-the-edges", "ragged"])
def test_the_kernel_is_the_plain_form_to_each_slots_own_position(
        monkeypatch, pos):
    got, want = _both(monkeypatch, 4 * BLOCK, pos, [True] * 3)
    np.testing.assert_allclose(got, want, **TOLERANCE)
    assert np.abs(want).max() > 0.05


def test_a_dead_slot_reads_nothing_and_the_others_are_exact(monkeypatch):
    live = [False, True, False, False, True, False]
    pos = [300, 3 * BLOCK + 1, 0, 9, 40, 200]
    got, want = _both(monkeypatch, 4 * BLOCK, pos, live)
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], want[on], **TOLERANCE)
    assert np.isfinite(got).all()
    # slot, first and last block: a dead slot's steps stay on the block the
    # live slot before it ended on (slot 0 has none before it: block 0)
    src, first, last, at = (np.asarray(a).tolist() for a in slot_rows.plan(
        jnp.asarray(pos), jnp.asarray(live), 4 * BLOCK, BLOCK))
    assert (src, first, last) == ([0, 1, 1, 1, 4, 4], [0, 0, 3, 3, 0, 0],
                                  [0, 3, 3, 3, 0, 0])
    assert at == [-1, 3 * BLOCK + 1, -1, -1, 40, -1]


def test_the_layer_worked_on_is_the_one_named(monkeypatch):
    got, want = _both(monkeypatch, 2 * BLOCK, [5, 2 * BLOCK - 1], [True] * 2,
                      L=3, layer=2)
    np.testing.assert_allclose(got, want, **TOLERANCE)
    other, _ = _both(monkeypatch, 2 * BLOCK, [5, 2 * BLOCK - 1], [True] * 2,
                     L=3, layer=1)
    assert np.abs(other - want).max() > 0.1


@pytest.mark.parametrize("T,block,pos", [
    (3 * BLOCK + 40, BLOCK, [3 * BLOCK + 39, 3 * BLOCK, 7]),
    (200, 256, [199, 0, 100])], ids=["a-ragged-last-block", "one-block"])
def test_a_length_that_is_no_multiple_of_the_block(monkeypatch, T, block,
                                                   pos):
    # 424 has no divisor that is whole lane tiles: its last block hangs over
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    assert slot_rows.block_of(T) == min(T, block)
    got, want = _both(monkeypatch, T, pos, [True] * 3, block=block)
    np.testing.assert_allclose(got, want, **TOLERANCE)


@pytest.mark.parametrize("T,most,block", [
    (10240, 1024, 1024), (4096, 1024, 1024), (10240, 1500, 1280),
    (4096, 4096, 4096), (96, 1024, 96), (1000, 256, 256)])
def test_the_block_divides_the_length_where_whole_lane_tiles_can(
        monkeypatch, T, most, block):
    monkeypatch.setattr(slot_rows, "BLOCK", most)
    assert slot_rows.block_of(T) == block


def test_read_positions_are_a_slots_position_rounded_up_to_a_block(
        monkeypatch):
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    T = 3 * BLOCK + 40
    pos = jnp.asarray([0, BLOCK - 1, BLOCK, T - 1, 77])
    live = jnp.asarray([True, True, True, True, False])
    assert int(op.read_positions(pos, live, T, kernel=False)) == 4 * T
    assert int(op.read_positions(pos, live, T, interpret=True)) == (
        BLOCK + BLOCK + 2 * BLOCK + T)


def _one_layers_call(family, C, slot):
    """Trace one attention layer of `family` with C lanes a row."""
    if family is deepseek:
        cfg = deepseek.DeepseekConfig.preset("deepseek-tiny")
        bp = jax.tree.map(lambda a: a[0], deepseek.init_params(
            jax.random.key(0), cfg)["blocks"])
    else:
        cfg = kimi.KimiConfig.preset("kimi-tiny")
        bp = jax.tree.map(lambda a: a[0], kimi.init_params(
            jax.random.key(0), cfg)["mla"])
    B, T = 3, 32
    N = B if slot is None else 1
    cache = family.init_cache(cfg, B, T)
    x = jnp.ones((N, C, cfg.d_model), jnp.float32)
    pos0 = jnp.arange(N, dtype=jnp.int32) + 2
    pos = pos0[:, None] + jnp.arange(C)
    ok = jnp.ones((N, C), bool)
    if family is deepseek:
        out = deepseek._attention(x, bp, cfg, cache["latent"],
                                  cache["k_rope"], 1, pos0, pos, ok, slot)[0]
    else:
        out = kimi._mla(x, bp, cfg, cache, 1, pos0, pos, ok, slot)[0]
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("family", [deepseek, kimi],
                         ids=["deepseek._attention", "kimi._mla"])
@pytest.mark.parametrize("C,slot,through", [
    (1, None, True), (4, None, False), (1, 1, False), (4, 1, False)],
    ids=["every-slots-one-lane", "lanes", "one-slots-lane", "one-slots-lanes"])
def test_the_families_call_the_op_for_every_slots_one_lane_and_only_there(
        monkeypatch, family, C, slot, through):
    calls = []

    def seen(*args, **kwargs):
        calls.append(args[2].shape)             # the latent leaf, whole
        return op.mla_attend(*args, **kwargs)

    # deepseek's layer is `models/mla.py`'s, which holds the name it calls
    monkeypatch.setattr(mla if family is deepseek else family, "mla_attend",
                        seen)
    _one_layers_call(family, C, slot)
    assert len(calls) == (1 if through else 0)
    assert all(len(shape) == 4 for shape in calls)
