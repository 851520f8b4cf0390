"""`ops/slot_rows.py`: the grid over a slot's rows, interpreted, with toy
bodies (scores = q . k): the plan, the fold over blocks, a ragged last
block, the leaves' layouts. The three kernels that stand on it have their
own files (`test_ops_{mla,gqa,dsa}_attend.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import slot_rows, slot_state
from ray_tpu.ops.slot_rows import MASKED, Kernel, Leaf

BLOCK = 128
Q, N = 8, 128


def _all_at_once(blk, q_ref, rows_ref):
    """One leaf of rows, keys and values both: every query at once."""
    rows = rows_ref[0, 0]                                      # [block, N]
    s = lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = jnp.where(blk.at(s.shape, 1) <= blk.pos, s, MASKED)
    yield ..., s, slot_rows.zero_past_end(rows, blk.held(rows.shape, 0))


def _head_by_head(blk, q_ref, keep_ref, k_ref, v_ref):
    """Keys with the positions on the lanes `[H, N, block]`, values by head
    `[H, block, N]`, a mask a slot `[1, block]`: a head at a time."""
    seen = (keep_ref[0] != 0) & (blk.at((1, blk.block), 1) <= blk.pos)
    held = blk.held((blk.block, 1), 0)
    for h in range(q_ref.shape[1]):
        s = jnp.dot(q_ref[0, h], k_ref[0, 0, h],
                    preferred_element_type=jnp.float32)
        yield h, jnp.where(seen, s, MASKED), slot_rows.zero_past_end(
            v_ref[0, 0, h], held)


def _one_leaf(B, T, L=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(ks[0], (B, Q, N)),
            jax.random.normal(ks[1], (L, B, T, N)))


def _softmax_over_the_row(q, rows, pos):
    """q [B, Q, N] against rows [B, T, N], slot b to pos[b]: one softmax."""
    s = jnp.einsum("bqn,btn->bqt", q, rows,
                   precision=lax.Precision.HIGHEST)
    s = jnp.where(jnp.arange(rows.shape[1])[None, None] <= pos[:, None, None],
                  s, -jnp.inf)
    return jnp.einsum("bqt,btn->bqn", jax.nn.softmax(s, axis=-1), rows,
                      precision=lax.Precision.HIGHEST)


def _attend(q, leaf, layer, pos, live, **how):
    return jax.jit(lambda q, leaf: slot_rows.attend(
        Kernel("toy", _all_at_once, (q, Leaf(leaf, 2)), q.shape[1:]),
        jnp.int32(layer), jnp.asarray(pos, jnp.int32),
        jnp.asarray(live, bool), interpret=True, **how))(q, leaf)


@pytest.mark.parametrize("pos", [
    [0, 0, 0], [BLOCK - 1] * 3, [BLOCK] * 3, [4 * BLOCK - 1] * 3,
    [3 * BLOCK + 5, 17, 4 * BLOCK - 1]],
    ids=["first", "a-blocks-last", "a-blocks-first", "the-leafs-last",
         "ragged"])
def test_the_fold_over_blocks_is_one_softmax_over_the_row(monkeypatch, pos):
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    q, leaf = _one_leaf(3, 4 * BLOCK)
    got = _attend(q, leaf, 1, pos, [True] * 3)
    want = _softmax_over_the_row(q, leaf[1], jnp.asarray(pos))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)
    other = _softmax_over_the_row(q, leaf[0], jnp.asarray(pos))
    assert np.abs(np.asarray(other - want)).max() > 0.1     # the layer named


def test_a_dead_slots_steps_name_the_block_the_last_live_slot_ended_on(
        monkeypatch):
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    live = [False, True, False, False, True, False]
    pos = [300, 3 * BLOCK + 1, 0, 9, 40, 200]
    src, first, last, at = (np.asarray(a).tolist() for a in slot_rows.plan(
        jnp.asarray(pos), jnp.asarray(live), 4 * BLOCK, BLOCK))
    # slot 0 has no live slot before it: block 0 of itself
    assert (src, first, last) == ([0, 1, 1, 1, 4, 4], [0, 0, 3, 3, 0, 0],
                                  [0, 3, 3, 3, 0, 0])
    assert at == [-1, 3 * BLOCK + 1, -1, -1, 40, -1]
    q, leaf = _one_leaf(6, 4 * BLOCK)
    # a dead slot's rows are never read: NaNs there reach nothing
    leaf = leaf.at[:, ~np.asarray(live)].set(jnp.nan)
    got = np.asarray(_attend(q, leaf, 0, pos, live))
    on = np.asarray(live)
    want = _softmax_over_the_row(q[on], leaf[0][on], jnp.asarray(pos)[on])
    np.testing.assert_allclose(got[on], want, rtol=0, atol=2e-2)
    assert not got[~on].any()                   # nothing folded: 0 / 1


@pytest.mark.parametrize("T,block,pos", [
    (3 * BLOCK + 40, BLOCK, [3 * BLOCK + 39, 3 * BLOCK, 7]),
    (200, 256, [199, 0, 100])], ids=["a-ragged-last-block", "one-block"])
def test_a_ragged_last_block_adds_nothing(monkeypatch, T, block, pos):
    # 424 has no divisor that is whole lane tiles: its last block hangs over
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    assert slot_rows.block_of(T) == min(T, block)
    q, leaf = _one_leaf(3, T)
    got = _attend(q, leaf, 0, pos, [True] * 3)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        got, _softmax_over_the_row(q, leaf[0], jnp.asarray(pos)), rtol=0,
        atol=2e-2)


def test_the_block_handed_over_decides_where_the_rule_does_not():
    """`block=`: what the tools that measured `BLOCK` drive."""
    q, leaf = _one_leaf(2, 3 * BLOCK)
    assert slot_rows.block_of(3 * BLOCK) == 3 * BLOCK
    whole = _attend(q, leaf, 0, [5, 3 * BLOCK - 1], [True] * 2)
    by_block = _attend(q, leaf, 0, [5, 3 * BLOCK - 1], [True] * 2,
                       block=BLOCK)
    np.testing.assert_allclose(by_block, whole, rtol=0, atol=1e-4)


def test_leaves_by_head_on_the_lanes_and_a_slots_mask(monkeypatch):
    """A head axis before the positions, the positions on the lanes, an
    array a slot without layers, and a body that yields a head at a time:
    every index map is the same clamped block of the same slot."""
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    L, B, H, T = 2, 4, 2, 3 * BLOCK + 40
    ks = jax.random.split(jax.random.key(1), 4)
    q = jax.random.normal(ks[0], (B, H, Q, N))
    k = jax.random.normal(ks[1], (L, B, H, T, N))
    v = jax.random.normal(ks[2], (L, B, H, T, N))
    keep = jax.random.bernoulli(ks[3], 0.5, (B, T)).at[:, 0].set(True)
    pos = jnp.asarray([T - 1, 0, BLOCK, 77], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    got = jax.jit(lambda q, k, v, keep: slot_rows.attend(Kernel(
        "toy", _head_by_head,
        (q, Leaf(keep.astype(jnp.int32)[:, None], 2, False),
         Leaf(jnp.swapaxes(k, 3, 4), 4), Leaf(v, 3)), q.shape[1:]),
        jnp.int32(1), pos, live, interpret=True))(q, k, v, keep)
    s = jnp.einsum("bhqn,bhtn->bhqt", q, k[1],
                   precision=lax.Precision.HIGHEST)
    seen = keep & (jnp.arange(T)[None] <= pos[:, None])
    s = jnp.where(seen[:, None, None], s, -jnp.inf)
    want = jnp.einsum("bhqt,bhtn->bhqn", jax.nn.softmax(s, axis=-1), v[1],
                      precision=lax.Precision.HIGHEST)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on],
                               rtol=0, atol=2e-2)


@pytest.mark.parametrize("T,most,block", [
    (10240, 1024, 1024), (4096, 1024, 1024), (10240, 1500, 1280),
    (25600, 2048, 1280), (13312, 1024, 1024), (96, 1024, 96),
    (1000, 256, 256)])
def test_the_block_divides_the_length_where_whole_lane_tiles_can(
        monkeypatch, T, most, block):
    monkeypatch.setattr(slot_rows, "BLOCK", most)
    assert slot_rows.block_of(T) == block


@pytest.mark.parametrize("on_the_chip", [False, True],
                         ids=["plain-form", "kernels-path"])
def test_read_positions_follow_the_one_platform(monkeypatch, on_the_chip):
    """One `on_tpu` for all of `ops/`: all T a live slot plain, its
    position rounded up to a block where the kernels run."""
    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    T = 3 * BLOCK + 40
    pos = jnp.asarray([0, BLOCK - 1, BLOCK, T - 1, 77])
    live = jnp.asarray([True, True, True, True, False])
    rounded = BLOCK + BLOCK + 2 * BLOCK + T
    assert int(slot_rows.read_positions(pos, live, T)) == (
        rounded if on_the_chip else 4 * T)
    assert int(slot_rows.read_positions(pos, live, T, kernel=False)) == 4 * T
    assert int(slot_rows.read_positions(pos, live, T, interpret=True)) \
        == rounded


def test_a_ring_leaf_is_one_block_masked_at_its_last_row(monkeypatch):
    """A leaf whose T is a sliding window (`ops/gqa_attend.py`, `ring`):
    one block whatever `BLOCK` is, every slot's grid one step, and a
    position past the leaf held at its last row, where `t <= pos` lets
    every row through: the fold is then one softmax over the whole ring."""
    monkeypatch.setattr(slot_rows, "BLOCK", 1024)
    W = 128
    assert slot_rows.block_of(W) == W
    pos = [0, 77, W - 1, W, 40 * W + 5]
    src, first, last, at = (np.asarray(a).tolist() for a in slot_rows.plan(
        jnp.asarray(pos), jnp.ones(5, bool), W, W))
    assert (src, first, last) == (list(range(5)), [0] * 5, [0] * 5)
    assert at == [0, 77, W - 1, W - 1, W - 1]
    q, leaf = _one_leaf(5, W)
    got = _attend(q, leaf, 1, pos, [True] * 5)
    want = _softmax_over_the_row(q, leaf[1], jnp.minimum(
        jnp.asarray(pos), W - 1))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)
    assert int(slot_rows.read_positions(
        jnp.asarray(pos), jnp.ones(5, bool), W, interpret=True)) == 5 * W
