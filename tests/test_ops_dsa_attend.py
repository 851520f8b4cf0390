"""`ops/dsa_attend.py`: the decode kernel, interpreted, against the plain
path (`dsa.attend_selected` over `dsa.gather_rows` with `dsa.select_rows`'
set) on `keye-tiny`'s shapes and on one slot at the published widths; the
two forms of the set; where `keye._attend_first` calls it; and what the
programs count as read."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import keye
from ray_tpu.ops import dsa, slot_rows, slot_state

op = importlib.import_module("ray_tpu.ops.dsa_attend")

F32, BF16 = jnp.float32, jnp.bfloat16
# (G, R, d, topk, T, block): `keye-tiny`'s heads at 96 positions in blocks of
# 32, and the published widths, one slot of the cell's 13,312 positions
TINY = (2, 2, 16, 16, 96, 32)
PUBLISHED = (4, 8, 128, 2048, 13312, 1024)


def _operands(shape, B, dtype, L=1, seed=0, T=None):
    G, R, d, _, T0, _ = shape
    T = T or T0
    ks = jax.random.split(jax.random.key(seed), 4)
    # q as large as it takes for a softmax that is not flat
    return ((4 * jax.random.normal(ks[0], (B, G, R, d), F32)).astype(dtype),
            jax.random.normal(ks[1], (L, B, T, G * d), F32).astype(dtype),
            jax.random.normal(ks[2], (L, B, T, G * d), F32).astype(dtype),
            jax.random.normal(ks[3], (B, T), F32))


def _seen(scores, pos):
    T = scores.shape[1]
    return jnp.where(jnp.arange(T) <= jnp.asarray(pos)[:, None], scores,
                     -jnp.inf)


def _both(monkeypatch, shape, pos, live=None, dtype=BF16, L=1, layer=0,
          T=None, scores=None):
    """(the kernel's values, the plain path's) [B, G, R, d] as numpy, from
    one set of scores chosen in each path's own form."""
    G, R, d, topk, _, block = shape
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.ones(len(pos), bool) if live is None else jnp.asarray(live)
    q, ck, cv, drawn = _operands(shape, len(pos), dtype, L, T=T)
    scores = _seen(drawn if scores is None else scores, pos)
    scale = 1.0 / np.sqrt(d)

    def through(**how):
        return jax.jit(lambda q, ck, cv, scores: op.dsa_attend(
            q, ck, cv, jnp.int32(layer), pos, live,
            op.rows_chosen(scores, topk, **how), scale,
            interpret=how.get("interpret", False)))(q, ck, cv, scores)

    return (np.asarray(through(interpret=True)),
            np.asarray(through(kernel=False)))


# One piece rounds the probabilities to the rows' dtype, the plain path the
# normalised ones and the kernel the unnormalised: 2^-9 of a weighted sum of
# unit-variance values either way. float32 leaves leave the order of the
# sums, a block at a time.
TOLERANCE = {F32: dict(rtol=0, atol=2e-5), BF16: dict(rtol=0, atol=2e-2)}
LEAVES = pytest.mark.parametrize("dtype", [BF16, F32],
                                 ids=["bf16-leaves", "float32-leaves"])


@LEAVES
@pytest.mark.parametrize("pos", [
    [0, 7, 15], [31, 31, 63], [32, 64, 32], [95, 95, 95], [70, 16, 95]],
    ids=["below-topk-all-rows", "a-blocks-last", "a-blocks-first",
         "the-leafs-last", "ragged"])
def test_the_kernel_is_the_plain_path_on_the_tiny_presets_shapes(
        monkeypatch, pos, dtype):
    got, want = _both(monkeypatch, TINY, pos, dtype=dtype)
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])
    assert np.abs(want).max() > 0.5


@pytest.mark.parametrize("pos", [1000, 1023, 1024, 2047, 9000, 13311],
                         ids=lambda p: f"at-{p}")
def test_the_kernel_is_the_plain_path_at_the_published_widths(
        monkeypatch, pos):
    """One slot of 4 x 128 lanes, 8 queries a head, 2,048 of up to 13,312
    rows: below the topk (all rows), on a block's last and first row, the
    last row the topk still covers, mid-leaf and the leaf's last."""
    got, want = _both(monkeypatch, PUBLISHED, [pos])
    np.testing.assert_allclose(got, want, **TOLERANCE[BF16])
    assert np.abs(want).max() > 0.3


@LEAVES
@pytest.mark.parametrize("T,block,pos", [
    (3 * 32 + 8, 32, [3 * 32 + 7, 3 * 32, 5]), (40, 64, [39, 0, 20])],
    ids=["a-ragged-last-block", "one-block"])
def test_a_length_that_is_no_multiple_of_the_block(monkeypatch, T, block,
                                                   pos, dtype):
    shape = (*TINY[:4], T, block)
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    assert slot_rows.block_of(T) == min(T, block)
    got, want = _both(monkeypatch, shape, pos, dtype=dtype, T=T)
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])


@LEAVES
@pytest.mark.parametrize("live", [
    [False, True, True, True], [True, False, True, True],
    [True, False, False, True], [True, True, True, False], [False] * 4],
    ids=["the-first", "one-between", "two-between", "the-last", "all"])
def test_a_dead_slot_reads_nothing_and_the_others_are_exact(
        monkeypatch, live, dtype):
    pos = [40, 95, 3, 64]
    got, want = _both(monkeypatch, TINY, pos, live, dtype)
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], want[on], **TOLERANCE[dtype])
    assert np.isfinite(got).all()
    # a dead slot's grid steps stay on the block the live slot before it
    # ended on: the pipeline moves nothing, of the leaves or of the mask
    src, first, last, _ = (np.asarray(a) for a in slot_rows.plan(
        jnp.asarray(pos), jnp.asarray(live), TINY[4], TINY[5]))
    assert (first[~on] == last[~on]).all() and not first[on].any()
    assert (src[on] == np.flatnonzero(on)).all()


@LEAVES
def test_the_layer_worked_on_is_the_one_named(monkeypatch, dtype):
    pos = [5, 63]
    got, want = _both(monkeypatch, TINY, pos, dtype=dtype, L=3, layer=2)
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])
    other, _ = _both(monkeypatch, TINY, pos, dtype=dtype, L=3, layer=1)
    assert np.abs(other - want).max() > 0.1


@LEAVES
def test_blocks_that_hold_no_chosen_row_leave_nothing_behind(monkeypatch,
                                                             dtype):
    """Slot 0's set lies in its last block, slot 1's in its first and slot
    2's in the middle one: the weights of 1 that an empty block's masked
    scores leave are shrunk to nothing by the first chosen row, and an
    empty block after it adds nothing."""
    T = TINY[4]
    t = jnp.arange(T)
    scores = jnp.stack([jnp.where(t >= 64, 5.0, 0.0) + 0.01 * t,
                        jnp.where(t < 32, 5.0, 0.0) - 0.01 * t,
                        jnp.where((t >= 40) & (t < 56), 5.0, 0.0)])
    got, want = _both(monkeypatch, TINY, [95, 95, 95], dtype=dtype,
                      scores=scores)
    keep = np.asarray(dsa.select_mask(_seen(scores, [95] * 3), TINY[3]))
    assert not keep[0, :64].any() and not keep[1, 32:].any() \
        and not keep[2, :32].any() and not keep[2, 64:].any()
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])


# ------------------------------------------------------------- the one set

def _tied(T, seed):
    """Scores of which many are equal, the set's boundary among them."""
    return jnp.asarray(np.random.default_rng(seed).integers(0, 4, (3, T)),
                       F32)


@LEAVES
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_equal_scores_at_the_sets_boundary_name_the_same_rows(
        monkeypatch, seed, dtype):
    """Four values over 96 positions: the 16th largest is one of some two
    dozen equal scores. The mask and the indices name the same rows
    (ties to the lower index), and the kernel over the one is the plain
    path over the other."""
    topk, T = TINY[3], TINY[4]
    pos = [95, 50, 20]
    scores = _seen(_tied(T, seed), pos)
    keep = np.asarray(dsa.select_mask(scores, topk))
    idx, chosen = (np.asarray(a) for a in dsa.select_rows(scores, topk))
    for b in range(3):
        assert sorted(idx[b][chosen[b]]) == list(np.flatnonzero(keep[b]))
        level = np.asarray(scores)[b, idx[b][chosen[b]]].min()
        assert (np.asarray(scores)[b] == level).sum() > (
            np.asarray(scores)[b, keep[b]] == level).sum() > 0  # a real tie
    got, want = _both(monkeypatch, TINY, pos, dtype=dtype,
                      scores=_tied(T, seed))
    np.testing.assert_allclose(got, want, **TOLERANCE[dtype])


@pytest.mark.parametrize("on_the_chip", [False, True],
                         ids=["plain-form", "kernels-path"])
def test_the_set_comes_in_the_form_the_platform_reads(monkeypatch,
                                                      on_the_chip):
    """No option and no name decides: `slot_state.use_kernel`'s platform
    (or `interpret`, or `kernel`) does, and `dsa_attend` follows the form it
    is handed."""
    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    scores = _seen(_tied(96, 0), [95, 50, 20])
    rows = op.rows_chosen(scores, 16)
    assert isinstance(rows, tuple) != on_the_chip
    assert not isinstance(op.rows_chosen(scores, 16, interpret=True), tuple)
    assert isinstance(op.rows_chosen(scores, 16, kernel=False), tuple)
    if on_the_chip:
        assert rows.shape == (3, 96) and rows.dtype == bool
        np.testing.assert_array_equal(rows, dsa.select_mask(scores, 16))


@pytest.mark.parametrize("on_the_chip", [False, True],
                         ids=["plain-form", "kernels-path"])
def test_read_positions_follow_the_path(monkeypatch, on_the_chip):
    """Through the kernel a live slot's position rounded up to a block
    (`slot_rows.BLOCK`, the one constant); plain the chosen rows."""
    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    monkeypatch.setattr(slot_rows, "BLOCK", 32)
    T = 3 * 32 + 8
    pos = jnp.asarray([0, 31, 32, T - 1, 77])
    live = jnp.asarray([True, True, True, True, False])
    want = (32 + 32 + 64 + T) if on_the_chip else (1 + 16 + 16 + 16)
    assert int(op.read_positions(pos, live, T, 16)) == want
    assert int(op.read_positions(pos, live, T, 16, kernel=False)) == 49
    assert int(op.read_positions(pos, live, T, 16, interpret=True)) \
        == 32 + 32 + 64 + T


# ------------------------------------------------------ where it is called

def _tiny_layer():
    cfg = keye.KeyeConfig.preset("keye-tiny")
    return cfg, jax.tree.map(lambda a: a[1], keye.init_params(
        jax.random.key(0), cfg)["layers"])


def _first_lanes(monkeypatch, cfg, p, how, B=4, T=40):
    """`keye._attend_first` over B slots, one of them not on, with the op's
    two functions steered `how`: (x, the cache)."""
    monkeypatch.setattr(slot_rows, "BLOCK", 16)           # 40: a ragged last block
    monkeypatch.setattr(keye, "rows_chosen", functools.partial(
        op.rows_chosen, **how))
    monkeypatch.setattr(keye, "dsa_attend", functools.partial(
        op.dsa_attend, interpret=how.get("interpret", False)))
    ks = jax.random.split(jax.random.key(3), 4)
    cache = keye.init_cache(cfg, B, T)
    cache = {name: jax.random.normal(k, cache[name].shape, F32).astype(
        cache[name].dtype) for name, k in zip(("k", "v", "ik"), ks)}
    x = jax.random.normal(ks[3], (B, 1, cfg.d_model), F32)
    pos = jnp.asarray([0, 39, 16, 25], jnp.int32)
    on = jnp.asarray([True, True, False, True])
    angles = keye.rope_angles(keye._text_positions(pos, 1), cfg)
    return x, on, jax.jit(lambda x, cache: keye._attend_first(
        x, p, cfg, cache, 1, pos, angles, on))(x, cache)


def test_the_layer_through_the_kernel_is_the_layer_through_the_plain_path(
        monkeypatch):
    """What `keye._attend_first` adds to x through the kernel (positions
    below, at and past the tiny topk of 16, a slot that is not on) is what
    it adds through the gather, and the rows it writes are the same."""
    cfg, p = _tiny_layer()
    x, on, (got, got_cache) = _first_lanes(monkeypatch, cfg, p,
                                           dict(interpret=True))
    _, _, (want, want_cache) = _first_lanes(monkeypatch, cfg, p,
                                            dict(kernel=False))
    on = np.asarray(on)
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on],
                               rtol=0, atol=2e-3)
    assert np.abs(np.asarray(want - x)[on]).max() > 1e-2
    for name in ("k", "v", "ik"):
        np.testing.assert_array_equal(np.asarray(got_cache[name], F32),
                                      np.asarray(want_cache[name], F32))


@pytest.mark.parametrize("how,form", [
    (dict(interpret=True), "mask"), (dict(kernel=False), "indices")])
def test_the_first_lanes_hand_the_op_the_leaves_whole(monkeypatch, how,
                                                      form):
    cfg, p = _tiny_layer()
    calls = []
    real = op.dsa_attend

    def seen(q, ck, cv, layer, pos, live, rows, scale, **kw):
        calls.append((q.shape, q.dtype, ck.shape, cv.shape,
                      "indices" if isinstance(rows, tuple) else "mask"))
        return real(q, ck, cv, layer, pos, live, rows, scale, **kw)

    monkeypatch.setattr(op, "dsa_attend", seen)
    _first_lanes(monkeypatch, cfg, p, how)
    # q in the rows' dtype, [B, G, R, d]; both leaves [L, B, T, G d]
    assert calls == [((4, 2, 2, 16), BF16, (3, 4, 40, 32), (3, 4, 40, 32),
                      form)]
