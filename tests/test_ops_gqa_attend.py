"""`ops/gqa_attend.py`: the decode kernel, interpreted, against the plain
`lm.gqa_attend` over the same rows at Solar's head sizes; where
`kimi._gqa` calls it; what the decode program counts as read; and the body
for leaves with the positions on the lanes, against `models/gpt2.py`'s plain
lines at GPT-2 XL's geometry and `lm.gqa_attend` at granite's."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, kimi, lm
from ray_tpu.ops import slot_rows, slot_state

op = importlib.import_module("ray_tpu.ops.gqa_attend")

G, R, D = 8, 8, 128
SCALE = 1.0 / np.sqrt(D)
BLOCK = 128
F32, BF16 = jnp.float32, jnp.bfloat16


def _operands(B, T, q_dtype, L=1, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    # q as large as it takes for a softmax that is not flat: the weights of
    # a few hundred positions differ by orders of magnitude
    return (4 * jax.random.normal(ks[0], (B, G, R, D), F32).astype(q_dtype),
            jax.random.normal(ks[1], (L, B, G, T, D), F32).astype(BF16),
            jax.random.normal(ks[2], (L, B, G, T, D), F32).astype(BF16))


def _both(monkeypatch, T, pos, live, q_dtype=F32, L=1, layer=0, block=BLOCK):
    """(the kernel's values, the plain form's) [B, G, R, d] as numpy."""
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    pos = jnp.asarray(pos, jnp.int32)
    live = jnp.asarray(live, bool)
    args = (*_operands(len(pos), T, q_dtype, L), jnp.int32(layer), pos, live)
    got = jax.jit(lambda *a: op.gqa_attend(*a, SCALE, interpret=True))(*args)
    want = jax.jit(lambda *a: op.gqa_attend(*a, SCALE, kernel=False))(*args)
    return np.asarray(got), np.asarray(want)


# Two pieces carry 16 bits of q and of a probability: what is left is the
# order of the sums, a block at a time. One piece rounds the probabilities
# to bf16, the plain form the normalised ones and the kernel the
# unnormalised: 2^-9 of a weighted sum of unit-variance values either way.
TOLERANCE = {F32: dict(rtol=0, atol=2e-5), BF16: dict(rtol=0, atol=2e-2)}
PIECES = pytest.mark.parametrize("q_dtype", [F32, BF16],
                                 ids=["float32-q-two-pieces",
                                      "bf16-q-one-piece"])


@PIECES
@pytest.mark.parametrize("pos", [
    [0, 0, 0], [BLOCK - 1] * 3, [BLOCK] * 3, [4 * BLOCK - 1] * 3,
    [0, BLOCK - 1, BLOCK], [3 * BLOCK + 5, 17, 4 * BLOCK - 1]],
    ids=["first", "a-blocks-last", "a-blocks-first", "the-leafs-last",
         "ragged-at-the-edges", "ragged"])
def test_the_kernel_is_the_plain_form_to_each_slots_own_position(
        monkeypatch, pos, q_dtype):
    got, want = _both(monkeypatch, 4 * BLOCK, pos, [True] * 3, q_dtype)
    np.testing.assert_allclose(got, want, **TOLERANCE[q_dtype])
    assert np.abs(want).max() > 0.5


def test_one_piece_of_a_float32_q_is_another_function(monkeypatch):
    """What the two pieces are for: the same q rounded to the rows' dtype
    lies a hundred tolerances from the float32 q's values, in the kernel as
    in the plain form."""
    pos = [3 * BLOCK + 5, 17, 4 * BLOCK - 1]
    two, want = _both(monkeypatch, 4 * BLOCK, pos, [True] * 3, F32)
    one, _ = _both(monkeypatch, 4 * BLOCK, pos, [True] * 3, BF16)
    assert np.abs(one - want).max() > 100 * TOLERANCE[F32]["atol"]
    np.testing.assert_allclose(two, want, **TOLERANCE[F32])


@PIECES
@pytest.mark.parametrize("live", [
    [False, True, True, True], [True, True, True, False],
    [False, False, True, False], [False] * 4],
    ids=["the-first", "the-last", "all-but-one", "all"])
def test_a_dead_slot_reads_nothing_and_the_others_are_exact(
        monkeypatch, live, q_dtype):
    pos = [300, 3 * BLOCK + 1, 40, 2 * BLOCK]
    got, want = _both(monkeypatch, 4 * BLOCK, pos, live, q_dtype)
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], want[on], **TOLERANCE[q_dtype])
    assert np.isfinite(got).all() and not got[~on].any()
    # a dead slot's grid steps stay on the block the live slot before it
    # ended on (none before it: slot 0's block 0): the pipeline moves nothing
    src, first, last, _ = (np.asarray(a) for a in slot_rows.plan(
        jnp.asarray(pos), jnp.asarray(live), 4 * BLOCK, BLOCK))
    assert (first[~on] == last[~on]).all() and not first[on].any()
    assert (src[on] == np.flatnonzero(on)).all()


@PIECES
def test_the_layer_worked_on_is_the_one_named(monkeypatch, q_dtype):
    pos = [5, 2 * BLOCK - 1]
    got, want = _both(monkeypatch, 2 * BLOCK, pos, [True] * 2, q_dtype,
                      L=3, layer=2)
    np.testing.assert_allclose(got, want, **TOLERANCE[q_dtype])
    other, _ = _both(monkeypatch, 2 * BLOCK, pos, [True] * 2, q_dtype,
                     L=3, layer=1)
    assert np.abs(other - want).max() > 0.1


@PIECES
@pytest.mark.parametrize("T,block,pos", [
    (3 * BLOCK + 40, BLOCK, [3 * BLOCK + 39, 3 * BLOCK, 7]),
    (200, 256, [199, 0, 100])], ids=["a-ragged-last-block", "one-block"])
def test_a_length_that_is_no_multiple_of_the_block(monkeypatch, T, block,
                                                   pos, q_dtype):
    # 424 has no divisor that is whole lane tiles: its last block hangs over
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    assert slot_rows.block_of(T) == min(T, block)
    got, want = _both(monkeypatch, T, pos, [True] * 3, q_dtype, block=block)
    np.testing.assert_allclose(got, want, **TOLERANCE[q_dtype])


@pytest.mark.parametrize("T,most,block", [
    (25600, 1024, 1024), (25600, 2048, 1280), (25600, 2560, 2560),
    (96, 1024, 96), (1000, 256, 256)])
def test_the_block_is_this_kernels_own_and_divides_the_length(
        monkeypatch, T, most, block):
    monkeypatch.setattr(slot_rows, "BLOCK", most)
    assert slot_rows.block_of(T) == block


def test_leaves_with_the_positions_on_the_lanes_take_the_body_of_their_own():
    """Which way round a leaf lies is read off its shape: the same rows,
    their last two axes swapped, give the same values through the other
    body, and only that one takes a slot's own row."""
    q, ck, cv = _operands(2, 2 * BLOCK, F32)    # two pieces: a block's order
    pos, live = jnp.asarray([5, 2 * BLOCK - 1]), jnp.ones(2, bool)
    rows = op.gqa_attend(q, ck, cv, 0, pos, live, SCALE, interpret=True)
    last = op.gqa_attend(q, jnp.swapaxes(ck, 3, 4), jnp.swapaxes(cv, 3, 4),
                         0, pos, live, SCALE, interpret=True)
    np.testing.assert_allclose(last, rows, **TOLERANCE[F32])
    assert op.rows_kernel(q, ck, cv, SCALE).body.func is op._block_body
    assert op.rows_kernel(q, ck, cv, SCALE, last=True).body.func \
        is op._lanes_body
    with pytest.raises(AssertionError):
        op.rows_kernel(q, ck, cv, SCALE, own=(q[:, :, 0], q[:, :, 0]))


def test_read_positions_are_a_slots_position_rounded_up_to_a_block(
        monkeypatch):
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    T = 3 * BLOCK + 40
    pos = jnp.asarray([0, BLOCK - 1, BLOCK, T - 1, 77])
    live = jnp.asarray([True, True, True, True, False])
    assert int(op.read_positions(pos, live, T, kernel=False)) == 4 * T
    assert int(op.read_positions(pos, live, T, interpret=True)) == (
        BLOCK + BLOCK + 2 * BLOCK + T)


# ------------------------------------------------------ where it is called

def _solar_layer():
    cfg = kimi.KimiConfig.preset("solar-tiny")
    bp = jax.tree.map(lambda a: a[1], kimi.init_params(
        jax.random.key(0), cfg)["gqa"])
    return cfg, bp


def _one_layers_call(C, slot, B=3, T=32):
    """Trace softmax layer 1 of the tiny Solar with C lanes a row."""
    cfg, bp = _solar_layer()
    N = B if slot is None else 1
    cache = kimi.init_cache(cfg, B, T)
    x = jnp.ones((N, C, cfg.d_model), jnp.float32)
    out = kimi._gqa(x, bp, cfg, cache, 1, jnp.arange(N, dtype=jnp.int32) + 2,
                    jnp.ones((N, C), bool), slot)[0]
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("C,slot,through", [(1, None, True), (4, 1, False)],
                         ids=["every-slots-one-lane", "one-slots-lanes"])
def test_the_softmax_layer_calls_the_op_for_every_slots_one_lane_only(
        monkeypatch, C, slot, through):
    calls = []

    def seen(*args, **kwargs):
        calls.append((args[0].dtype, args[1].shape, args[2].shape))
        return op.gqa_attend(*args, **kwargs)

    monkeypatch.setattr(kimi, "gqa_attend", seen)
    _one_layers_call(C, slot)
    # a float32 q, and both leaves whole: [L, B, G, T, d]
    assert calls == ([(F32, (2, 3, 2, 32, 16), (2, 3, 2, 32, 16))]
                     if through else [])


def test_the_layer_through_the_kernel_is_the_layer_through_the_plain_form(
        monkeypatch):
    """`kimi._gqa` at one lane a slot with a slot that is not on: what it
    adds to x through the kernel is what it adds through `lm.gqa_attend`,
    with no backend asked."""
    cfg, bp = _solar_layer()
    B, T = 4, 40
    monkeypatch.setattr(slot_rows, "BLOCK", 16)             # 40: a ragged last block
    ks = jax.random.split(jax.random.key(3), 3)
    cache = kimi.init_cache(cfg, B, T)
    cache = {**cache, **{name: jax.random.normal(
        k, cache[name].shape, F32).astype(cache[name].dtype)
        for name, k in zip(("k", "v"), ks)}}
    x = jax.random.normal(ks[2], (B, 1, cfg.d_model), F32)
    pos0 = jnp.asarray([0, 39, 16, 7], jnp.int32)
    ok = jnp.asarray([True, True, False, True])[:, None]

    def layer(interpret):
        how = dict(interpret=True) if interpret else dict(kernel=False)
        monkeypatch.setattr(kimi, "gqa_attend", lambda *a: op.gqa_attend(
            *a, **how))
        return jax.jit(lambda x, cache: kimi._gqa(
            x, bp, cfg, cache, 1, pos0, ok))(x, cache)

    (got, got_cache), (want, want_cache) = layer(True), layer(False)
    on = np.asarray(ok[:, 0])
    np.testing.assert_allclose(np.asarray(got)[on], np.asarray(want)[on],
                               rtol=0, atol=1e-5)
    assert np.abs(np.asarray(want - x)[on]).max() > 1e-2
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_cache[name], F32),
                                      np.asarray(want_cache[name], F32))


@pytest.mark.parametrize("on_the_chip", [False, True],
                         ids=["plain-form", "kernels-path"])
def test_the_programs_count_of_positions_read_follows_the_path(
        monkeypatch, on_the_chip):
    """`kimi._read_positions`' grouped-head branch: all T a live slot in
    the plain form, its position rounded up to a block where the kernel
    runs; a prefilling slot's further lanes `lm.gqa_attend_blocks`' turns
    either way."""
    monkeypatch.setattr(slot_state, "on_tpu", lambda: on_the_chip)
    monkeypatch.setattr(slot_rows, "BLOCK", 32)
    monkeypatch.setattr(lm, "GQA_BLOCK", 48)
    T = 160
    cache = {"k": jnp.zeros((1, 4, 2, T, 16), BF16)}
    pos0 = jnp.asarray([0, 31, 32, 159], jnp.int32)
    on = jnp.asarray([True, True, True, False])
    first = 4 * 32 if on_the_chip else 3 * T
    assert int(kimi._read_positions(cache, pos0, None, on, None)) == first
    # slot 1 prefills 20 lanes from position 31: its further lanes reach
    # position 50, two turns of 48
    length = jnp.asarray([1, 20, 1, 0], jnp.int32)
    further = jnp.arange(19)[None, :] < (length - 1)[:, None]
    assert int(kimi._read_positions(cache, pos0, length, on, further)) == (
        first + 2 * 48)


# ------------------------------------------------------------- a ring leaf

W = 128                     # the window, and a head's lanes: a square leaf


def _ring(B, pos, L=2, seed=0, stale=9.0):
    """Rings [L, B, G, W, d] as a sequence that has reached position pos[b]
    leaves them: position p at row p mod W; the rows the sequence has not
    reached hold another request's values (large ones: a live stale row
    would move the result by far more than any tolerance)."""
    ks = jax.random.split(jax.random.key(seed), 4)
    most = max(pos) + 1
    keys = jax.random.normal(ks[0], (L, B, G, most, D), F32)
    vals = jax.random.normal(ks[1], (L, B, G, most, D), F32)
    wk = stale * jax.random.normal(ks[2], (L, B, G, W, D), F32)
    wv = stale + jax.random.normal(ks[3], (L, B, G, W, D), F32)
    for b, p in enumerate(pos):
        for t in range(max(0, p - W + 1), p + 1):
            wk = wk.at[:, b, :, t % W].set(keys[:, b, :, t])
            wv = wv.at[:, b, :, t % W].set(vals[:, b, :, t])
    return (keys.astype(BF16), vals.astype(BF16), wk.astype(BF16),
            wv.astype(BF16))


def _banded(q, keys, vals, layer, pos):
    """The plain banded form over the whole sequence: slot b's query at
    pos[b] against positions pos[b] - W + 1 .. pos[b], in float64."""
    out = np.zeros(q.shape, np.float64)
    q, keys, vals = (np.asarray(a, np.float64) for a in (q, keys, vals))
    for b, p in enumerate(pos):
        seen = np.arange(max(0, p - W + 1), p + 1)
        s = np.einsum("grd,gtd->grt", q[b], keys[layer, b][:, seen]) * SCALE
        w = np.exp(s - s.max(-1, keepdims=True))
        out[b] = np.einsum("grt,gtd->grd", w / w.sum(-1, keepdims=True),
                           vals[layer, b][:, seen])
    return out


@PIECES
@pytest.mark.parametrize("pos", [
    [0, 1, 126], [127, 128, 129], [5 * W + 77, 3 * W - 1, 3 * W]],
    ids=["filling", "the-first-wrap", "past-a-wrap"])
def test_a_ring_through_the_kernel_is_the_banded_form_with_stale_rows_dead(
        pos, q_dtype):
    """`ring=True`: the leaf is the last 128 positions, position p at row p
    mod 128, and 128 x 128 (nothing can be read off its shape). At
    positions 0, 1, 126, 127, 128, 129 and past several wraps, with a stale
    ring from an earlier request in the slot: the kernel (interpreted) and
    the plain form both give the banded form over the whole sequence."""
    keys, vals, wk, wv = _ring(3, pos)
    q = (4 * jax.random.normal(jax.random.key(7), (3, G, R, D), F32)).astype(
        q_dtype)
    args = (q, wk, wv, jnp.int32(1), jnp.asarray(pos, jnp.int32),
            jnp.ones(3, bool))
    got = jax.jit(lambda *a: op.gqa_attend(*a, SCALE, ring=True,
                                           interpret=True))(*args)
    plain = jax.jit(lambda *a: op.gqa_attend(*a, SCALE, ring=True,
                                             kernel=False))(*args)
    want = _banded(q, keys, vals, 1, pos)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, plain, **TOLERANCE[q_dtype])
    # against float64 the two pieces' own remainder shows (16 bits of q and
    # of a probability): 6e-5; a stale row let in would show as 1 and more
    exact = dict(rtol=0, atol=2e-4 if q_dtype == F32 else 2e-2)
    np.testing.assert_allclose(got, want, **exact)
    np.testing.assert_allclose(plain, want, **exact)
    # the other layer's ring is another sequence's
    other = _banded(q, keys, vals, 0, pos)
    assert np.abs(other - want).max() > 0.1


def test_a_ring_is_one_block_whose_mask_is_the_rows_mask_held_at_its_end():
    """What `ring=True` costs the kernel: nothing. A ring is a leaf whose T
    is the window, one block; `plan` holds a position past it at row W - 1,
    where the body's `t <= pos` is the mask by age; the call is named
    `swa_attend`, the rows' `gqa_attend`; and a ring's shape is refused
    without the word."""
    assert slot_rows.block_of(W) == W
    pos = jnp.asarray([0, 5, W - 1, W, 9 * W + 3])
    _, first, last, held = (np.asarray(a).tolist() for a in slot_rows.plan(
        pos, jnp.ones(5, bool), W, W))
    assert first == last == [0] * 5
    assert held == [0, 5, W - 1, W - 1, W - 1]
    for p in np.asarray(pos).tolist():
        live_by_age = np.asarray(lm.ring_positions(p, W)) >= 0
        np.testing.assert_array_equal(live_by_age,
                                      np.arange(W) <= min(p, W - 1))
    q, ck, cv = _operands(2, W, F32)
    assert op.rows_kernel(q, ck, cv, SCALE).name == "gqa_attend"
    assert op.rows_kernel(q, ck, cv, SCALE, "swa_attend").name == "swa_attend"
    with pytest.raises(AssertionError):
        op.gqa_attend(q, ck, cv, 0, jnp.zeros(2, jnp.int32),
                      jnp.ones(2, bool), SCALE, kernel=False)


def test_a_dead_slot_of_a_ring_reads_nothing():
    pos = [300, 40, 127]
    keys, vals, wk, wv = _ring(3, pos, L=1)
    wk = wk.at[:, 1].set(jnp.nan)
    q = jax.random.normal(jax.random.key(2), (3, G, R, D), F32)
    live = jnp.asarray([True, False, True])
    got = np.asarray(jax.jit(lambda *a: op.gqa_attend(
        *a, SCALE, ring=True, interpret=True))(
            q, wk, wv, jnp.int32(0), jnp.asarray(pos, jnp.int32), live))
    want = _banded(q, keys, vals, 0, pos)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=0, atol=2e-4)
    assert not got[1].any()


@pytest.mark.parametrize("how", [dict(interpret=True), dict(kernel=False)],
                         ids=["kernel", "plain"])
def test_a_rings_row_is_written_at_the_position_modulo_the_window(how):
    """`ops/rows_write.py` with `ring=True`: position p goes to row p mod
    128 of a 128 x 128 leaf (whose way round cannot be read off its shape),
    a slot that is not on keeps its ring bit for bit, and no other layer or
    row changes."""
    from ray_tpu.ops.rows_write import rows_write

    ring = jax.random.normal(jax.random.key(0), (2, 3, G, W, D), F32).astype(
        BF16)
    val = jax.random.normal(jax.random.key(1), (3, G, D), F32).astype(BF16)
    pos = jnp.asarray([5, 3 * W + 17, 2 * W - 1], jnp.int32)
    on = jnp.asarray([True, True, False])
    got = np.asarray(jax.jit(lambda c, v: rows_write(
        c, jnp.int32(1), v, pos, on, ring=True, **how))(ring, val), np.float32)
    want = np.asarray(ring, np.float32).copy()
    want[1, 0, :, 5] = np.asarray(val[0], np.float32)
    want[1, 1, :, 17] = np.asarray(val[1], np.float32)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(AssertionError):
        rows_write(ring, jnp.int32(1), val, pos, on, **how)


# ------------------------------------------- the positions on the lanes

LAST = 256                  # `BLOCK_LAST` in these tests, whatever the chip's
XL, GRANITE = dict(G=25, R=1, T=1024), dict(G=8, R=4, T=8192)
EDGES = [0, LAST - 1, LAST, 1023]
LANES_CASES = {
    # GPT-2 XL's geometry, through `gpt2._decode_attend`: a bf16 q, the
    # step's own row handed over
    "gpt2-on-and-beside-block-edges": dict(XL, pos=EDGES, live=[1, 1, 1, 1]),
    "gpt2-dead-slots-between-live-ones": dict(
        XL, pos=[300, 0, 2 * LAST - 1, 2 * LAST, 77, 1023],
        live=[1, 0, 1, 0, 0, 1]),
    "gpt2-a-dead-slot-first-and-last": dict(XL, pos=EDGES,
                                            live=[0, 1, 1, 0]),
    "gpt2-the-own-row-alone-at-position-0": dict(XL, pos=[0, 0],
                                                 live=[1, 1]),
    "gpt2-no-slot-live": dict(XL, pos=EDGES, live=[0, 0, 0, 0]),
    # granite's (`granite._attention_first`, PR 63): rows written before
    # they are read; at 8,192 the block is a 16th of the leaf
    "granite-rows-written-first": dict(
        GRANITE, block=512, pos=[0, LAST - 1, LAST, 511, 512, 8191, 5000],
        live=[1, 1, 1, 1, 1, 1, 0]),
    "granite-the-own-row-handed-over": dict(
        GRANITE, block=512, pos=[0, 512, 8191], live=[1, 1, 1], own=True),
    # a q of another dtype: two pieces, of it and of its probabilities
    "float32-q-two-pieces": dict(GRANITE, T=1024, pos=EDGES,
                                 live=[1, 1, 0, 1], q=F32),
    "float32-q-two-pieces-the-own-row": dict(
        GRANITE, T=1024, pos=EDGES, live=[1, 1, 0, 1], q=F32, own=True),
}


@pytest.mark.parametrize("case", LANES_CASES)
def test_leaves_by_the_lane_through_the_kernel_are_the_plain_form(
        monkeypatch, case):
    """`_lanes_body` on `slot_rows.attend`'s grid, interpreted: every live
    slot's values are the plain form's (GPT-2: `decode_step`'s own lines,
    which read the cache as it was and the own row beside it; the others:
    `lm.gqa_attend` over the layer with the own row written first), a slot
    that is not live gets zeros, and its NaN rows are never read."""
    G, R, T, pos, live = (LANES_CASES[case][n] for n in (
        "G", "R", "T", "pos", "live"))
    q_dtype = LANES_CASES[case].get("q", BF16)
    is_gpt2 = case.startswith("gpt2")
    monkeypatch.setattr(op, "BLOCK_LAST", LAST)
    assert op.block_last(T) == LANES_CASES[case].get("block", LAST)
    B, L, d, layer = len(pos), 2, 64, 1
    ks = jax.random.split(jax.random.key(61), 5)
    q = (4 * jax.random.normal(ks[0], (B, G, R, d), F32)).astype(q_dtype)
    ck, cv = (jax.random.normal(k, (L, B, G, d, T), F32).astype(BF16)
              for k in ks[1:3])
    own = tuple(jax.random.normal(k, (B, G, d), F32).astype(BF16)
                for k in ks[3:]) if is_gpt2 or LANES_CASES[case].get(
                    "own") else ()
    pos, live = jnp.asarray(pos, jnp.int32), jnp.asarray(live, bool)
    on = np.asarray(live)
    # what a dead slot holds must not matter: the grid reads none of it
    dead = jnp.where(live, 0.0, jnp.nan)[None, :, None, None, None]
    args = (q, (ck + dead).astype(BF16), (cv + dead).astype(BF16), *own)
    scale = 1.0 / np.sqrt(d)
    if is_gpt2:
        def through(interpret):
            return jax.jit(lambda q, ck, cv, k, v: gpt2._decode_attend(
                q[:, :, 0], k, v, {"k": jnp.swapaxes(ck, 3, 4),
                                   "v": jnp.swapaxes(cv, 3, 4)},
                jnp.int32(layer), pos, live,
                interpret=interpret)[:, :, None])(*args)
    else:
        def through(interpret):
            how = dict(interpret=True) if interpret else dict(kernel=False)
            return jax.jit(lambda q, ck, cv, *own: op.gqa_attend(
                q, ck, cv, jnp.int32(layer), pos, live, scale, own=own,
                **how))(*args)
    got, want = np.asarray(through(True)), np.asarray(through(False))
    assert got.shape == (B, G, R, d) and got.dtype == np.float32
    np.testing.assert_allclose(got[on], want[on], **TOLERANCE[q_dtype])
    assert np.isfinite(got).all() and not got[~on].any()
    if on.any():
        assert np.abs(want[on]).max() > 0.5
    if is_gpt2:
        # the op's own plain form, the row written first, is those lines
        rows = np.asarray(op.gqa_attend(*args[:3], jnp.int32(layer), pos,
                                        live, scale, own=own, kernel=False))
        np.testing.assert_allclose(rows[on], want[on], **TOLERANCE[q_dtype])
    if case == "gpt2-the-own-row-alone-at-position-0":
        np.testing.assert_allclose(got[:, :, 0], np.asarray(own[1], F32),
                                   rtol=0, atol=1e-6)


@pytest.mark.parametrize("T,least,block", [
    (1024, 256, 256), (1024, 512, 512), (8192, 256, 512), (1024, 2048, 1024),
    (96, 256, 96), (384, 256, 128), (1000, 256, 256),
    # the shipped `BLOCK_LAST`: GPT-2's cells' leaf and granite's cell's
    (1024, None, 128), (8192, None, 512), (2048, None, 128),
    (4096, None, 256), (64, None, 64), (16384, None, 1024),
    (131072, None, 1024),   # `slot_rows.BLOCK`: what VMEM was sized for
    # 16ths that are no whole tiles or divide nothing: the longest that do
    (3072, None, 128), (6144, None, 384), (10240, None, 640),
    # a length no block divides: the bound itself, the last block ragged
    (8200, None, 512), (1000, None, 128)])
def test_the_lanes_block_is_whole_lane_tiles_that_divide_the_length(
        monkeypatch, T, least, block):
    """`BLOCK_LAST` positions at least and a 16th of the leaf above that,
    cut to whole lane tiles that divide T where there are such."""
    if least is not None:
        monkeypatch.setattr(op, "BLOCK_LAST", least)
    assert (op.BLOCK_LAST, op.STEPS_LAST) == (least or 128, 16)
    assert op.block_last(T) == block
    assert block == T or block % 128 == 0


def _grids(jaxpr):
    """The grid of every Pallas call in a jaxpr, calls within calls too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _grids(sub)


@pytest.mark.parametrize("how", [dict(interpret=True), dict(kernel=False)],
                         ids=["kernel", "plain"])
@pytest.mark.parametrize("keys,values,T,block", [
    ((8, 64, 8192), (8, 64, 8192), 8192, 512),      # granite's leaves
    ((25, 64, 1024), (25, 64, 1024), 1024, 128),    # GPT-2's, as viewed
    ((8, 2304, 64), (8, 2304, 64), 2304, 768),      # rows: `slot_rows.BLOCK`
    ((4, 192, 2304), (4, 2304, 128), 2304, 768),    # MiMo's: keys last only
], ids=["lanes-8192", "lanes-1024", "rows", "keys-on-the-lanes"])
def test_read_block_is_the_block_the_call_takes(keys, values, T, block, how):
    """`read_block`, what the engines count `positions_read` by, against the
    program `gqa_attend` traces for the same leaves: the grid's steps a slot
    are T over it where the kernel runs; no Pallas call and all T where the
    plain form does."""
    B, d = 2, 64 if keys[1] == 64 or keys[2] == 64 else 192
    ck, cv = (jax.ShapeDtypeStruct((1, B) + shape, BF16)
              for shape in (keys, values))
    q = jax.ShapeDtypeStruct((B, keys[0], 2, d), BF16)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    live = jax.ShapeDtypeStruct((B,), bool)
    grids = list(_grids(jax.make_jaxpr(lambda q, ck, cv, pos, live: (
        op.gqa_attend(q, ck, cv, 0, pos, live, 0.125, **how)))(
            q, ck, cv, pos, live).jaxpr))
    got = op.read_block(ck.shape, cv.shape, d, **how)
    if "kernel" in how:
        assert not grids and got == T
    else:
        assert got == block and grids == [(B, T // block)]


def test_a_leaf_by_the_lane_whose_last_block_hangs_over(monkeypatch):
    """1,000 positions in blocks of 256: the last block's lanes past the
    leaf hold whatever VMEM held, and reach no result."""
    monkeypatch.setattr(op, "BLOCK_LAST", LAST)
    B, G, R, d, T = 3, 2, 4, 64, 1000
    ks = jax.random.split(jax.random.key(5), 3)
    q = (4 * jax.random.normal(ks[0], (B, G, R, d), F32)).astype(BF16)
    ck, cv = (jax.random.normal(k, (1, B, G, d, T), F32).astype(BF16)
              for k in ks[1:])
    pos, live = jnp.asarray([999, 3 * LAST, 100]), jnp.ones(3, bool)
    got = op.gqa_attend(q, ck, cv, 0, pos, live, 0.125, interpret=True)
    want = op.gqa_attend(q, ck, cv, 0, pos, live, 0.125, kernel=False)
    np.testing.assert_allclose(got, want, **TOLERANCE[BF16])


# ---------------------------------------------------------------------------
# Keys of 192 lanes beside values of 128, 16 queries a head, a sink
# (MiMo-V2: the keys hold the positions on the lanes, the values a position a
# row; `rows_write.leaves_lie`)
# ---------------------------------------------------------------------------

DK, DV = 192, 128


def _mimo_operands(B, T, heads, per, seed=0, L=2, ring=False):
    """q [B, G, R, 192] float32, keys [L, B, G, 192, T], values
    [L, B, G, T, 128] in bf16, and a sink a query head [G, R]."""
    ks = jax.random.split(jax.random.key(seed), 4)
    return (3 * jax.random.normal(ks[0], (B, heads, per, DK), F32),
            jax.random.normal(ks[1], (L, B, heads, DK, T), F32).astype(BF16),
            jax.random.normal(ks[2], (L, B, heads, T, DV), F32).astype(BF16),
            2.0 + jax.random.normal(ks[3], (heads, per), F32))


def _loop(q, ck, cv, layer, pos, scale, sink=None, window=None):
    """A slot, a head and a query at a time, in float64: the rows before and
    at `pos` (a ring: the last `window`, wherever they lie)."""
    q, ck, cv = (np.asarray(a, np.float64) for a in (q, ck, cv))
    B, G, R, _ = q.shape
    out = np.zeros((B, G, R, cv.shape[-1]))
    for b in range(B):
        if window is None:
            seen = np.arange(pos[b] + 1)
        else:       # position p lies at p mod W
            seen = np.arange(max(0, pos[b] - window + 1), pos[b] + 1) % window
        for g in range(G):
            k, v = ck[layer, b, g][:, seen], cv[layer, b, g][seen]
            for r in range(R):
                s = q[b, g, r] @ k * scale
                top = s.max() if sink is None else max(s.max(), sink[g, r])
                w = np.exp(s - top)
                total = w.sum() + (0 if sink is None
                                   else np.exp(sink[g, r] - top))
                out[b, g, r] = (w / total) @ v
    return out


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("pos,live", [
    ([0, 1, 2], [True] * 3), ([BLOCK - 1, BLOCK, 3 * BLOCK - 1], [True] * 3),
    ([2 * BLOCK + 5, 17, 3 * BLOCK - 1], [True, False, True]),
    ([40, 300, 7], [False, False, True])],
    ids=["first", "ragged-at-the-edges", "a-dead-slot", "dead-slots-first"])
def test_keys_on_the_lanes_beside_values_by_the_row_at_16_queries_a_head(
        monkeypatch, pos, live, sink):
    """A global layer's shape (4 key-value heads, 16 queries each, 192 and
    128 lanes), interpreted, against `lm.gqa_attend` and a loop: ragged
    positions, dead slots, and a sink (which a global layer does not have:
    the fold's start is the kernel's, whoever asks)."""
    monkeypatch.setattr(slot_rows, "BLOCK", BLOCK)
    q, ck, cv, b = _mimo_operands(3, 3 * BLOCK, 4, 16)
    b = b if sink else None
    scale = DK ** -0.5
    args = (q, ck, cv, jnp.int32(1), jnp.asarray(pos, jnp.int32),
            jnp.asarray(live))
    got = np.asarray(jax.jit(lambda *a: op.gqa_attend(
        *a, scale, sink=b, interpret=True))(*args))
    plain = np.asarray(jax.jit(lambda *a: op.gqa_attend(
        *a, scale, sink=b, kernel=False))(*args))
    assert got.shape == plain.shape == (3, 4, 16, DV)
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], plain[on], **TOLERANCE[F32])
    want = _loop(q, ck, cv, 1, pos, scale, None if b is None
                 else np.asarray(b))
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=2e-4)
    assert np.isfinite(got).all() and not got[~on].any()
    kernel = op.rows_kernel(q, ck, cv, scale, last=True, values_last=False)
    assert kernel.body.func is op._lanes_body and kernel.acc == (4, 16, DV)


@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
def test_a_ring_of_keys_on_the_lanes_with_a_sink_a_head(sink):
    """A sliding layer's rings (8 key-value heads, 8 queries each; keys
    [.., 192, 128], values [.., 128, 128]) under `swa_attend`: a ring that
    fills (rows 0 .. pos), one just full, one that has wrapped, and a dead
    slot; the sink takes its share and weighs no value."""
    W = 128
    q, wk, wv, b = _mimo_operands(4, W, 8, 8, seed=1, L=3)
    b = b if sink else None
    pos, live = [5, W - 1, 3 * W + 17, 40], [True, True, True, False]
    scale = DK ** -0.5
    args = (q, wk, wv, jnp.int32(2), jnp.asarray(pos, jnp.int32),
            jnp.asarray(live))
    got = np.asarray(jax.jit(lambda *a: op.gqa_attend(
        *a, scale, ring=True, sink=b, interpret=True))(*args))
    plain = np.asarray(jax.jit(lambda *a: op.gqa_attend(
        *a, scale, ring=True, sink=b, kernel=False))(*args))
    on = np.asarray(live)
    np.testing.assert_allclose(got[on], plain[on], **TOLERANCE[F32])
    want = _loop(q, wk, wv, 2, pos, scale,
                 None if b is None else np.asarray(b), window=W)
    np.testing.assert_allclose(got[on], want[on], rtol=0, atol=2e-4)
    assert not got[~on].any()
    if sink:
        # the sink takes probability: every weighted sum is the smaller
        without = np.asarray(op.gqa_attend(*args, scale, ring=True,
                                           interpret=True))
        assert np.abs(without[on] - got[on]).max() > 1e-2


@pytest.mark.parametrize("ring", [False, True], ids=["rows", "ring"])
def test_rows_write_takes_a_key_of_192_lanes_along_the_lanes(ring):
    """`ops/rows_write.py`, interpreted, against its plain form: a key of
    192 lanes into a leaf [.., 192, T] (a ring [.., 192, 128]: at pos mod
    128) and a value of 128 into [.., T, 128]; a slot that is not on keeps
    its tile to the bit."""
    from ray_tpu.ops.rows_write import leaves_lie, rows_write

    T = 128 if ring else 384
    _, ck, cv, _ = _mimo_operands(3, T, 4, 1, seed=2)
    assert leaves_lie(ck.shape, cv.shape, DK, ring) == (True, False)
    pos = jnp.asarray([5, 300, 127], jnp.int32)
    on = jnp.asarray([True, True, False])
    for leaf, d, axis in ((ck, DK, 4), (cv, DV, 3)):
        val = jax.random.normal(jax.random.key(d), (3, 4, d), F32).astype(
            BF16)
        got = rows_write(leaf, jnp.int32(1), val, pos, on, ring=ring,
                         interpret=True)
        want = rows_write(leaf, jnp.int32(1), val, pos, on, ring=ring,
                          kernel=False)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))
        at = np.asarray(pos) % T
        moved = np.moveaxis(np.asarray(got, np.float32), axis, 3)
        for slot in (0, 1):
            np.testing.assert_array_equal(
                moved[1, slot, :, at[slot]], np.asarray(val[slot],
                                                        np.float32))
        np.testing.assert_array_equal(
            np.asarray(got[:, 2], np.float32),
            np.asarray(leaf[:, 2], np.float32))
