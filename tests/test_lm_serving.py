"""What the serving families share (`models/lm.py`, "The serving families";
`ops/pieces.py`), each function alone. The families' own tests
(`tests/test_{deepseek,brumby,granite,kimi}_serving.py`) reach the same code
through each family's two programs."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import lm
from ray_tpu.ops.pieces import pieces


def bits(a):
    return np.asarray(a).tobytes()


# ------------------------------------------------------- the lanes of a chunk

def lanes(B, C, D=3, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((B, C, D)), jnp.float32)
    length = rng.integers(0, C + 1, B)
    length[0], length[-1] = C, 0            # a whole chunk, an empty one
    active = np.ones(B, bool)
    active[1] = False
    ok = (np.arange(C)[None, :] < length[:, None]) & active[:, None]
    return x, jnp.asarray(ok), length, active


@pytest.mark.parametrize("pad", [True, False], ids=["padded", "unpadded"])
@pytest.mark.parametrize("C", [1, 2, 64])
def test_the_split_and_the_rejoin_are_inverse(C, pad):
    B = 6
    x, ok, length, active = lanes(B, C)
    first, on, rest, further, prefilling = jax.jit(
        lambda x, ok: lm.split_lanes(x, ok, pad))(x, ok)
    assert first.shape == (B, 1, 3)
    np.testing.assert_array_equal(on, active & (length > 0))
    if C == 1:      # the decode program: nothing for a loop to turn over
        assert rest is None and further is None and prefilling is None
    else:
        M = C if pad else C - 1
        assert rest.shape == (B, M, 3) and further.shape == (B, M)
        np.testing.assert_array_equal(further[:, :C - 1], ok[:, 1:])
        assert not np.asarray(further[:, C - 1:]).any()      # the padding
        more = active & (length > 1)
        slots, count = prefilling
        assert int(count) == more.sum() and slots.dtype == jnp.int32
        np.testing.assert_array_equal(slots[:int(count)],
                                      np.flatnonzero(more))
        assert sorted(np.asarray(slots)) == list(range(B))
    assert bits(lm.join_lanes(first, rest, C)) == bits(x)
    # each slot's last valid lane; lane 0 of a slot with none
    want = x[np.arange(B), np.clip(length - 1, 0, C - 1)]
    assert bits(lm.last_valid_lane(x, jnp.asarray(length))) == bits(want)


@pytest.mark.parametrize("has", [
    [False] * 5, [True] * 5, [False, True, False, True, True],
    [True, False, False, False, False], [False, False, False, False, True]],
    ids=["none", "all", "some", "first", "last"])
def test_the_loop_visits_the_slots_that_have_lanes_once_each_in_order(has):
    """A toy body that marks what it visits: the turn it came at, how often
    it came, and the slot's rows rewritten through `slot_lanes` and
    `put_lanes`."""
    B, M, D = len(has), 4, 3
    has = np.asarray(has)
    rng = np.random.default_rng(1)
    rest = jnp.asarray(rng.standard_normal((B, M, D)), jnp.float32)
    ok = jnp.asarray(has[:, None] & (np.arange(M) < 2)[None, :])
    pos = jnp.arange(B, dtype=jnp.int32) * 10

    def program(rest, ok, pos):
        def slot(b, carry):
            rest, came, turn, turns = carry
            xb, okb, at = lm.slot_lanes(b, rest, ok, pos)
            assert xb.shape == (1, M, D) and okb.shape == (1, M)
            assert at.shape == (1,)
            xb = jnp.where(okb[:, :, None], xb + at[0], xb)
            return (lm.put_lanes(rest, xb, b), came.at[b].add(1),
                    turn.at[b].set(turns), turns + 1)

        none = jnp.zeros((B,), jnp.int32)
        return lm.each_slot(lm.slots_first(ok.any(axis=1)), slot,
                            (rest, none, none - 1, jnp.int32(0)))

    out, came, turn, turns = jax.jit(program)(rest, ok, pos)
    np.testing.assert_array_equal(came, has.astype(np.int32))
    assert int(turns) == has.sum()
    # in index order: the k-th slot that has lanes came at turn k
    np.testing.assert_array_equal(
        turn, np.where(has, np.cumsum(has) - 1, -1))
    want = np.asarray(rest).copy()
    for b in np.flatnonzero(has):
        want[b, :2] += 10 * b
    np.testing.assert_array_equal(out, want)
    for b in np.flatnonzero(~has):          # the others: bit for bit
        assert bits(out[b]) == bits(rest[b])
    if not has.any():                       # the carry comes back untouched
        assert bits(out) == bits(rest)


# lengths of a chunk of 8 in 5 slots (the call's rows are 13: 5 first lanes
# and 8 further): (lengths, active, the slots of each round)
STEPS = {
    "no-further-lane": ([1, 1, 0, 1, 1], [1] * 5, [[]]),
    "one-slot": ([1, 6, 1, 0, 1], [1] * 5, [[1]]),
    "three-slots-an-inactive-one-between": (
        [3, 8, 4, 1, 2], [1, 0, 1, 1, 1], [[0, 2, 4]]),
    "eight-further-lanes-fill-the-call": (
        [5, 1, 5, 1, 1], [1] * 5, [[0, 2]]),
    "nine-are-one-too-many": ([5, 1, 6, 1, 1], [1] * 5, [[0], [2]]),
    "every-slot-a-whole-chunk": (
        [8] * 5, [1] * 5, [[0], [1], [2], [3], [4]]),
    "two-together-then-each-alone": (
        [4, 0, 5, 8, 2], [1] * 5, [[0, 2], [3], [4]]),
}


@pytest.mark.parametrize("step", STEPS)
def test_packing_a_steps_lanes_and_putting_them_back_are_inverse(step):
    """`pack_lanes` a round: the first lanes, then the valid further lanes
    of the round's slots in slot order, each exactly once over the rounds,
    `ok` on those rows alone. `unpack_lanes` of the rows times two: every
    valid lane doubled once, no other lane of `rest` touched, the first
    lanes taken in round 0 only. `all_lanes` is that loop."""
    lengths, active, want_rounds = STEPS[step]
    B, C, D = 5, 8, 3
    N = lm.lanes_a_dispatch(B, C)
    assert N == 13
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((B, C, D)), jnp.float32)
    ok = jnp.asarray((np.arange(C)[None, :] < np.asarray(lengths)[:, None])
                     & np.asarray(active, bool)[:, None])

    def program(x, ok):
        first, on, rest, further, prefilling = lm.split_lanes(x, ok, True)
        rounds = lm.lane_rounds(further, prefilling)
        packed = [lm.pack_lanes(first, on, rest, rounds, jnp.int32(g))
                  for g in range(B + 1)]

        def block(rows, row_ok, g, seen):
            assert rows.shape == (1, N, D) and row_ok.shape == (1, N)
            return rows * 2, seen + row_ok.sum()

        doubled = lm.all_lanes(block, first, on, rest, further, rounds,
                               jnp.int32(0))
        return (first, on, rest, further, rounds["count"], packed, doubled,
                [lm.round_slots(rounds, jnp.int32(g)) for g in range(B + 1)])

    (first, on, rest, further, count, packed, (first2, rest2, seen),
     slots) = jax.jit(program)(x, ok)
    first, rest, further, on = (np.asarray(a) for a in (first, rest, further,
                                                        on))
    assert int(count) == len(want_rounds)
    for g, want in enumerate(want_rounds):
        indices, lo, hi = slots[g]
        assert list(np.asarray(indices)[int(lo):int(hi)]) == want
        rows, row_ok = (np.asarray(a)[0] for a in packed[g])
        lanes_ = [rest[b, m] for b in want for m in range(C)
                  if further[b, m]]
        assert row_ok[B:].sum() == len(lanes_) <= N - B
        assert row_ok[B:B + len(lanes_)].all()
        np.testing.assert_array_equal(row_ok[:B], on & (g == 0))
        np.testing.assert_array_equal(rows[:B], first[:, 0])
        if lanes_:
            np.testing.assert_array_equal(rows[B:B + len(lanes_)],
                                          np.stack(lanes_))
    assert sorted(b for r in want_rounds for b in r) == \
        list(np.flatnonzero(further.any(axis=1)))
    assert int(seen) == on.sum() + further.sum()
    np.testing.assert_array_equal(first2, first * 2)
    np.testing.assert_array_equal(
        rest2, np.where(further[:, :, None], rest * 2, rest))


# ----------------------------------------------------- the two-piece product

def _wide(shape, seed):
    """Normal float32 values over many binades."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(shape)
            * np.exp2(rng.integers(-20, 20, shape))).astype(np.float32)


def test_two_pieces_leave_a_hundredth_of_one_pieces_error():
    x = _wide((64, 256), 0)
    w = jnp.asarray(np.random.default_rng(1).standard_normal((256, 32)),
                    jnp.bfloat16)
    exact = x.astype(np.float64) @ np.asarray(w.astype(jnp.float32),
                                              np.float64)
    one = jnp.dot(jnp.asarray(x).astype(jnp.bfloat16), w,
                  preferred_element_type=jnp.float32)
    two = lm.dot(jnp.asarray(x), w, jnp.bfloat16)
    assert two.dtype == jnp.float32 and two.shape == exact.shape

    def error(got):
        return np.abs(np.asarray(got, np.float64) - exact).max()

    assert error(one) > 0
    assert error(two) * 100 <= error(one)


@pytest.mark.parametrize("n,axis", [(2, 0), (2, 1), (3, -1)])
def test_the_pieces_are_the_dtypes_and_add_up(n, axis):
    """Two pieces hold 16 of a float32's significant bits; three hold all
    of a normal x (`ops/kda_update.py` takes them on the last axis)."""
    x = _wide((8, 16, 128), 2)
    got = jax.jit(lambda x: pieces(x, jnp.bfloat16, n, axis))(x)
    assert got.dtype == jnp.bfloat16
    shape = list(x.shape)
    shape.insert(axis % (x.ndim + 1), n)
    assert got.shape == tuple(shape)
    parts = np.moveaxis(np.asarray(got.astype(jnp.float32)), axis, 0)
    # the first piece is x's rounding, as a conversion gives it
    np.testing.assert_array_equal(
        parts[0], np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(
            jnp.float32)))
    total = parts[0]
    for part in parts[1:]:
        total = total + part           # exact in float32: disjoint bits
    if n == 3:
        np.testing.assert_array_equal(total, x)
    else:
        np.testing.assert_allclose(total, x, rtol=2.0 ** -15, atol=0)
        assert (total != x).any()


def test_a_float32_compute_dtype_is_one_product_at_full_precision():
    x, w = jnp.ones((4, 8), jnp.float32), jnp.ones((8, 2), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda x, w: lm.dot(x, w, jnp.float32))(x, w))
    assert text.count("dot_general") == 1 and "HIGHEST" in text
    assert "reduce_precision" not in text
    narrow = str(jax.make_jaxpr(lambda x, w: lm.dot(x, w, jnp.bfloat16))(
        x, w))
    assert narrow.count("dot_general") == 1 and "HIGHEST" not in narrow
    assert narrow.count("reduce_precision") == 1


# ------------------------------------------------ a layer at a time, stacked

def _toy_layer(key, l, kind: str):
    ks = jax.random.split(jax.random.fold_in(key, l), 2)
    if kind == "wide":
        return {"w": lm.normal(ks[0], (8, 24), 0.02, jnp.bfloat16),
                "norm": lm.ones(8),
                "held": lm.normal(ks[1], (3, 8, 4), 0.02, jnp.bfloat16)}
    return {"w": lm.normal(ks[0], (5,), 0.3, jnp.float32)}


@pytest.mark.parametrize("kind", ["wide", "narrow"])
def test_a_layer_made_alone_is_the_layer_in_the_stack(kind):
    key = jax.random.key(5)
    program = lm.layer_program(_toy_layer, kind)
    assert program is lm.layer_program(_toy_layer, kind)     # one a kind
    layers = [4, 0, 9]
    stack = lm.stack_layers(lambda i: program(key, jnp.int32(layers[i])),
                            len(layers))
    for i, l in enumerate(layers):
        alone = program(key, jnp.int32(l))
        for path, leaf in jax.tree_util.tree_leaves_with_path(alone):
            there = stack
            for step in path:
                there = there[step.key]
            assert there.shape == (len(layers),) + leaf.shape
            assert there.dtype == leaf.dtype
            assert bits(there[i]) == bits(leaf), (kind, l, path)
    assert bits(stack["w"][0]) != bits(stack["w"][1])


def test_a_slice_tied_to_a_loops_turn_is_the_slice():
    """`layer_weights(.., turn=b)` in a body of `each_slot`: the entry the
    index names, whatever the turn."""
    stack = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 3, 2)}

    def program(i):
        def slot(b, seen):
            return seen.at[b].set(lm.layer_weights(stack, i, turn=b)["w"])

        return lm.each_slot(lm.slots_first(jnp.array([True, False, True])),
                            slot, jnp.zeros((3, 3, 2), jnp.float32))

    seen = jax.jit(program)(jnp.int32(2))
    assert bits(lm.layer_weights(stack, 2)["w"]) == bits(stack["w"][2])
    assert bits(seen[0]) == bits(seen[2]) == bits(stack["w"][2])
    assert not np.asarray(seen[1]).any()


def test_a_part_with_an_axis_of_its_own_lies_end_to_end():
    """Kimi's held experts: [E', ...] a layer in a stack [layers x E',
    ...]."""
    key = jax.random.key(6)
    program = lm.layer_program(_toy_layer, "wide")
    like = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
    stack = lm.empty_stack(like, 2 * 3)
    assert stack.shape == (6, 8, 4) and not np.asarray(
        stack.astype(jnp.float32)).any()
    for i in range(2):
        stack = lm.put_layer(stack, program(key, jnp.int32(i))["held"],
                             jnp.int32(i))
    for i in range(2):
        assert bits(stack[3 * i:3 * i + 3]) == bits(
            program(key, jnp.int32(i))["held"])


# ------------------------------------------------------ the short convolution

@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no-bias"])
def test_the_one_lane_form_lane_by_lane_is_the_chunk_form(bias):
    B, M, F, K = 4, 7, 6, 4
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((B, M, F)), jnp.float32)
    taps = jnp.asarray(rng.uniform(-.5, .5, (K, F)), jnp.float32)
    b = jnp.asarray(rng.uniform(-.5, .5, (F,)), jnp.float32) if bias else None
    window = jnp.asarray(rng.standard_normal((B, (K - 1) * F)), jnp.float32)
    length = np.array([M, 3, 0, 1])
    ok = jnp.asarray(np.arange(M)[None, :] < length[:, None])
    out, left = lm.short_conv(x, taps, window, ok, b)
    assert out.shape == (B, M, F) and left.shape == window.shape

    outs, w = [], window
    for i in range(M):
        o, w = lm.short_conv(x[:, i:i + 1], taps, w, ok[:, i:i + 1], b)
        assert o.shape == (B, 1, F)
        outs.append(o)
    by_lane = jnp.concatenate(outs, axis=1)
    for s in range(B):              # the valid lanes; the others are garbage
        np.testing.assert_allclose(out[s, :length[s]],
                                   by_lane[s, :length[s]], rtol=1e-6,
                                   atol=1e-6)
    np.testing.assert_array_equal(left, w)
    # a slot with no valid lane keeps its window bit for bit, both forms
    assert bits(left[2]) == bits(window[2]) == bits(w[2])
    # the definition: silu(bias + sum_k taps[k] * input K-1-k back)
    ext = np.concatenate([np.asarray(window).reshape(B, K - 1, F),
                          np.asarray(x)], axis=1)
    want = sum(np.asarray(taps)[k] * ext[:, k:k + M] for k in range(K))
    want = want + (np.asarray(b) if bias else 0.0)
    np.testing.assert_allclose(out, want / (1 + np.exp(-want)), rtol=1e-5,
                               atol=1e-6)
    # and the window left behind: the K - 1 inputs ending at the last valid
    np.testing.assert_array_equal(
        left[1], ext[1, length[1]:length[1] + K - 1].reshape(-1))


# ------------------------------------------------------------- what it costs

def test_importing_the_models_every_process_imports_brings_no_kernel_in():
    """`ray_tpu.ops`' `__init__` imports Pallas, a second of a process's
    start: `lm` (which gpt2 and llama import) takes `ops.pieces` when `dot`
    is called, not when it is imported."""
    code = ("import sys, ray_tpu.models.gpt2, ray_tpu.models.llama, "
            "ray_tpu.models.lm; print('ray_tpu.ops' in sys.modules)")
    run = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert run.stdout.strip() == "False", run.stderr[-1000:]
