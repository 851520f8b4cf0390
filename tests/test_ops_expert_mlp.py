"""`ops/expert_mlp.py`: the kernel, interpreted, against the three grouped
matmuls and the SwiGLU it stands for (`moe._three_products`); its plan; and
where `moe._experts` takes it."""

import functools
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe

op = importlib.import_module("ray_tpu.ops.expert_mlp")

D = 128
SMALL = (32, 16, 128)       # rows a block, rows a product, columns of F


def _operands(R, F, G, dtype, seed=0, D=D):
    ks = jax.random.split(jax.random.key(seed), 4)
    bf = jnp.bfloat16
    return (jax.random.normal(ks[0], (R, D), jnp.float32).astype(dtype),
            (jax.random.normal(ks[1], (G, D, F)) / D ** 0.5).astype(bf),
            (jax.random.normal(ks[2], (G, D, F)) / D ** 0.5).astype(bf),
            (jax.random.normal(ks[3], (G, F, D)) / F ** 0.5).astype(bf))


def _both(sizes, G, F=256, first=None, dtype=jnp.float32, tiles=SMALL, D=D):
    """(the kernel's rows, the three products') of the stack's groups, and
    the kernel's whole result."""
    sizes = np.asarray(sizes, np.int32)
    R = int(sizes.sum())
    args = (*_operands(R, F, G, dtype, D=D), jnp.asarray(sizes),
            None if first is None else jnp.int32(first))
    got = jax.jit(lambda *a: op.expert_mlp(*a, tiles=tiles, interpret=True))(
        *args)
    want = jax.jit(moe._three_products)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    ends = np.cumsum(sizes)
    lo = (ends - sizes)[first or 0]
    hi = ends[(first or 0) + G - 1]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return got[lo:hi], want[lo:hi], got


# two pieces a float32 row in both forms, float32 sums in another order
EXACT = dict(rtol=0, atol=2e-5)
# the three products round g, u and the result to bf16 between them
ONE_PIECE = dict(rtol=0, atol=4e-2)


@pytest.mark.parametrize("sizes,G,kwargs,tolerance", [
    ([5, 0, 7, 3], 4, {}, EXACT),
    ([5, 0, 0, 9, 2], 5, {}, EXACT),
    ([20, 30, 14], 3, {}, EXACT),
    ([3, 128, 5, 24], 4, {}, EXACT),
    ([3, 128, 5, 120], 4, {"tiles": None}, EXACT),
    ([5, 7, 3, 49], 3, {"first": 0}, EXACT),
    ([0, 0, 0, 0, 4, 0, 6, 1, 0, 0, 0, 0, 37], 12, {"first": 0}, EXACT),
    ([11, 0, 4, 0, 6, 1, 26], 4, {"first": 2}, EXACT),
    ([5, 0, 7, 3, 49], 5, {"dtype": jnp.bfloat16}, ONE_PIECE),
    ([20, 30, 14], 3, {"dtype": jnp.bfloat16}, ONE_PIECE),
    ([5, 0, 7, 20], 4, {"F": 320}, EXACT),
    ([5, 0, 7, 20], 4, {"F": 200}, EXACT),
    ([2, 1], 2, {}, EXACT),
    ([9, 0, 31, 63], 4, {"tiles": (64, 32, 256), "F": 512}, EXACT)],
    ids=["an-empty-group-between-two-touched", "two-empty-groups",
         "groups-that-cross-a-row-tile", "one-expert-with-128-rows",
         "128-rows-at-the-ops-own-tiles", "rows-past-the-stacks-end",
         "a-layer-of-the-stack-other-than-the-first",
         "a-shard-that-starts-at-group-2", "one-piece-rows",
         "one-piece-rows-across-tiles", "F-in-tiles-and-a-part-of-one",
         "F-no-multiple-of-the-lanes", "fewer-rows-than-a-sublane-tile",
         "two-tiles-of-F-and-of-rows"])
def test_the_kernel_is_the_three_products_and_the_swiglu(sizes, G, kwargs,
                                                         tolerance):
    got, want, _ = _both(sizes, G, **kwargs)
    np.testing.assert_allclose(got, want, **tolerance)
    assert np.abs(want).max() > 0.3


@pytest.mark.parametrize("sizes", [
    [3, 0, 250, 10, 120, 60], [0, 0, 0, 2, 300, 141], [200, 56, 1, 255, 0, 0]],
    ids=["an-empty-expert-and-rows-that-cross-a-tiles-end",
         "one-held-pair-in-443", "every-pair-held-and-a-tile-filled-exactly"])
def test_the_kernel_at_the_widest_row_is_the_three_products(sizes):
    """LongCat-Flash's experts, d = 6,144 and F = 2,048 in three matrices,
    at the op's own tiles there (256 rows a block, 64 a product, four column
    tiles of 512): a layer's 4 held experts of a stack, then the two ids past
    its end that `models/longcat.py` sends the absent experts' pairs and the
    zero-compute experts' to, which are given no row of any matrix."""
    D, F = 6144, 2048
    assert op._tiles(sum(sizes), D, F, 2) == (256, 64, 512)
    assert op._column_tile(D, F, 2) == 512 and F % 512 == 0
    got, want, whole = _both(sizes, 4, F=F, first=0, tiles=None, D=D)
    assert got.shape[0] == sum(sizes[:4])
    # float32 sums over 6,144 and 2,048 lanes in another order
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4)
    assert np.abs(want).max() > 1.0 or not sum(sizes[:4])
    # a tile that holds rows of the stack's experts comes back zero outside
    # them; the caller masks the rest (`moe._experts`)
    held = sum(sizes[:4])
    tile_end = -(-held // 256) * 256
    assert not whole[held:min(tile_end, len(whole))].any()


def test_rows_of_no_matrix_come_back_zero_beside_a_held_group_or_unread():
    """Groups past the stack's end: their rows in a row tile that a held
    group shares are zero; the tiles past it are never visited."""
    sizes = [5, 7, 100]                     # the stack holds the first two
    got, want, whole = _both(sizes, 2, first=0)
    np.testing.assert_allclose(got, want, **EXACT)
    assert not whole[12:32].any()
    *_, visits = op._plan(jnp.asarray(sizes, jnp.int32), jnp.int32(0), 2,
                          112, 32)
    assert int(visits) == 2


def test_the_plan_visits_each_touched_expert_once_a_row_tile_it_has_rows_in():
    sizes = jnp.asarray([3, 0, 40, 0, 21, 64], jnp.int32)
    (group, tile, lo, hi), visits = op._plan(sizes, None, 6, 128, 32)
    n = int(visits)
    assert n == 6 and group.shape == (6 + 4 - 1,)
    assert list(zip(*(np.asarray(a)[:n].tolist()
                      for a in (group, tile, lo, hi)))) == [
        (0, 0, 0, 3), (2, 0, 3, 32), (2, 1, 0, 11), (4, 1, 11, 32),
        (5, 2, 0, 32), (5, 3, 0, 32)]


def test_a_float32_row_goes_as_both_its_pieces_and_h_too():
    """Against the product of the float32 rows themselves (float64): the
    kernel's result is what two pieces leave of a row away (2^-17 of it),
    the one-piece product (rows rounded to bf16, `h` too) a hundred times
    as far: what a `bfloat16_state`-style degradation is caught by."""
    sizes = jnp.asarray([9, 0, 31, 24], jnp.int32)
    xs, wg, wu, wd = _operands(64, 256, 4, jnp.float32)
    run = jax.jit(lambda *a: op.expert_mlp(*a, tiles=SMALL, interpret=True))
    two = np.asarray(run(xs, wg, wu, wd, sizes), np.float64)
    one = np.asarray(run(xs.astype(jnp.bfloat16), wg, wu, wd, sizes),
                     np.float64)
    group = np.repeat(np.arange(4), np.asarray(sizes))
    x, g, u, d = (np.asarray(a, np.float64) for a in (xs, wg, wu, wd))
    a, b = (np.einsum("rd,rdf->rf", x, w[group]) for w in (g, u))
    exact = np.einsum("rf,rfd->rd", a / (1 + np.exp(-a)) * b, d[group])
    assert np.abs(two - exact).max() < 3e-5
    assert np.abs(one - exact).max() > 100 * np.abs(two - exact).max()


@pytest.mark.parametrize("rows,held,stack,backend,takes", [
    (jnp.float32, jnp.bfloat16, 512, "tpu", True),
    (jnp.float32, jnp.bfloat16, 512, "cpu", False),
    (jnp.bfloat16, jnp.bfloat16, 512, "tpu", False),
    (jnp.float32, jnp.float32, 512, "tpu", False),
    (jnp.float32, jnp.bfloat16, 1, "tpu", False)],
    ids=["float32-rows-few-a-group", "off-the-chip", "rows-of-one-piece",
         "matrices-as-wide-as-the-rows", "many-rows-a-group"])
def test_the_experts_take_the_kernel_where_their_arguments_say(
        monkeypatch, rows, held, stack, backend, takes):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    xs = jax.ShapeDtypeStruct((1024, 64), rows)
    w = jax.ShapeDtypeStruct((stack, 64, 32), held)
    assert moe._one_kernel(xs, w) is takes


def test_experts_through_the_kernel_are_experts_through_the_three_products(
        monkeypatch):
    """`moe._experts` as `models/kimi.py` calls it (float32 tokens, a bf16
    stack of every layer's held experts, the pairs of experts that are not
    held past its end) on the chip's branch, the kernel interpreted, against
    the branch the CPU takes."""
    N, K, held, layers, F = 24, 4, 4, 3, 256
    stack = held * layers
    ks = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(ks[0], (N, 1, D), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(ks[1], (N, 1, K)))
    local = jax.random.randint(ks[2], (N, 1, K), -4, 8)
    entry = jnp.where((local >= 0) & (local < held), 2 * held + local, stack)
    _, wg, wu, wd = _operands(1, F, stack, jnp.float32)
    cfg = types.SimpleNamespace(n_experts=stack + 1, experts_per_token=K,
                                dtype=jnp.float32)
    run = jax.jit(lambda: moe._experts(x, gates, entry, wg, wu, wd, cfg,
                                       first_expert=jnp.int32(0)))
    want = np.asarray(run())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "expert_mlp", functools.partial(
        op.expert_mlp, tiles=SMALL, interpret=True))
    got = np.asarray(jax.jit(lambda: moe._experts(
        x, gates, entry, wg, wu, wd, cfg, first_expert=jnp.int32(0)))())
    np.testing.assert_allclose(got, want, **EXACT)
    assert np.abs(want).max() > 0.1


# ------------------------------------------------- the two-matrix relu^2 form

def _both_without_a_gate(sizes, G, F=256, first=None, dtype=jnp.float32,
                         tiles=SMALL):
    """`_both` for an expert of two matrices (`wg` None): the kernel's rows
    of the stack's groups against the two grouped matmuls'."""
    sizes = np.asarray(sizes, np.int32)
    R = int(sizes.sum())
    xs, _, wu, wd = _operands(R, F, G, dtype)
    args = (xs, None, wu, wd, jnp.asarray(sizes),
            None if first is None else jnp.int32(first))
    got = jax.jit(lambda *a: op.expert_mlp(*a, tiles=tiles, interpret=True))(
        *args)
    want = jax.jit(moe._three_products)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    ends = np.cumsum(sizes)
    lo = (ends - sizes)[first or 0]
    hi = ends[(first or 0) + G - 1]
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return got[lo:hi], want[lo:hi], got


@pytest.mark.parametrize("sizes,G,kwargs,tolerance", [
    ([5, 0, 7, 3], 4, {}, EXACT),
    ([20, 30, 14], 3, {}, EXACT),
    ([3, 128, 5, 120], 4, {"tiles": None}, EXACT),
    ([0, 0, 0, 0, 4, 0, 6, 1, 0, 0, 0, 0, 37], 12, {"first": 0}, EXACT),
    ([11, 0, 4, 0, 6, 1, 26], 4, {"first": 2}, EXACT),
    ([5, 0, 7, 3, 49], 5, {"dtype": jnp.bfloat16}, ONE_PIECE),
    ([5, 0, 7, 20], 4, {"F": 200}, EXACT),
    ([9, 0, 31, 63], 4, {"tiles": (64, 32, 128), "F": 384}, EXACT)],
    ids=["an-empty-group-between-two-touched", "groups-that-cross-a-row-tile",
         "128-rows-at-the-ops-own-tiles",
         "a-layer-of-the-stack-other-than-the-first",
         "a-shard-that-starts-at-group-2", "one-piece-rows",
         "F-no-multiple-of-the-lanes", "three-tiles-of-F-that-divide-it"])
def test_the_kernel_without_a_gate_is_two_products_and_relu_squared(
        sizes, G, kwargs, tolerance):
    """`wg` None: relu(xs wu)^2 wd, Nemotron-H's experts, against
    `moe._three_products` in that form (two grouped matmuls), with empty
    experts, rows crossing a row tile's end, a shard of the stack and rows
    past its end."""
    got, want, _ = _both_without_a_gate(sizes, G, **kwargs)
    np.testing.assert_allclose(got, want, **tolerance)
    assert np.abs(want).max() > 0.3


def test_without_a_gate_the_result_is_relu_squared_in_float64():
    sizes = jnp.asarray([9, 0, 31, 24], jnp.int32)
    xs, _, wu, wd = _operands(64, 256, 4, jnp.float32)
    two = np.asarray(jax.jit(lambda *a: op.expert_mlp(
        *a, tiles=SMALL, interpret=True))(xs, None, wu, wd, sizes),
        np.float64)
    group = np.repeat(np.arange(4), np.asarray(sizes))
    x, u, d = (np.asarray(a, np.float64) for a in (xs, wu, wd))
    a = np.einsum("rd,rdf->rf", x, u[group])
    exact = np.einsum("rf,rfd->rd", np.maximum(a, 0) ** 2, d[group])
    assert np.abs(two - exact).max() < 1e-4
    assert np.abs(exact).max() > 1.0


def test_experts_without_a_gate_through_the_kernel_and_through_the_products(
        monkeypatch):
    """`moe._experts` as `models/nemotron.py` calls it (float32 latent rows,
    a bf16 stack of every layer's held experts' two matrices, the pairs of
    experts that are not held past its end) on the chip's branch, the kernel
    interpreted, against the branch the CPU takes."""
    N, K, held, layers, F, C = 24, 6, 4, 3, 384, 128
    stack = held * layers
    ks = jax.random.split(jax.random.key(3), 5)
    wu = (jax.random.normal(ks[0], (stack, C, F)) / C ** 0.5).astype(
        jnp.bfloat16)
    wd = (jax.random.normal(ks[1], (stack, F, C)) / F ** 0.5).astype(
        jnp.bfloat16)
    x = jax.random.normal(ks[2], (1, N, C), jnp.float32)
    gates = jax.nn.softmax(jax.random.normal(ks[3], (1, N, K)))
    # layer 1's entries (4..7) and, for the absent experts, the end (12)
    entry = jnp.where(jax.random.bernoulli(ks[4], 0.4, (1, N, K)),
                      held + jax.random.randint(ks[4], (1, N, K), 0, held),
                      stack)
    cfg = types.SimpleNamespace(n_experts=stack + 1, experts_per_token=K,
                                dtype=jnp.float32)
    run = functools.partial(moe._experts, x, gates, entry, None, wu, wd, cfg,
                            first_expert=jnp.int32(0))
    plain = jax.jit(run)()
    monkeypatch.setattr(moe, "_one_kernel", lambda xs, w: True)
    monkeypatch.setattr(moe, "expert_mlp", functools.partial(
        op.expert_mlp, interpret=True))
    kernel = jax.jit(run)()
    np.testing.assert_allclose(kernel, plain, rtol=0, atol=3e-5)
    assert float(jnp.abs(plain).max()) > 0.1
    # a token none of whose experts is held gets nothing
    none_held = np.asarray((entry == stack).all(axis=-1))[0]
    if none_held.any():
        assert not np.asarray(plain)[0][none_held].any()
