"""A prefix's blocks of rows move between pool and slot in one program call
a leaf (`serve/kv_cache.py`): the rows-only pools' geometries against a
numpy reference, to the bit."""

import numpy as np
import pytest

B, T, SIZE = 3, 32, 4               # slots, positions, a block's tokens
GEOMETRIES = {
    # GPT-2's keys and values by head
    "by_head": ({"k": (2, B, 2, T, 4), "v": (2, B, 2, T, 4)},
                {"k": 3, "v": 3}),
    # a latent cache: two leaves of different widths
    "latent": ({"latent": (2, B, T, 16), "k_rope": (2, B, T, 4)},
               {"latent": 2, "k_rope": 2}),
    # Keye's three leaves of two geometries
    "three_leaves": ({"k": (2, B, T, 8), "v": (2, B, T, 8),
                      "ik": (2, B, T, 2)}, {"k": 2, "v": 2, "ik": 2}),
}


def made(geometry, num_blocks=16, seed=0):
    """(pool, a cache of random rows, the leaves' token axes)"""
    import jax.numpy as jnp

    from ray_tpu.serve.kv_cache import PagedKVCache

    shapes, axes = GEOMETRIES[geometry]
    rng = np.random.default_rng(seed)
    cache = {n: jnp.asarray(rng.normal(size=s), jnp.float32)
             for n, s in shapes.items()}
    return (PagedKVCache.for_cache(cache, axes, num_blocks=num_blocks,
                                   block_size=SIZE), cache, axes)


def rows(leaf, axis, slot, t0, t1):
    """[slot]'s positions t0..t1 of a numpy leaf (a view)."""
    at = [slice(None)] * leaf.ndim
    at[1], at[axis] = slot, slice(t0, t1)
    return leaf[tuple(at)]


def pooled(kv, cache, axes, chains):
    """What the pool's arrays must hold: zeros but for each table entry's
    block, which holds the rows `chains` says ({hash: (slot, block of the
    slot)})."""
    want = {n: np.zeros(kv.pools[n].shape, np.float32) for n in axes}
    for h, blk in kv._table.items():
        slot, i = chains[h]
        for n, axis in axes.items():
            rows(want[n], axis, blk, 0, SIZE)[...] = rows(
                np.asarray(cache[n]), axis, slot, i * SIZE, (i + 1) * SIZE)
    return want


def landed(geometry, cache, axes, slot, hit):
    """(another cache of random rows, what it must hold once `cache`'s slot
    0 has come to its `slot` up to `hit`: every other row as it was)"""
    _, target, _ = made(geometry, seed=1)
    want = {name: np.asarray(leaf).copy() for name, leaf in target.items()}
    for name, axis in axes.items():
        rows(want[name], axis, slot, 0, hit)[...] = rows(
            np.asarray(cache[name]), axis, 0, 0, hit)
    return target, want


def hashes(ids):
    from ray_tpu.serve.kv_cache import chain_hashes

    return [h for h, _ in chain_hashes(ids, SIZE)]


@pytest.mark.parametrize("n", [1, 3, T // SIZE],
                         ids=["one_block", "a_few", "a_whole_slot"])
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_prefix_goes_to_the_pool_and_into_another_slot(geometry, n):
    kv, cache, axes = made(geometry)
    other = list(range(900, 900 + 2 * SIZE))
    assert kv.store_prefix(other, cache, 1) == 2
    ids = list(range(n * SIZE + 3))             # n whole blocks and a rest
    before = kv.stats()
    assert kv.store_prefix(ids, cache, 0) == n
    after = kv.stats()
    assert after["copy_out_calls"] - before["copy_out_calls"] == len(axes)
    assert after["copy_out_blocks"] - before["copy_out_blocks"] \
        == len(axes) * n
    chains = {h: (1, i) for i, h in enumerate(hashes(other))}
    chains.update({h: (0, i) for i, h in enumerate(hashes(ids))})
    for name, want in pooled(kv, cache, axes, chains).items():
        np.testing.assert_array_equal(np.asarray(kv.pools[name]), want)

    hit, blocks = kv.match_prefix(ids)
    assert hit == n * SIZE and len(blocks) == n
    target, want = landed(geometry, cache, axes, 2, hit)
    out = kv.copy_into_slot(target, 2, blocks)
    assert set(out) == set(target)
    for name in axes:       # the hit's rows, and every other row as it was
        np.testing.assert_array_equal(np.asarray(out[name]), want[name])
    assert kv.stats()["copy_in_calls"] == len(axes)
    assert kv.stats()["copy_in_blocks"] == len(axes) * n


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_store_that_runs_out_of_blocks_pools_what_it_got(geometry,
                                                           monkeypatch):
    kv, cache, axes = made(geometry)
    ids = list(range(5 * SIZE))
    handed, alloc = [], kv._alloc
    monkeypatch.setattr(kv, "_alloc", lambda: (
        None if len(handed) == 2 else handed.append(alloc()) or handed[-1]))
    assert kv.store_prefix(ids, cache, 0) == 2
    assert list(kv._table) == hashes(ids)[:2]
    assert kv.stats()["copy_out_blocks"] == len(axes) * 2
    chains = {h: (0, i) for i, h in enumerate(hashes(ids))}
    for name, want in pooled(kv, cache, axes, chains).items():
        np.testing.assert_array_equal(np.asarray(kv.pools[name]), want)
    assert kv.match_prefix(ids) == (2 * SIZE, handed)


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_prefix_longer_than_the_pool_leaves_its_last_blocks(geometry):
    """As the one-block programs left it: the chain's later blocks take the
    earlier ones' places, in the chain's order, within one call."""
    kv, cache, axes = made(geometry, num_blocks=3)
    ids = list(range(5 * SIZE))
    assert kv.store_prefix(ids, cache, 0) == 5
    assert kv.stats()["blocks_evicted"] == 2
    assert kv.stats()["copy_out_calls"] == len(axes)
    assert list(kv._table) == hashes(ids)[2:]
    chains = {h: (0, i) for i, h in enumerate(hashes(ids))}
    for name, want in pooled(kv, cache, axes, chains).items():
        np.testing.assert_array_equal(np.asarray(kv.pools[name]), want)
    assert kv.match_prefix(ids) == (0, [])


@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_a_pool_the_device_lays_out_otherwise_moves_a_block_a_call(
        geometry, monkeypatch):
    """What a TPU does to GPT-2's pool by head (the blocks along the lanes)
    cannot be had on the CPU: the reading of the arrays' layouts is stood
    in for, and the same rows land."""
    from ray_tpu.serve import kv_cache

    kv, cache, axes = made(geometry)
    monkeypatch.setattr(
        kv_cache, "_major_to_minor",
        lambda a: ("pool's" if a.shape[1] == kv.num_blocks else "cache's"))
    ids = list(range(3 * SIZE))
    assert kv.store_prefix(ids, cache, 0) == 3
    assert kv._apart == list(axes)
    assert kv.stats()["copy_out_calls"] == len(axes) * 3
    assert kv.stats()["copy_out_blocks"] == len(axes) * 3
    chains = {h: (0, i) for i, h in enumerate(hashes(ids))}
    for name, want in pooled(kv, cache, axes, chains).items():
        np.testing.assert_array_equal(np.asarray(kv.pools[name]), want)
    hit, blocks = kv.match_prefix(ids)
    target, want = landed(geometry, cache, axes, 1, hit)
    out = kv.copy_into_slot(target, 1, blocks)
    for name in axes:
        np.testing.assert_array_equal(np.asarray(out[name]), want[name])
    assert kv.stats()["copy_in_calls"] == len(axes) * 3
    assert kv.stats()["copy_in_blocks"] == len(axes) * 3
