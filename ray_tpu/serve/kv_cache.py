"""Paged KV-cache block pool with prompt-prefix reuse.

Behavioral parity with the reference's vLLM-side paged KV + prefix
caching surfaces (`python/ray/llm/_internal/serve/request_router/
prefix_aware/prefix_aware_router.py:39` routes on them; vLLM owns the
block table): KV state is stored in fixed-size token blocks addressed by
a rolling content hash of the prompt prefix, so requests sharing a
prefix skip prefill for the cached span and shared prefixes are stored
ONCE.

TPU-first shape choice: the pool is a dense jax array
`[n_layer, n_blocks, n_head, block_size, head_dim]` and reuse happens by
block-granular device-to-device copies into the decode engine's dense
per-slot cache (XLA-friendly static shapes; dynamic_update_slice on
block boundaries). In-kernel gather-paging is a Pallas follow-up; the
bookkeeping, hashing, eviction, and dedup semantics here are the real
thing.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple


def _chain_hash(prev: bytes, token_block: Tuple[int, ...]) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(prev)
    h.update(repr(token_block).encode())
    return h.digest()


def chain_hashes(ids: List[int], block_size: int) -> List[Tuple[bytes, int]]:
    """Rolling content hashes of every FULL block boundary of a prompt:
    [(hash_of_blocks_1..k, k*block_size), ...]. This is THE content
    address of a prefix — the same function keys the local block table,
    the cluster prefix store, and the routing residency hints, so a hash
    computed anywhere matches a prefix computed anywhere else."""
    out: List[Tuple[bytes, int]] = []
    h = b"root"
    for i in range(0, len(ids) - len(ids) % block_size, block_size):
        h = _chain_hash(h, tuple(ids[i:i + block_size]))
        out.append((h, i + block_size))
    return out


class PagedKVCache:
    """Host-side block table + device-side block pool.

    match_prefix(ids)  -> (n_cached_tokens, [block ids]) — longest chain
                          of full blocks whose content hashes are pooled.
    store_prefix(...)  -> copy a finished prompt's full blocks from a
                          slot's dense cache into the pool (dedup'd).
    copy_into_slot(...)-> materialize matched blocks into a slot cache.
    """

    def __init__(self, n_layer: int, n_head: int, head_dim: int,
                 num_blocks: int = 64, block_size: int = 16,
                 dtype=None):
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.block_size = block_size
        self.num_blocks = num_blocks
        shape = (n_layer, num_blocks, n_head, block_size, head_dim)
        dtype = dtype or jnp.float32
        self.pool_k = jnp.zeros(shape, dtype)
        self.pool_v = jnp.zeros(shape, dtype)
        self._free: List[int] = list(range(num_blocks))
        # chain hash -> block id, LRU order (least recent first)
        self._table: "OrderedDict[bytes, int]" = OrderedDict()
        self._hash_of_block: Dict[int, bytes] = {}
        # counters (tests + /stats)
        self.hits = 0
        self.tokens_reused = 0
        self.blocks_evicted = 0

        L, N, H, Bs, Dh = shape

        def _copy_out(pool, cache, slot, t0, blk):
            with jax.named_scope("prefix_pool"):
                data = jax.lax.dynamic_slice(
                    cache, (0, slot, 0, t0, 0), (L, 1, H, Bs, Dh))
                return jax.lax.dynamic_update_slice(
                    pool, data.reshape(L, 1, H, Bs, Dh), (0, blk, 0, 0, 0))

        def _copy_in(cache, pool, slot, t0, blk):
            with jax.named_scope("prefix_pool"):
                data = jax.lax.dynamic_slice(
                    pool, (0, blk, 0, 0, 0), (L, 1, H, Bs, Dh))
                return jax.lax.dynamic_update_slice(
                    cache, data, (0, slot, 0, t0, 0))

        self._copy_out = jax.jit(_copy_out, donate_argnums=(0,))
        self._copy_in = jax.jit(_copy_in, donate_argnums=(0,))

    # ------------------------------------------------------------ hashing
    def _chains(self, ids: List[int]):
        """Yield (chain_hash, token_block) for every FULL block of ids —
        delegates to `chain_hashes` so the local block table and the
        cluster prefix store can never disagree on a content address."""
        B = self.block_size
        for h, n in chain_hashes(ids, B):
            yield h, tuple(ids[n - B:n])

    # ------------------------------------------------------------- lookup
    def peek_prefix_len(self, ids: List[int]) -> int:
        """Cached-token count for `ids`' prefix WITHOUT touching the LRU
        order or the hit/miss counters — the disagg decode side uses this
        to decide whether fetching remote KV would gain anything before
        it commits to a prefill RPC."""
        n = 0
        for h, _blk in self._chains(ids):
            if h not in self._table:
                break
            n += self.block_size
        return n

    def recent_chain_hashes(self, n: int = 48) -> List[bytes]:
        """The most-recently-touched pooled chain hashes (LRU tail) —
        what this engine advertises as its resident-prefix routing hint."""
        return list(self._table)[-n:]

    def match_prefix(self, ids: List[int]) -> Tuple[int, List[int]]:
        blocks: List[int] = []
        for h, _blk in self._chains(ids):
            blk_id = self._table.get(h)
            if blk_id is None:
                break
            self._table.move_to_end(h)       # LRU touch
            blocks.append(blk_id)
        n = len(blocks) * self.block_size
        if blocks:
            self.hits += 1
            self.tokens_reused += n
        return n, blocks

    # ----------------------------------------------------------- eviction
    def _alloc(self) -> Optional[int]:
        if self._free:
            return self._free.pop()
        if not self._table:
            return None
        # evict the least-recently-matched chain entry. A child whose
        # parent is evicted can never match again (match walks from the
        # root) and ages out the same way.
        _h, blk = self._table.popitem(last=False)
        self._hash_of_block.pop(blk, None)
        self.blocks_evicted += 1
        return blk

    # -------------------------------------------------------------- store
    def store_prefix(self, ids: List[int], cache, slot: int) -> int:
        """Copy every full block of `ids` from `cache`'s dense slot lane
        into the pool (skipping chains already present). Returns the
        number of NEW blocks stored. `cache` is the engine's {"k","v"}."""
        stored = 0
        t0 = 0
        for h, _blk in self._chains(ids):
            if h not in self._table:
                blk = self._alloc()
                if blk is None:
                    break
                self.pool_k = self._copy_out(self.pool_k, cache["k"],
                                             slot, t0, blk)
                self.pool_v = self._copy_out(self.pool_v, cache["v"],
                                             slot, t0, blk)
                self._table[h] = blk
                self._hash_of_block[blk] = h
                stored += 1
            else:
                self._table.move_to_end(h)
            t0 += self.block_size
        return stored

    # --------------------------------------------------------------- load
    def copy_into_slot(self, cache, slot: int, blocks: List[int]):
        """Materialize matched pool blocks into cache slot lane starting
        at position 0; returns the updated cache dict."""
        k, v = cache["k"], cache["v"]
        t0 = 0
        for blk in blocks:
            k = self._copy_in(k, self.pool_k, slot, t0, blk)
            v = self._copy_in(v, self.pool_v, slot, t0, blk)
            t0 += self.block_size
        return {"k": k, "v": v}

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        return {"blocks_used": self.num_blocks - len(self._free),
                "prefix_hits": self.hits,
                "tokens_reused": self.tokens_reused,
                "blocks_evicted": self.blocks_evicted}


# ----------------------------------------------------- KV transfer (P/D)
# Reference: serve.llm KV-transfer connectors (`llm/_internal/serve/...
# nixl_connector.py`, lmcache) — ship computed prefix KV between
# replicas so a PREFILL fleet feeds a DECODE fleet. Here blocks are jax
# arrays, so the wire format is a plain numpy blob dict that can ride
# the object store / an ObjectRef between actors.

def export_prefix(kv: "PagedKVCache", ids) -> Optional[dict]:
    """Serialize the pooled KV blocks covering `ids`' prefix into a
    host-memory blob: {"ids", "k", "v"} with k/v [n_blocks, L, H, Bs, Dh].
    Returns None when nothing is pooled for this prompt.

    NOTE: blobs that serialize below the object store's inline threshold
    (core/store.py INLINE_THRESHOLD, 100 KiB) are NEVER published to the
    cluster prefix store — inline objects ride actor replies, not the
    sealed-object plane, so a directory binding could not serve a P2P
    pull. Tiny models / very short prefixes fall below it; the skip is
    counted as `prefix_store_inline_skipped_total` on /metrics."""
    import numpy as np

    n, blocks = kv.match_prefix(list(ids))
    if not blocks:
        return None
    k = np.stack([np.asarray(
        kv.jax.lax.dynamic_index_in_dim(kv.pool_k, b, 1, keepdims=False))
        for b in blocks])
    v = np.stack([np.asarray(
        kv.jax.lax.dynamic_index_in_dim(kv.pool_v, b, 1, keepdims=False))
        for b in blocks])
    return {"ids": list(ids[:n]), "k": k, "v": v,
            "block_size": kv.block_size}


def import_prefix(kv: "PagedKVCache", blob: dict) -> int:
    """Install an exported prefix into THIS pool (dedup'd against what's
    already cached). Returns the number of new blocks installed."""
    if not blob:
        return 0
    if blob["block_size"] != kv.block_size:
        raise ValueError(
            f"block_size mismatch: {blob['block_size']} != {kv.block_size}")
    jnp = kv.jnp
    installed = 0
    for i, (h, _blk) in enumerate(kv._chains(blob["ids"])):
        if h in kv._table:
            kv._table.move_to_end(h)
            continue
        blk = kv._alloc()
        if blk is None:
            break
        kb = jnp.asarray(blob["k"][i])[:, None]   # [L,1,H,Bs,Dh]
        vb = jnp.asarray(blob["v"][i])[:, None]
        kv.pool_k = kv.jax.lax.dynamic_update_slice(
            kv.pool_k, kb.astype(kv.pool_k.dtype), (0, blk, 0, 0, 0))
        kv.pool_v = kv.jax.lax.dynamic_update_slice(
            kv.pool_v, vb.astype(kv.pool_v.dtype), (0, blk, 0, 0, 0))
        kv._table[h] = blk
        kv._hash_of_block[blk] = h
        installed += 1
    return installed
