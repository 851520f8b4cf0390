"""Paged KV-cache block pool with prompt-prefix reuse.

Behavioral parity with the reference's vLLM-side paged KV + prefix
caching surfaces (`python/ray/llm/_internal/serve/request_router/
prefix_aware/prefix_aware_router.py:39` routes on them; vLLM owns the
block table): KV state is stored in fixed-size token blocks addressed by
a rolling content hash of the prompt prefix, so requests sharing a
prefix skip prefill for the cached span and shared prefixes are stored
ONCE.

TPU-first shape choice: the pool is a dense jax array a cache leaf
(GPT-2: `[n_layer, n_blocks, n_head, block_size, head_dim]` for keys and for
values; a latent cache: `[n_layer, n_blocks, block_size, width]` for each of
its leaves) and reuse happens by
block-granular device-to-device copies into the decode engine's dense
per-slot cache (XLA-friendly static shapes; dynamic_update_slice on
block boundaries). In-kernel gather-paging is a Pallas follow-up; the
bookkeeping, hashing, eviction, and dedup semantics here are the real
thing.

A family whose cache is recurrent state (`for_cache(..., state=...)`) has no
rows to keep by the block: what a prefix leaves behind is the state at its
end. The pool then holds snapshots, one entry a prefix: the slot's state
leaves as they stood when the prompt's last whole block had gone through,
keyed by that boundary's chain hash, in the same table under the same LRU.
`match_prefix` returns the longest boundary that has one, `copy_into_slot`
copies one entry over the slot's whole state.

A family whose cache holds both (rows a token in some layers, state a slot
in others) gets a pool of both kinds. An entry for a prefix is a snapshot of
the state at a block boundary plus the row blocks up to that boundary: one
hash table, one LRU, `num_blocks` blocks of rows and `num_snapshots`
snapshots. A hit is the longest boundary that has both; rows without a
snapshot at their end are no hit (`rows_without_snapshot_tokens` counts the
tokens prefilled again for it); a block is not evicted while a pooled
snapshot stands on it, and a snapshot that makes room goes before its blocks
do.

Whatever the pool's kind, a prefix's blocks of rows move between pool and
slot in one program call a leaf, whatever their number (a loop on the device
over the blocks a small int32 `plan` lists), and a snapshot in one call a
state leaf. A store or an admission makes its plan once, puts it on the
device once and hands it to every leaf's call.
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


def _major_to_minor(array) -> tuple:
    """The order in which the device holds `array`'s axes."""
    return array.format.layout.major_to_minor


class PagedKVCache:
    """Host-side block table + device-side block pool.

    match_prefix(ids)  -> (n_cached_tokens, [block ids]) — longest chain
                          of full blocks whose content hashes are pooled.
    store_prefix(...)  -> copy a finished prompt's full blocks from a
                          slot's dense cache into the pool (dedup'd).
    copy_into_slot(...)-> materialize matched blocks into a slot cache.

    The pool holds a block of whatever a token leaves in the model's
    cache: one array a leaf, the leaf's shape with the slots' axis (1)
    counting blocks and the tokens' axis a block long. `for_cache` builds
    it from a cache and the model's word on which axis counts tokens;
    the plain constructor is GPT-2's keys and values by head,
    {"k", "v"}: [n_layer, slots, n_head, T, head_dim]."""

    def __init__(self, n_layer: int, n_head: int, head_dim: int,
                 num_blocks: int = 64, block_size: int = 16,
                 dtype=None):
        import jax
        import jax.numpy as jnp

        leaf = jax.ShapeDtypeStruct((n_layer, 1, n_head, block_size,
                                     head_dim), dtype or jnp.float32)
        self._build({"k": leaf, "v": leaf}, {"k": 3, "v": 3},
                    num_blocks, block_size)

    @classmethod
    def for_cache(cls, cache: dict, token_axis: Dict[str, int],
                  num_blocks: int = 64, block_size: int = 16,
                  state: Tuple[str, ...] = (),
                  num_snapshots: Optional[int] = None) -> "PagedKVCache":
        """A pool for the leaves of `cache` that `token_axis` names (leaf
        -> the axis that counts tokens; axis 0 the layers, 1 the slots) and
        for those `state` names (a slot's recurrent state, no token axis:
        snapshots, taken at multiples of `block_size`). With both kinds,
        `num_blocks` counts the blocks of rows and `num_snapshots` the
        snapshots (by default one for every whole slot of rows the blocks
        hold); with state alone `num_blocks` counts the snapshots. Leaves
        neither names are not pooled."""
        self = cls.__new__(cls)
        if not (token_axis and state):
            num_snapshots = num_blocks if state else 0
        elif num_snapshots is None:
            name, axis = next(iter(token_axis.items()))
            num_snapshots = max(
                1, num_blocks * block_size // cache[name].shape[axis])
        self._build({name: cache[name]
                     for name in list(token_axis) + list(state)},
                    token_axis, num_blocks, block_size, num_snapshots)
        return self

    def _build(self, leaves: dict, token_axis: Dict[str, int],
               num_blocks: int, block_size: int,
               num_snapshots: int = 0) -> None:
        import jax
        import jax.numpy as jnp

        self.jax, self.jnp = jax, jnp
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.num_snapshots = num_snapshots
        # leaves with no token axis: an entry holds a snapshot of a slot
        self.snapshots = any(name not in token_axis for name in leaves)
        # both kinds: an entry is a snapshot and the row blocks under it
        self.both = self.snapshots and bool(token_axis)
        self.pools: Dict[str, "jax.Array"] = {}
        self._copiers: Dict[str, tuple] = {}
        # rows leaves whose pool the device lays out otherwise than the
        # cache's (`_laid_apart`: known at the first store or admission)
        self._apart: Optional[List[str]] = None
        # leaf -> (one block of one slot, axis, dtype); geometry -> programs
        self._geometry: Dict[str, tuple] = {}
        self._programs: Dict[tuple, tuple] = {}
        for name, leaf in leaves.items():
            axis = token_axis.get(name)
            block = list(leaf.shape)
            block[1] = 1
            if axis is not None:
                block[axis] = block_size
            self._geometry[name] = (tuple(block), axis,
                                    jnp.dtype(leaf.dtype).name)
            self._copiers[name] = self._programs_of(name)
            block[1] = num_snapshots if self.both and axis is None \
                else num_blocks
            self.pools[name] = jnp.zeros(tuple(block), leaf.dtype)
        self._rows = [n for n in leaves if n in token_axis]
        self._states = [n for n in leaves if n not in token_axis]
        # the first leaf's two programs, under the names they have had
        self._copy_out, self._copy_in = next(iter(self._copiers.values()))
        self._free: List[int] = list(range(num_blocks))
        # chain hash -> block id, LRU order (least recent first)
        self._table: "OrderedDict[bytes, int]" = OrderedDict()
        self._hash_of_block: Dict[int, bytes] = {}
        # a pool of both kinds: boundary hash -> snapshot id, the blocks
        # each snapshot stands on, and how many snapshots stand on a block
        self._free_snapshots: List[int] = list(range(num_snapshots))
        self._snapshot_at: Dict[bytes, int] = {}
        self._stands_on: Dict[bytes, List[int]] = {}
        self._pins: Dict[int, int] = {}
        # counters (tests + /stats)
        self.hits = 0
        self.tokens_reused = 0
        self.blocks_evicted = 0
        self.snapshots_evicted = 0
        self.rows_without_snapshot_tokens = 0
        # calls of the programs that move blocks of rows (one a leaf a
        # store or an admission) and the blocks they moved
        self.copy_in_calls = self.copy_in_blocks = 0
        self.copy_out_calls = self.copy_out_blocks = 0

    def _programs_of(self, name: str, loop: bool = True) -> tuple:
        """Leaf `name`'s (copy_out, copy_in): leaves of one geometry share a
        pair."""
        key = self._geometry[name] + (loop,)
        if key not in self._programs:
            self._programs[key] = self._copy_programs(*key[:2], loop)
        return self._programs[key]

    def _copy_programs(self, block: tuple, axis: Optional[int],
                       loop: bool = True) -> tuple:
        """(copy_out, copy_in) for leaves whose one block of one slot is
        `block` ([L, 1, ..., block_size at `axis`, ...]; with no `axis` a
        slot's whole leaf). Each takes what it writes into (donated), what
        it reads and a `plan` (`_plan` makes it). With an `axis` a loop over
        the plan's blocks of rows writes each into the carry in place
        (without `loop`, the plan's first block and no other); with none the
        plan's one entry moves."""
        jax = self.jax

        def where(second, t0):
            return tuple(second if i == 1 else t0 if i == axis else 0
                         for i in range(len(block)))

        def each(plan, move, carry):
            """`move(carry, slot, pool block, position in the slot)` for the
            plan's entry, or for each of its blocks of rows in turn."""
            slot = plan[0]
            if axis is None:
                return move(carry, slot, plan[1], 0)
            most = (plan.shape[0] - 3) // 2

            def one(i, carry):
                return move(carry, slot, plan[3 + i],
                            plan[3 + most + i] * block[axis])

            return jax.lax.fori_loop(0, plan[2], one, carry) if loop \
                else one(0, carry)

        def _copy_out(pool, cache, plan):
            def move(pool, slot, blk, t0):
                data = jax.lax.dynamic_slice(cache, where(slot, t0), block)
                return jax.lax.dynamic_update_slice(pool, data, where(blk, 0))

            with jax.named_scope("prefix_pool"):
                return each(plan, move, pool)

        def _copy_in(cache, pool, plan):
            def move(cache, slot, blk, t0):
                data = jax.lax.dynamic_slice(pool, where(blk, 0), block)
                return jax.lax.dynamic_update_slice(cache, data,
                                                    where(slot, t0))

            with jax.named_scope("prefix_pool"):
                return each(plan, move, cache)

        return (jax.jit(_copy_out, donate_argnums=(0,)),
                jax.jit(_copy_in, donate_argnums=(0,)))

    def _plan(self, cache, slot: int, entry: int = 0, rows=()):
        """What one store or one admission moves, as every leaf's program
        takes it, on the device once: int32 [slot, `entry` (the one entry of
        the leaves without a token axis), how many blocks of rows, each's
        pool block id, each's place among the slot's blocks], the two lists
        as long as a slot of `cache` has blocks. `rows`: (block id, place)
        pairs."""
        import numpy as np

        most = 0
        if self._rows:
            name = self._rows[0]
            most = cache[name].shape[self._geometry[name][1]] \
                // self.block_size
        plan = np.zeros((3 + 2 * most,), np.int32)
        plan[:3] = slot, entry, len(rows)
        if rows:
            plan[3:].reshape(2, most)[:, :len(rows)] = np.asarray(rows).T
        return self.jnp.asarray(plan)

    # GPT-2's two pools by name: the transfer blobs below are theirs
    @property
    def pool_k(self):
        return self.pools["k"]

    @pool_k.setter
    def pool_k(self, value):
        self.pools["k"] = value

    @property
    def pool_v(self):
        return self.pools["v"]

    @pool_v.setter
    def pool_v(self, value):
        self.pools["v"] = value

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
        if self.both:
            return self._longest_entry(chain_hashes(ids, self.block_size))[0]
        if self.snapshots:
            return max((n for h, n in chain_hashes(ids, self.block_size)
                        if h in self._table), default=0)
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

    def _longest_entry(self, chain) -> Tuple[int, int]:
        """(tokens up to the longest boundary whose rows are pooled from the
        root on and whose snapshot stands, tokens whose rows are pooled from
        the root on)."""
        rows = 0
        for h, n in chain:
            if h not in self._table:
                break
            rows = n
        hit = max((n for h, n in chain[:rows // self.block_size]
                   if h in self._snapshot_at), default=0)
        return hit, rows

    def pooled_to(self, ids: List[int]) -> tuple:
        """(`chain_hashes` of `ids`, the tokens they would be a hit for now,
        the tokens whose rows are pooled from the root on, with a snapshot
        at their end or without), for a pool of both kinds; nothing is
        touched or counted."""
        chain = chain_hashes(ids, self.block_size)
        return (chain, *self._longest_entry(chain))

    def match_prefix(self, ids: List[int]) -> Tuple[int, List[int]]:
        if self.both:
            chain = chain_hashes(ids, self.block_size)
            n, rows = self._longest_entry(chain)
            # rows without a snapshot at their end are no hit
            self.rows_without_snapshot_tokens += rows - n
            blocks = []
            for h, _ in chain[:n // self.block_size]:
                self._table.move_to_end(h)
                blocks.append(self._table[h])
            if blocks:
                self.hits += 1
                self.tokens_reused += n
            return n, blocks
        if self.snapshots:
            # the longest boundary with a snapshot: one entry, whatever
            # shorter ones exist
            for h, n in reversed(chain_hashes(ids, self.block_size)):
                if h in self._table:
                    self._table.move_to_end(h)
                    self.hits += 1
                    self.tokens_reused += n
                    return n, [self._table[h]]
            return 0, []
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
        # evict the least-recently-matched chain entry. A child whose
        # parent is evicted can never match again (match walks from the
        # root) and ages out the same way. A block under a pooled snapshot
        # stays: the snapshot would be gone with it
        for h, blk in self._table.items():
            if not self._pins.get(blk):
                del self._table[h]
                self._hash_of_block.pop(blk, None)
                self.blocks_evicted += 1
                return blk
        # every block stands under a snapshot: the least recently used
        # snapshot goes, and its blocks may follow
        snapshot = self._drop_snapshot()
        if snapshot is None:
            return None
        self._free_snapshots.append(snapshot)
        return self._alloc()

    def _drop_snapshot(self) -> Optional[int]:
        """Evict the least recently used snapshot; its id, or None."""
        for h in self._table:
            if h in self._snapshot_at:
                for blk in self._stands_on.pop(h):
                    self._pins[blk] -= 1
                self.snapshots_evicted += 1
                return self._snapshot_at.pop(h)
        return None

    # -------------------------------------------------------------- store
    def store_prefix(self, ids: List[int], cache, slot: int) -> int:
        """Copy every full block of `ids` from `cache`'s dense slot lane
        into the pool (skipping chains already present): the new blocks in
        one program call a leaf. Returns the number of NEW blocks stored.
        `cache` is the engine's dict of leaves.

        A pool of snapshots keeps the slot's state as it stands, under the
        hash of `ids`' last whole block: the caller calls when the slot has
        taken exactly those blocks and no token more."""
        chain = chain_hashes(ids, self.block_size)
        if self.both:
            return self._store_entry(chain, cache, slot)
        # a block of rows a hash, or the slot's state once, under the last
        hashes = [h for h, _ in chain]
        if self.snapshots:
            hashes = hashes[-1:]
        new = []
        for i, h in enumerate(hashes):
            if h in self._table:
                self._table.move_to_end(h)
                continue
            blk = self._alloc()
            if blk is None:
                break
            self._table[h] = blk
            self._hash_of_block[blk] = h
            new.append((blk, i))
        if new and self.snapshots:
            self._to_pool(cache, slot, entry=new[0][0])
        elif new:
            self._to_pool(cache, slot, rows=new)
        return len(new)

    def _to_pool(self, cache, slot: int, rows=(),
                 entry: Optional[int] = None) -> None:
        """The slot's blocks `rows` ((pool block, place in the slot) pairs)
        and, with an `entry`, its state as that entry."""
        for names, plan in self._calls(cache, slot, rows, entry or 0,
                                       entry is not None):
            for name in names:
                self.pools[name] = self._copiers[name][0](
                    self.pools[name], cache[name], plan)
                self.copy_out_calls += name in self._rows
        self.copy_out_blocks += len(self._rows) * len(rows)

    def _calls(self, cache, slot: int, rows, entry: int, states: bool):
        """The program calls of one store or one admission, as (leaves,
        their plan): every block of `rows` in one call a leaf behind one
        plan, and the state leaves' `entry` with them. A leaf laid apart
        (`_laid_apart`) takes a block a call, each plan made when its calls
        are, so that the device starts on the first block while the host
        makes the next."""
        apart = self._laid_apart(cache)
        together = [n for n in self._rows if rows and n not in apart] + (
            self._states if states else [])
        if together:
            yield together, self._plan(cache, slot, entry, rows)
        for pair in rows if apart else ():
            yield apart, self._plan(cache, slot, rows=[pair])

    def _store_entry(self, chain, cache, slot: int) -> int:
        """A pool of both kinds keeps the slot's rows up to `chain`'s last
        boundary and its state as it stands there (the caller calls when the
        slot has taken exactly those blocks): 1 when a new snapshot was
        stored, 0 when it stood already or nothing could make room."""
        if not chain or len(chain) > self.num_blocks:
            return 0            # nothing whole, or more than the pool holds
        if chain[-1][0] in self._snapshot_at:
            for h, _ in chain:
                self._table.move_to_end(h)
            return 0
        held, new = [], []
        for i, (h, _) in enumerate(chain):
            blk = self._table.get(h)
            if blk is None:
                blk = self._alloc()
                if blk is None:
                    break
                self._table[h] = blk
                self._hash_of_block[blk] = h
                new.append((blk, i))
            else:
                self._table.move_to_end(h)
            # held against this call's own evictions
            self._pins[blk] = self._pins.get(blk, 0) + 1
            held.append(blk)
        snapshot = None
        if len(held) == len(chain):
            snapshot = (self._free_snapshots.pop() if self._free_snapshots
                        else self._drop_snapshot())
        if new or snapshot is not None:
            self._to_pool(cache, slot, new, snapshot)
        if snapshot is None:        # rows without a snapshot: no entry
            for blk in held:
                self._pins[blk] -= 1
            return 0
        self._snapshot_at[chain[-1][0]] = snapshot
        self._stands_on[chain[-1][0]] = held
        return 1

    # --------------------------------------------------------------- load
    def copy_into_slot(self, cache, slot: int, blocks: List[int]):
        """Materialize what `match_prefix` found into cache slot lane: the
        blocks of rows from position 0 on, in one program call a leaf
        whatever their number, and the entry's snapshot over the slot's
        whole state (a pool of snapshots alone: `blocks` is that one
        entry); returns the updated cache dict."""
        cache = dict(cache)
        rows, entry = [(b, i) for i, b in enumerate(blocks)], 0
        if self.both:
            # the entry whose last block this is
            entry = self._snapshot_at[self._hash_of_block[blocks[-1]]]
        elif self.snapshots:
            rows, entry = [], blocks[0]
        for names, plan in self._calls(cache, slot, rows, entry, True):
            for name in names:
                cache[name] = self._copiers[name][1](
                    cache[name], self.pools[name], plan)
                self.copy_in_calls += name in self._rows
        self.copy_in_blocks += len(self._rows) * len(rows)
        return cache

    def _laid_apart(self, cache) -> List[str]:
        """The rows leaves whose pool the device lays out otherwise than the
        leaf of the cache (a TPU puts the blocks of GPT-2's pool by head
        along the lanes, 16 x 64 being less than a tile: ROADMAP S6). A
        loop that reads such a pool is compiled with a copy of the whole
        pool in the cache's layout, eight times its bytes, and one that
        writes it gains nothing on the device, so these leaves move a block
        a call, by the loop's body alone. Asked of the arrays once, at the
        first store or admission."""
        if self._apart is None:
            self._apart = [n for n in self._rows
                           if _major_to_minor(self.pools[n])
                           != _major_to_minor(cache[n])]
            for name in self._apart:
                self._copiers[name] = self._programs_of(name, loop=False)
        return self._apart

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        out = {"blocks_used": self.num_blocks - len(self._free),
               "prefix_hits": self.hits,
               "tokens_reused": self.tokens_reused,
               "blocks_evicted": self.blocks_evicted,
               "copy_in_calls": self.copy_in_calls,
               "copy_in_blocks": self.copy_in_blocks,
               "copy_out_calls": self.copy_out_calls,
               "copy_out_blocks": self.copy_out_blocks}
        if self.both:
            out.update(
                snapshots_used=self.num_snapshots - len(self._free_snapshots),
                snapshots_evicted=self.snapshots_evicted,
                rows_without_snapshot_tokens=(
                    self.rows_without_snapshot_tokens))
        return out


# ----------------------------------------------------- KV transfer (P/D)
# Reference: serve.llm KV-transfer connectors (`llm/_internal/serve/...
# nixl_connector.py`, lmcache) — ship computed prefix KV between
# replicas so a PREFILL fleet feeds a DECODE fleet. Here blocks are jax
# arrays, so the wire format is a plain numpy blob dict that can ride
# the object store / an ObjectRef between actors.

def _require_rows(kv: "PagedKVCache", what: str) -> None:
    if kv.snapshots:
        raise NotImplementedError(
            f"{what} ships blocks of keys and values; a pool that holds "
            f"state snapshots (the brumby and granite families) has no "
            f"wire format yet")


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

    _require_rows(kv, "export_prefix")
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
    _require_rows(kv, "import_prefix")
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
