"""In-process device collective group: eager collectives over a jax Mesh.

This is the TPU-native replacement for the reference's NCCL group
(`python/ray/util/collective/collective_group/nccl_collective_group.py:128`):
one process drives N local chips (ranks = devices), and each collective is a
jit-compiled shard_map program whose data plane is XLA collectives riding ICI.
There are no communicator handles or streams to manage — XLA owns scheduling.

The primary use is API parity for eager multi-device code (the reference's
`allreduce_multigpu` shape: one tensor per local device). High-performance
training should instead express parallelism as shardings inside one pjit
program (ray_tpu.parallel) so collectives fuse with compute; this group is
for the cases Ray users reach for ray.util.collective today.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.util.collective.types import ReduceOp

_AXIS = "ranks"


class XlaCollectiveGroup:
    backend_name = "xla"

    def __init__(self, devices: Optional[Sequence] = None,
                 group_name: str = "default"):
        self.devices = list(devices) if devices is not None else jax.devices()
        self.group_name = group_name
        self.world_size = len(self.devices)
        self.mesh = Mesh(np.array(self.devices), (_AXIS,))
        self._sharding = NamedSharding(self.mesh, P(_AXIS))

    # --------------------------------------------------------------- helpers
    def _stack(self, tensors: Sequence) -> jax.Array:
        """One tensor per rank -> global array sharded over the rank axis."""
        if len(tensors) != self.world_size:
            raise ValueError(
                f"need {self.world_size} tensors (one per device), got "
                f"{len(tensors)}")
        shards = [
            jax.device_put(jnp.expand_dims(jnp.asarray(t), 0), d)
            for t, d in zip(tensors, self.devices)
        ]
        shape = (self.world_size, *shards[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, self._sharding, shards)

    @staticmethod
    def _unstack(x: jax.Array) -> List[jax.Array]:
        shards = sorted(x.addressable_shards, key=lambda s: s.index[0].start)
        return [s.data[0] for s in shards]

    @functools.lru_cache(maxsize=None)
    def _allreduce_fn(self, op: ReduceOp):
        if op is ReduceOp.SUM:
            body = lambda x: jax.lax.psum(x, _AXIS)
        elif op is ReduceOp.MAX:
            body = lambda x: jax.lax.pmax(x, _AXIS)
        elif op is ReduceOp.MIN:
            body = lambda x: jax.lax.pmin(x, _AXIS)
        else:
            # PRODUCT: XLA has no pprod primitive — all-gather the factors
            # and multiply. The gather materializes a [world, ...]
            # intermediate, so it runs CHUNKED (32 MiB gather cap via
            # hierarchy.gathered_reduce) instead of asking for
            # world x leaf bytes on large leaves.
            from ray_tpu.util.collective.hierarchy import gathered_reduce

            def body(x):
                return jnp.expand_dims(gathered_reduce(
                    x[0], _AXIS, lambda g: g.prod(axis=0)), 0)

        return jax.jit(shard_map(body, mesh=self.mesh, in_specs=P(_AXIS),
                                 out_specs=P(_AXIS)))

    @functools.cached_property
    def _reducescatter_fn(self):
        # per-shard block is [1, world, ...]; scatter over the contribution
        # axis so rank r keeps the reduced row r, then restore the rank axis
        return jax.jit(shard_map(
            lambda x: jnp.expand_dims(
                jax.lax.psum_scatter(x[0], _AXIS, tiled=False), 0),
            mesh=self.mesh, in_specs=P(_AXIS), out_specs=P(_AXIS)))

    @functools.cached_property
    def _allgather_fn(self):
        return jax.jit(shard_map(
            lambda x: jax.lax.all_gather(x[0], _AXIS),
            mesh=self.mesh, in_specs=P(_AXIS), out_specs=P(),
            check_vma=False))

    @functools.lru_cache(maxsize=None)
    def _ppermute_fn(self, perm: tuple):
        return jax.jit(shard_map(
            lambda x: jax.lax.ppermute(x, _AXIS, perm=list(perm)),
            mesh=self.mesh, in_specs=P(_AXIS), out_specs=P(_AXIS)))

    # ------------------------------------------------------------ collectives
    def allreduce(self, tensors: Sequence, op: ReduceOp = ReduceOp.SUM):
        out = self._allreduce_fn(op)(self._stack(tensors))
        return self._unstack(out)

    def reduce(self, tensors: Sequence, dst_rank: int = 0,
               op: ReduceOp = ReduceOp.SUM):
        full = self.allreduce(tensors, op)
        return [full[i] if i == dst_rank else tensors[i]
                for i in range(self.world_size)]

    def broadcast(self, tensors: Sequence, src_rank: int = 0):
        perm = tuple((src_rank, d) for d in range(self.world_size))
        # one-to-all: gather is simplest and XLA lowers it to an ICI broadcast
        x = self._stack(tensors)
        full = self._allgather_fn(x)  # replicated [world, ...]
        src = full[src_rank]
        return [jax.device_put(src, d) for d in self.devices]

    def allgather(self, tensors: Sequence) -> List[List[jax.Array]]:
        full = self._allgather_fn(self._stack(tensors))
        return [[jax.device_put(full[r], d) for r in range(self.world_size)]
                for d in self.devices]

    def reducescatter(self, tensors: Sequence, op: ReduceOp = ReduceOp.SUM):
        """Each rank contributes [world, ...]; rank r receives reduced row r."""
        if op is not ReduceOp.SUM:
            red = self.allreduce([jnp.asarray(t) for t in tensors], op)
            return [red[r][r] for r in range(self.world_size)]
        stacked = self._stack(tensors)  # [world, world, ...]
        out = self._reducescatter_fn(stacked)
        return [s.data[0] for s in sorted(out.addressable_shards,
                                          key=lambda s: s.index[0].start)]

    def barrier(self):
        jax.block_until_ready(
            self.allreduce([jnp.zeros(()) for _ in self.devices]))

    def send_recv(self, tensors: Sequence, pairs: Sequence[tuple]):
        """ppermute: pairs is a list of (src_rank, dst_rank)."""
        out = self._ppermute_fn(tuple(pairs))(self._stack(tensors))
        return self._unstack(out)

    def destroy(self):
        self._allreduce_fn.cache_clear()
        self._ppermute_fn.cache_clear()
