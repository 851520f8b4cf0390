"""Cross-process device collective group: `jax.distributed` sub-cluster.

Parity target: the reference's NCCL collective group
(`python/ray/util/collective/collective_group/nccl_collective_group.py:128`)
— N actor PROCESSES form a gang whose collectives run on the device plane.
TPU-native shape: rendezvous through the head KV (the reference stores the
NCCL uniqueId in a named actor), then `jax.distributed.initialize` welds
the member processes into one JAX cluster; a global 1-device-per-process
mesh is built and collectives execute as `shard_map` programs over it, so
the data plane is XLA's ICI/DCN collectives — not host relays.

p2p send/recv run the device plane too: the two peers build a 2-device
pair mesh (their devices only) and execute one `lax.ppermute` program —
the XLA CollectivePermute equivalent of NCCL Send/Recv
(`collective.py:584-705`). Broadcast is a one-to-many ppermute on the full
mesh (src transmits world-1 copies — a real broadcast, not the 2x-traffic
zeros-allreduce). Reduce keeps the psum lowering: on a ring, reduce and
allreduce move the same bytes, and XLA exposes no pairwise-accumulate
primitive that would beat it.

CI story (SURVEY §4.2 pattern 3): on CPU the same code runs with the gloo
CPU-collectives implementation and `--xla_force_host_platform_device_count=1`
per process — the fake-backend pattern the reference uses for NCCL tests.
"""

from __future__ import annotations

import os
import pickle
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
from jax import shard_map

from ray_tpu.util.collective.types import ReduceOp

_COORD_NS = "collective_xmh"
_MEMBER_NS = "collective_xmh_members"
_POLL_S = 0.05


def _reduce_op(op: ReduceOp):
    from jax import lax

    def pprod(a, ax):
        # XLA has no pprod primitive: all-gather the factors and multiply.
        # The gather materializes a [world, ...] intermediate, so it runs
        # CHUNKED (hierarchy.gathered_reduce): peak extra memory is the
        # 32 MiB cap + one chunk's product, not world x leaf bytes —
        # a naive gather of a 1 GiB leaf at world=64 would ask for 64 GiB.
        from ray_tpu.util.collective.hierarchy import gathered_reduce

        return gathered_reduce(a, ax, lambda g: g.prod(axis=0))

    return {ReduceOp.SUM: lambda a, ax: lax.psum(a, ax),
            ReduceOp.MAX: lambda a, ax: lax.pmax(a, ax),
            ReduceOp.MIN: lambda a, ax: lax.pmin(a, ax),
            ReduceOp.PRODUCT: pprod}[op]


def _rs_program(op: ReduceOp):
    """Per-shard reduce-scatter body over mesh axis "p"; factored out so
    tests can lower it on a local mesh and assert the HLO really is a
    reduce-scatter, not a full allreduce."""
    from jax import lax

    if op is ReduceOp.SUM:
        def fn(a):  # a: [1, world, ...] local block
            return lax.psum_scatter(a[0], "p", scatter_dimension=0,
                                    tiled=True)
        return fn
    red = _reduce_op(op)

    def fn(a):
        # non-sum ops have no scatter primitive in XLA: reduce, then
        # slice inside the program (the compiler sees the slice)
        full = red(a[0], "p")               # [world, ...]
        idx = lax.axis_index("p")
        return lax.dynamic_index_in_dim(full, idx, 0, keepdims=True)
    return fn


class XlaMultihostGroup:
    """One member process of a cross-process device collective gang."""

    backend_name = "xla-multihost"

    def __init__(self, client, group_name: str, world_size: int, rank: int,
                 timeout_s: float = 60.0):
        if not (0 <= rank < world_size):
            raise ValueError(f"rank {rank} out of range for world {world_size}")
        self._client = client
        self.group_name = group_name
        self.world_size = world_size
        self.rank = rank
        # collective launches are per-process serialized (NCCL-style: two
        # threads interleaving programs on one group would mismatch the
        # SPMD program order across members)
        import threading

        self._op_lock = threading.Lock()
        self._init_jax_cluster(timeout_s)
        self._publish_membership()

    # ------------------------------------------------------------ rendezvous
    def _coord_key(self) -> bytes:
        return f"{self.group_name}:coordinator".encode()

    def _init_jax_cluster(self, timeout_s: float) -> None:
        import jax

        # env check ONLY — jax.default_backend() would initialize XLA,
        # which must not happen before jax.distributed.initialize
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # the reference's mock-NCCL pattern: same code path, CPU gloo
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if self.rank == 0:
            # a leftover key from a crashed same-named group is deleted
            # here, BEFORE members can read it — liveness by generation,
            # not by comparing wall clocks across hosts (clock skew made
            # fresh keys look stale). The key itself is deleted again once
            # everyone has joined and on destroy().
            try:
                self._client.kv_del(_COORD_NS, self._coord_key())
            except Exception:
                pass
            addr = self._start_coordinator(timeout_s)
        else:
            deadline = time.monotonic() + timeout_s
            addr = None
            while True:
                blob = self._client.kv_get(_COORD_NS, self._coord_key())
                if blob:
                    cand = pickle.loads(blob)["addr"]
                    # liveness probe: a leftover key from a crashed group
                    # (read before rank 0's delete) or an abandoned
                    # bind-retry port refuses the connection — keep
                    # polling until a LIVE coordinator answers, instead of
                    # hanging initialize against a dead address
                    if self._probe(cand):
                        addr = cand
                        break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"group {self.group_name}: no live coordinator "
                        f"within {timeout_s}s")
                time.sleep(_POLL_S)
            self._ensure_jax_distributed(addr)
        if self.rank == 0:
            # initialize() returns once every process has joined — the
            # rendezvous key has served its purpose
            try:
                self._client.kv_del(_COORD_NS, self._coord_key())
            except Exception:
                pass
        import jax
        from jax.sharding import Mesh

        from ray_tpu.util.collective.hierarchy import (Topology,
                                                       device_rows_by_process)

        rows = device_rows_by_process(jax.devices())
        if len(rows) != self.world_size:
            raise RuntimeError(
                f"jax cluster has {len(rows)} processes, expected "
                f"{self.world_size}")
        devs = [row[0] for row in rows]
        self.mesh = Mesh(np.array(devs), ("p",))
        self._rank_dev = devs
        self._local_dev = rows[jax.process_index()][0]
        self._pair_meshes: Dict[Tuple[int, int], Any] = {}
        # hosts x local-devices hierarchy: every member process is one
        # "host" row; its local virtual/physical devices are the intra
        # (fast-fabric) axis. Asymmetric device counts truncate to the
        # common minimum so the 2D mesh stays rectangular.
        n_local = min(len(r) for r in rows)
        self.topology = Topology(inter=self.world_size, intra=n_local)
        self._local_devs = rows[jax.process_index()][:n_local]
        self._hier_mesh = Mesh(
            np.array([r[:n_local] for r in rows]),
            (self.topology.inter_axis, self.topology.intra_axis))
        self._hier_progs: Dict[Tuple, Any] = {}
        self._ef_state: Dict[Tuple, Any] = {}

    @staticmethod
    def _probe(addr: str) -> bool:
        import socket

        host, port = addr.rsplit(":", 1)
        try:
            with socket.create_connection((host, int(port)), timeout=1.0):
                return True
        except OSError:
            return False

    def _start_coordinator(self, timeout_s: float) -> str:
        """Rank 0: publish an address, then bind the coordinator inside
        jax.distributed.initialize. The free-port probe is only a hint —
        if the port is taken between probe and bind (TOCTOU), we re-pick
        a port, re-publish, and retry instead of failing."""
        import socket

        host = os.environ.get("RAY_TPU_NODE_IP", "127.0.0.1")
        last = None
        for _ in range(3):
            with socket.socket() as s:
                s.bind(("", 0))
                port = s.getsockname()[1]
            addr = f"{host}:{port}"
            self._client.kv_put(
                _COORD_NS, self._coord_key(),
                pickle.dumps({"addr": addr, "nonce": os.urandom(8).hex()}),
                overwrite=True)
            try:
                self._ensure_jax_distributed(addr)
                return addr
            except RuntimeError as e:
                # bind race lost: retry with a fresh port. Anything else
                # (geometry mismatch, member crash) propagates.
                if "bind" not in str(e).lower():
                    raise
                last = e
        raise RuntimeError(
            f"group {self.group_name}: coordinator could not bind "
            f"after 3 attempts: {last}")

    def _ensure_jax_distributed(self, addr: str) -> None:
        """Join (or reuse) this process's jax.distributed cluster.

        initialize() is once-per-process; a second group in the same
        process reuses the existing cluster when its geometry matches
        (process count == world_size, our index == rank) and fails loudly
        otherwise — never with jax's opaque 'already initialized' error."""
        import jax
        from jax._src import distributed as jdist

        state = getattr(jdist, "global_state", None)
        if state is not None and state.client is not None:
            if (jax.process_count() != self.world_size
                    or jax.process_index() != self.rank):
                raise RuntimeError(
                    f"group {self.group_name}: this process already belongs "
                    f"to a jax.distributed cluster of "
                    f"{jax.process_count()} processes (as index "
                    f"{jax.process_index()}) — an xla-multihost group must "
                    f"match it (asked world={self.world_size} "
                    f"rank={self.rank})")
            return
        jax.distributed.initialize(coordinator_address=addr,
                                   num_processes=self.world_size,
                                   process_id=self.rank)

    def _publish_membership(self) -> None:
        """worker-id -> (group, rank) in the head KV: lets the device
        object store route a get() between gang members over the ICI
        data plane instead of host staging. Also carries this member's
        topology coordinates (host identity + local device count) so
        `hierarchy.infer_topology` can group the gang into hosts x local
        devices without extra RPCs."""
        try:
            wid = self._client.worker_id.hex()
            host = os.environ.get("RAY_TPU_NODE_IP") or (
                __import__("socket").gethostname())
            self._client.kv_put(
                _MEMBER_NS, wid.encode(),
                pickle.dumps({"group": self.group_name, "rank": self.rank,
                              "world": self.world_size, "host": host,
                              "local_devices": self.topology.intra}),
                overwrite=True)
        except Exception:
            pass  # membership routing is an optimization, never fatal

    # ------------------------------------------------------------- data plane
    def _global(self, x: np.ndarray):
        """Local array -> global [world, ...] jax.Array, one shard per
        process, sharded over the mesh's `p` axis."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        x = np.ascontiguousarray(x)
        sharding = NamedSharding(self.mesh, P("p", *([None] * x.ndim)))
        local = jax.device_put(x[None], self._local_dev)
        return jax.make_array_from_single_device_arrays(
            (self.world_size,) + x.shape, sharding, [local])

    def _shard_map(self, fn, g):
        import jax
        from jax.sharding import PartitionSpec as P

        return shard_map(fn, mesh=self.mesh, in_specs=P("p"),
                         out_specs=P("p"))(g)

    def _local_of(self, garr) -> np.ndarray:
        """This process's shard of a [world, ...] global array."""
        shard = garr.addressable_shards[0]
        return np.asarray(shard.data)[0]

    # ------------------------------------------------------------ collectives
    def _allreduce_np(self, x: np.ndarray, op: ReduceOp) -> np.ndarray:
        red = _reduce_op(op)
        out = self._shard_map(lambda a: red(a, "p"), self._global(x))
        return self._local_of(out)

    def allreduce(self, tensor, op: ReduceOp = ReduceOp.SUM, timeout=None):
        from ray_tpu.util.collective.kv_group import _write_back

        # in-place semantics match the kv/reference backends: the caller's
        # tensor holds the reduced value afterwards
        with self._op_lock:
            out = self._allreduce_np(np.asarray(tensor), op)
        return _write_back(tensor, out)

    def reduce(self, tensor, dst_rank: int = 0, op: ReduceOp = ReduceOp.SUM,
               timeout=None):
        """Reduce-to-one, lowered as psum: on a ring interconnect a reduce
        moves the same bytes as an allreduce (reduce-scatter phase is
        identical; the gather phase converges on dst), and XLA exposes no
        cheaper pairwise-accumulate — so this is bandwidth-optimal, not a
        shortcut."""
        from ray_tpu.util.collective.kv_group import _write_back

        with self._op_lock:
            out = self._allreduce_np(np.asarray(tensor), op)
        if self.rank == dst_rank:
            return _write_back(tensor, out)
        return tensor

    def broadcast(self, tensor, src_rank: int = 0, timeout=None):
        """Binomial-tree broadcast: ceil(log2(world)) ppermute rounds with
        unique (src,dst) pairs per round. Moves (world-1)·size bytes total
        at log depth — a real broadcast lowering, not the old 2x-traffic
        zeros-allreduce."""
        import jax.numpy as jnp
        from jax import lax

        from ray_tpu.util.collective.kv_group import _write_back

        x = np.asarray(tensor)
        world, src = self.world_size, src_rank

        def real(v):  # virtual rank (src-rooted) -> mesh rank
            return (v + src) % world

        def fn(a):
            idx = lax.axis_index("p")
            v = (idx - src) % world
            step = 1
            while step < world:
                pairs = [(real(i), real(i + step))
                         for i in range(step) if i + step < world]
                moved = lax.ppermute(a, "p", pairs)
                is_dst = jnp.logical_and(v >= step, v < 2 * step)
                a = jnp.where(is_dst, moved, a)
                step *= 2
            return a

        with self._op_lock:
            out = self._shard_map(fn, self._global(x))
            local = self._local_of(out)
        return _write_back(tensor, local)

    def allgather(self, tensor, timeout=None) -> List[np.ndarray]:
        from jax import lax

        x = np.asarray(tensor)
        with self._op_lock:
            out = self._shard_map(
                lambda a: lax.all_gather(a[0], "p")[None], self._global(x))
            gathered = self._local_of(out)  # [world, ...]
        return [gathered[i] for i in range(self.world_size)]

    def reducescatter(self, tensor, op: ReduceOp = ReduceOp.SUM, timeout=None):
        """Input [world, ...]; returns this rank's reduced slice.

        SUM lowers to `lax.psum_scatter` INSIDE the shard_map program —
        a true reduce-scatter moving ~1/world of the allreduce bytes
        (slicing on the host after a full psum would force XLA to
        materialize and ship the whole reduced tensor to every rank).
        Reference semantics: `util/collective/collective.py:525`."""
        arr = np.asarray(tensor)
        if arr.shape[0] != self.world_size:
            raise ValueError(
                f"reducescatter input leading dim {arr.shape[0]} != world "
                f"{self.world_size}")

        with self._op_lock:
            out = self._shard_map(_rs_program(op), self._global(arr))
            return self._local_of(out)

    # --------------------------------------- hierarchical device-plane path
    def _hier_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        t = self.topology
        return NamedSharding(self._hier_mesh,
                             P((t.inter_axis, t.intra_axis)))

    def _hier_program(self, colpad: int, op: ReduceOp, quantize,
                      average: bool):
        """Compiled inter-hop program for one (column size, op, quant)
        shape; cached per group. The intra phases of the staged schedule
        (scatter to columns / regather) happen OUTSIDE the program on the
        host-local fabric — under a distributed CPU runtime every
        in-program collective pays the cross-process transport, so only
        the genuinely inter-host hop runs as a collective. On the fused
        TPU path (`hierarchy.hier_allreduce_program`) all three phases
        stay in one program."""
        ef = (quantize is not None and quantize.error_feedback
              and op is ReduceOp.SUM)
        key = (colpad, op, quantize.key() if quantize else None, average)
        prog = self._hier_progs.get(key)
        if prog is not None:
            return prog
        import jax
        from jax.sharding import PartitionSpec as P

        t = self.topology
        H, inter = t.inter, t.inter_axis
        spec = P((t.inter_axis, t.intra_axis))
        # divide (not multiply-by-reciprocal): the kv fallback divides its
        # host buffer, and grad-sync parity across backends must be exact
        world = self.world_size if average else 0
        red = _reduce_op(op)

        if quantize is not None and op is ReduceOp.SUM:
            if ef:
                def body(a, r):
                    out, nr = quantize.ring_allreduce(
                        a[0], inter, H, residual=r[0])
                    if world:
                        out = out / world
                    return out[None], nr[None]

                fn = shard_map(
                    body, mesh=self._hier_mesh, in_specs=(spec, spec),
                    out_specs=(spec, spec), check_vma=False)
            else:
                def body(a):
                    out = quantize.ring_allreduce(a[0], inter, H)
                    if world:
                        out = out / world
                    return out[None]

                fn = shard_map(body, mesh=self._hier_mesh,
                               in_specs=spec, out_specs=spec,
                               check_vma=False)
        else:
            def body(a):
                out = red(a[0], inter)
                if world:
                    out = out / world
                return out[None]

            fn = shard_map(body, mesh=self._hier_mesh,
                           in_specs=spec, out_specs=spec,
                           check_vma=False)
        prog = jax.jit(fn)
        self._hier_progs[key] = prog
        return prog

    def allreduce_device(self, tensor, op: ReduceOp = ReduceOp.SUM, *,
                         quantize=None, average: bool = False,
                         ef_key: str = "", timeout=None):
        """Hierarchical device-plane allreduce of a FLOATING tensor;
        returns a jax.Array on this process's first local device (input
        is NOT mutated — device consumers chain off the returned array).
        Integer payloads must use the flat `allreduce` (this path stages
        through f32 and would corrupt values above 2^24).

        Staged two-level schedule over `self.topology` (hosts x local
        devices): the payload is split into `intra` columns, one per
        local device; each column allreduces its S/intra shard across the
        `inter` (host) axis CONCURRENTLY — the slow fabric carries S/intra
        per link instead of S — and the columns regather on the local
        fabric. With `quantize`, the inter hop runs the int8/fp8 ppermute
        ring with per-chunk scales. Error-feedback residuals persist on
        device between calls, keyed by (`ef_key`, payload size, quant
        config): callers syncing SEVERAL same-sized logical buffers must
        pass a distinct `ef_key` per buffer, or their residuals
        cross-contaminate (each call would fold the OTHER buffer's
        leftover quantization error into its sum). One residual buffer is
        retained per distinct key for the life of the group.

        `timeout` is accepted for kv-API parity but NOT enforced: like
        every device-plane collective here, the gloo/ICI program blocks
        until all members enter it, so a dead peer hangs the call — gang
        death is the controller's job (the PR 6 death watch fences and
        rebuilds the group; the kv fallback is the path with a real
        deadline)."""
        import jax
        import jax.numpy as jnp

        t = self.topology
        H, L = t.inter, t.intra
        x = np.asarray(tensor)
        shape, orig_dtype, n = x.shape, x.dtype, x.size
        if orig_dtype.kind != "f":
            raise TypeError(
                f"allreduce_device needs a floating dtype, got "
                f"{orig_dtype}; integer tensors take the flat allreduce()")
        if orig_dtype.itemsize > 4:
            raise TypeError(
                f"allreduce_device stages through f32 and would silently "
                f"truncate {orig_dtype} precision; use the flat "
                f"allreduce() (dtype-preserving) or downcast explicitly")
        if quantize is not None and op is not ReduceOp.SUM:
            raise ValueError(
                f"quantized allreduce supports SUM only (got {op.name}): "
                f"the int8/fp8 exchange accumulates contributions in f32 "
                f"source-rank order, which has no analog for other "
                f"reductions — drop quantize= for {op.name}")
        colpad = -(-max(n, 1) // L)
        if quantize is not None:
            colpad = quantize.padded_size(colpad)
        flat = np.zeros(L * colpad, dtype=np.float32)
        flat[:n] = np.ravel(x)
        cols = flat.reshape(L, colpad)
        ef = (quantize is not None and quantize.error_feedback
              and op is ReduceOp.SUM)
        gshard = self._hier_sharding()
        with self._op_lock:
            puts = [jax.device_put(cols[i][None], d)
                    for i, d in enumerate(self._local_devs)]
            ga = jax.make_array_from_single_device_arrays(
                (H * L, colpad), gshard, puts)
            prog = self._hier_program(colpad, op, quantize, average)
            if ef:
                rkey = (ef_key, colpad, quantize.key())
                r = self._ef_state.get(rkey)
                if r is None:
                    zeros = [jax.device_put(
                        np.zeros((1, colpad), np.float32), d)
                        for d in self._local_devs]
                    r = jax.make_array_from_single_device_arrays(
                        (H * L, colpad), gshard, zeros)
                out, self._ef_state[rkey] = prog(ga, r)
            else:
                out = prog(ga)
            parts = sorted(out.addressable_shards,
                           key=lambda s: s.index[0].start)
            col_arrs = [jax.device_put(s.data[0], self._local_devs[0])
                        for s in parts]
            fused = (jnp.concatenate(col_arrs) if len(col_arrs) > 1
                     else col_arrs[0])
        self._account_hier(op, colpad, quantize)
        res = fused[:n].reshape(shape)
        if orig_dtype.kind == "f" and res.dtype != orig_dtype:
            res = res.astype(orig_dtype)
        return res

    def _account_hier(self, op: ReduceOp, colpad: int, quantize) -> None:
        from ray_tpu.util.collective import hierarchy as _hier

        t = self.topology
        fp32_wire = 2 * (t.inter - 1) * colpad * 4 * t.intra // max(t.inter, 1)
        if quantize is not None and op is ReduceOp.SUM:
            wire = (t.inter - 1) * quantize.wire_bytes(colpad) * t.intra
            _hier.account_collective("allreduce", wire,
                                     quantize.dtype, hop="inter")
            _hier.account_quant_saving(max(0, fp32_wire - wire))
        else:
            _hier.account_collective("allreduce", fp32_wire, "float32",
                                     hop="inter")
        if t.intra > 1:
            # scatter + regather columns on the host-local fabric
            _hier.account_collective("allreduce", 2 * t.intra * colpad * 4,
                                     "float32", hop="intra")

    def allreduce_tree(self, tree, *, average: bool = True, quantize=None,
                       timeout=None):
        """Fused device-plane gradient sync: flatten the pytree's leaves
        into one f32 buffer, run ONE hierarchical allreduce, unflatten.
        Cross-member bytes ride the gang's device transport (ICI/DCN on
        TPU, gloo here) — the head KV carries nothing (the kv collective
        is the CPU-only fallback, see train.spmd.cross_worker_grad_sync).
        `timeout` is not enforced on the device plane (see
        `allreduce_device`); leaves are staged through f32 (f64 leaves
        lose precision — keep f64 state on the kv path)."""
        import jax
        import jax.numpy as jnp

        leaves, treedef = jax.tree_util.tree_flatten(tree)
        if not leaves:
            return tree
        arrs = [np.asarray(leaf) for leaf in leaves]
        fused = np.concatenate(
            [a.ravel().astype(np.float32, copy=False) for a in arrs])
        out = np.asarray(self.allreduce_device(
            fused, ReduceOp.SUM, quantize=quantize, average=average))
        res, off = [], 0
        for a, leaf in zip(arrs, leaves):
            dt = getattr(leaf, "dtype", a.dtype)
            res.append(jnp.asarray(
                out[off:off + a.size].reshape(a.shape), dtype=dt))
            off += a.size
        return jax.tree_util.tree_unflatten(treedef, res)

    def barrier(self, timeout=None):
        from jax.experimental import multihost_utils

        # name must be IDENTICAL on every process (it is hashed and
        # compared); a per-group counter keeps successive barriers distinct
        with self._op_lock:
            self._barrier_seq = getattr(self, "_barrier_seq", 0) + 1
            multihost_utils.sync_global_devices(
                f"{self.group_name}:barrier:{self._barrier_seq}")

    # ------------------------------------------------------------------- p2p
    def _pair_mesh(self, src: int, dst: int):
        from jax.sharding import Mesh

        key = (src, dst)
        mesh = self._pair_meshes.get(key)
        if mesh is None:
            mesh = Mesh(np.array([self._rank_dev[src], self._rank_dev[dst]]),
                        ("pp",))
            self._pair_meshes[key] = mesh
        return mesh

    def _p2p_program(self, local_arr, src: int, dst: int):
        """Both peers execute ONE ppermute program on their 2-device pair
        mesh: src's shard moves to dst's device over the interconnect
        (ICI/DCN on TPU, gloo on the CPU CI incarnation). `local_arr` may
        be a jax.Array already resident on our device (no host bounce) or
        a numpy array (one H2D). Returns the receiver-side output STILL ON
        DEVICE so device consumers never round-trip host.

        Like NCCL Send/Recv, a pair program blocks until BOTH peers enter
        it and cannot be preempted — a dead peer hangs the call, and the
        relative order of programs launched on one group must match on
        every participating member (hence `_op_lock`)."""
        import jax
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._pair_mesh(src, dst)
        if isinstance(local_arr, jax.Array):
            local = local_arr[None]          # stays on its (our) device
            shape = tuple(local_arr.shape)
        else:
            x = np.ascontiguousarray(local_arr)
            local = jax.device_put(x[None], self._local_dev)
            shape = x.shape
        sharding = NamedSharding(mesh, P("pp", *([None] * len(shape))))
        # exactly the addressable shards of THIS process (one of the two)
        g = jax.make_array_from_single_device_arrays(
            (2,) + shape, sharding, [local])
        out = shard_map(
            lambda a: lax.ppermute(a, "pp", [(0, 1)]),
            mesh=mesh, in_specs=P("pp"), out_specs=P("pp"))(g)
        return out.addressable_shards[0].data  # [1, ...] on local device

    def send(self, tensor, dst_rank: int, timeout=None):
        """NCCL-parity p2p: blocks until the peer enters recv; `timeout`
        is accepted for API parity but a device-plane collective cannot be
        preempted once launched (same as the reference's NCCL backend)."""
        if dst_rank == self.rank:
            raise ValueError("send to self")
        with self._op_lock:
            self._p2p_program(np.asarray(tensor), self.rank, dst_rank)

    def recv(self, tensor, src_rank: int, timeout=None):
        from ray_tpu.util.collective.kv_group import _write_back

        if src_rank == self.rank:
            raise ValueError("recv from self")
        buf = np.asarray(tensor)
        with self._op_lock:
            out = self._p2p_program(np.zeros_like(buf), src_rank, self.rank)
        return _write_back(tensor, np.asarray(out)[0])

    def send_device(self, leaf, dst_rank: int):
        """Device-plane send of a jax leaf (device-object ICI fetch): the
        leaf feeds the pair mesh directly from HBM — no D2H/H2D bounce.

        Bounded lock acquire: if this process is wedged in another
        collective (e.g. a mutual bidirectional fetch — a known ordering
        hazard shared with NCCL p2p), fail loudly instead of deadlocking
        the executor thread forever."""
        if not self._op_lock.acquire(timeout=120):
            raise TimeoutError(
                f"group {self.group_name}: collective order lock held for "
                ">120s — concurrent conflicting collectives on this group")
        try:
            self._p2p_program(leaf, self.rank, dst_rank)
        finally:
            self._op_lock.release()

    def recv_device(self, shape, dtype, src_rank: int):
        """Device-plane recv returning a jax.Array on our device."""
        with self._op_lock:
            out = self._p2p_program(np.zeros(shape, dtype=dtype),
                                    src_rank, self.rank)
        return out[0]

    def destroy(self):
        try:
            self._client.kv_del(_MEMBER_NS,
                                self._client.worker_id.hex().encode())
        except Exception:
            pass
        if self.rank == 0:
            try:
                self._client.kv_del(_COORD_NS, self._coord_key())
            except Exception:
                pass


def lookup_membership(client, worker_id_hex: str) -> Optional[dict]:
    """Head-KV lookup: is `worker_id` a live gang member? Used by the
    device object store to pick the ICI path between gang peers."""
    try:
        blob = client.kv_get(_MEMBER_NS, worker_id_hex.encode())
    except Exception:
        return None
    if not blob:
        return None
    return pickle.loads(blob)
