"""Pipeline parallelism: GPipe-style microbatch pipeline over the `pp` mesh axis.

The reference gets pipeline parallelism two ways (SURVEY.md §2.13): vLLM's
multi-node PP driven through placement groups, and Compiled Graphs
(`python/ray/dag/compiled_dag_node.py`) whose per-actor READ/COMPUTE/WRITE
schedules pipeline NCCL send/recv between stages. The TPU-native answer keeps
the whole pipeline INSIDE one XLA program: stages are a `pp` mesh axis, stage
hand-off is `lax.ppermute` riding the ICI ring, and the schedule is a
`lax.scan` over M + F - 1 ticks — XLA overlaps the permute with the next
tick's compute, no host in the loop.

Design (partial-manual shard_map):
- only `pp` is manual (`axis_names={'pp'}`); dp/fsdp/tp stay auto, so the
  stage function can keep its ordinary sharding annotations and XLA still
  inserts dp gradient allreduces etc.;
- stage params have a leading stage dim sharded over `pp`; each instance
  squeezes its own stage's slice;
- microbatch schedule: at tick t, stage 0 injects microbatch t (t < M), the
  last stage emits microbatch t-(F-1); a final masked `psum` replicates the
  output to every stage so downstream (loss/unembed) code sees a plain
  replicated-over-pp activation.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import current_mesh
from ray_tpu.util.collective.hierarchy import (account_collective,
                                               ring_perm)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    n_microbatches: int,
    mesh=None,
    axis: str = "pp",
) -> jax.Array:
    """Run `stage_fn` as a `pp`-deep pipeline over microbatches of `x`.

    stage_params: pytree whose every leaf has leading dim = pp degree
      (stage-stacked), sharded over `axis`.
    x: [B, ...] activations; B % n_microbatches == 0.
    stage_fn(params_for_one_stage, x_mb) -> x_mb.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        raise RuntimeError("pipeline_apply needs a mesh (use_mesh or mesh=)")
    F = mesh.shape[axis]
    if F == 1:
        sp = jax.tree.map(lambda a: a[0], stage_params)
        return stage_fn(sp, x)

    M = n_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by n_microbatches {M}")
    if M < F:
        raise ValueError(f"n_microbatches {M} < pipeline depth {F}: "
                         "bubble would dominate; use M >= pp")
    # On the CPU backend (tests, dry runs) the shard_map boundary runs in
    # f32: XLA:CPU still miscompiles a sub-group bf16 psum in a
    # partial-manual region under jax 0.9.0 ("Invalid binary instruction
    # opcode copy" CHECK), and the f32 boundary also covers the backward
    # psum of the replicated input's cotangent. The TPU compiler takes the
    # bf16 boundary (tests/test_tpu_compile.py), which halves the buffer
    # and the ICI psum bytes. Compute inside the stages stays in x.dtype.
    compute_dtype = x.dtype
    boundary_dtype = (jnp.float32 if jax.default_backend() == "cpu"
                      else compute_dtype)
    xs = x.reshape(M, B // M, *x.shape[1:]).astype(boundary_dtype)
    if not isinstance(x, jax.core.Tracer):
        # eager entry: account the pipeline's stage hand-off wire bytes
        # ((M+F-1) ticks, each stage forwards one microbatch activation).
        # The ring moves compute_dtype state (spmd_fn casts back before
        # the ppermute) — size it off x, not the f32 boundary buffer.
        mb_bytes = x.nbytes // M
        account_collective("pipeline.ppermute", (M + F - 1) * F * mb_bytes,
                           str(compute_dtype), hop="intra")

    def spmd_fn(stage_p, xs):
        xs = xs.astype(compute_dtype)
        stage_p = jax.tree.map(lambda a: a[0], stage_p)   # this stage's slice
        stage = lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects microbatch t
            inp = lax.dynamic_index_in_dim(xs, jnp.minimum(t, M - 1), 0,
                                           keepdims=False)
            state = jnp.where((stage == 0) & (t < M), inp, state)
            state = stage_fn(stage_p, state)
            # last stage emits microbatch t-(F-1)
            out_t = t - (F - 1)
            idx = jnp.clip(out_t, 0, M - 1)
            cur = lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            new = jnp.where((stage == F - 1) & (out_t >= 0), state, cur)
            outs = lax.dynamic_update_index_in_dim(outs, new, idx, 0)
            # rotate activations one stage forward (ICI ring; the
            # canonical collective-layer ring hop)
            state = lax.ppermute(state, axis, ring_perm(F))
            return (state, outs), None

        (state, outs), _ = lax.scan(tick, (state, outs),
                                    jnp.arange(M + F - 1))
        # replicate the last stage's outputs to every stage (psum in the
        # boundary dtype — see dtype note above)
        outs = outs.astype(boundary_dtype)
        return lax.psum(
            jnp.where(stage == F - 1, outs, jnp.zeros_like(outs)), axis)

    # partial-manual: only `axis` is manual, so stage_fn keeps its auto
    # dp/tp shardings and constraints
    out = shard_map(
        spmd_fn,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )(stage_params, xs)
    return out.astype(compute_dtype).reshape(B, *x.shape[1:])


def stack_stages(block_params: Any, n_stages: int) -> Any:
    """[L, ...]-stacked block params -> [n_stages, L/n_stages, ...]."""

    def reshape(a):
        L = a.shape[0]
        if L % n_stages:
            raise ValueError(f"{L} layers not divisible by {n_stages} stages")
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(reshape, block_params)


def make_stage_fn(block_fn: Callable[[jax.Array, Any], jax.Array],
                  remat: bool = True) -> Callable:
    """Lift a single-block fn (x, block_params) -> x into a stage fn that
    scans its stage's [L/F, ...] blocks."""
    body = jax.checkpoint(block_fn) if remat else block_fn

    def stage_fn(stage_p, x):
        x, _ = lax.scan(lambda c, bp: (body(c, bp), None), x, stage_p)
        return x

    return stage_fn


# ---------------------------------------------------------------------------
# Channel-driven compiled 1F1B schedule (SURVEY §3.7 Compiled Graphs).
#
# `pipeline_apply` above keeps the whole pipeline inside ONE XLA program —
# right when every stage fits one mesh. The classes below are the
# HOST-level pipeline: stages are long-lived actors (one per host/slice,
# possibly on different nodes), and the per-microbatch hand-offs ride the
# same pre-negotiated channels as compiled DAGs — local shm rings between
# co-located stages, `RemoteChannelReader` RPC edges across nodes, and
# (tensor_transport="device") DLPack descriptors through the PR 7
# device-object plane so activations never leave device memory for a
# co-located consumer. The 1F1B order (warmup forwards, steady
# one-forward-one-backward, cooldown backwards) bounds live activations
# per stage at pipeline depth, and the ring depth (`max_inflight`) is
# what lets a stage run ahead instead of serializing on the slowest
# neighbour — max_inflight=1 degenerates to lock-step single-slot
# hand-offs. The scheduler participates only at start(): a warm step is
# shm writes + condvar wakes, zero control-plane RPCs.
# ---------------------------------------------------------------------------


def mse_loss(pred: jax.Array, target: jax.Array) -> jax.Array:
    """Default last-stage loss for ChannelPipelineStage (top-level: must
    pickle by reference into stage actors)."""
    return jnp.mean((pred - target) ** 2)


def mlp_stage_fn(params: dict, x: jax.Array) -> jax.Array:
    """Reference stage for tests/benchmarks: one tanh MLP layer."""
    return jnp.tanh(x @ params["w"] + params["b"])


def init_mlp_stage(key, d_in: int, d_out: int, scale: float = 0.3) -> dict:
    k1, _ = jax.random.split(jax.random.key(key) if isinstance(key, int)
                             else key)
    return {"w": jax.random.normal(k1, (d_in, d_out)) * scale,
            "b": jnp.zeros((d_out,))}


class ChannelPipelineStage:
    """One pipeline stage as a long-lived actor: holds its params, a
    jitted forward, a jitted VJP backward, and (last stage) a jitted
    loss-and-grad. Wrap with `ray_tpu.remote` (or use
    `CompiledPipeline.build_stages`). Two drive modes:

    - eager: the driver calls `fwd_eager`/`bwd_eager` per microbatch
      (GPipe over ordinary actor RPCs — the baseline the compiled mode
      is measured against);
    - compiled: `pp_stage_loop(cfg)` attaches pre-negotiated channels
      and runs the 1F1B schedule until the input channel closes.
    """

    def __init__(self, stage_fn: Callable, params: Any, *,
                 position: int, n_stages: int, lr: float = 0.05,
                 loss_fn: Optional[Callable] = None):
        self.position = int(position)
        self.n_stages = int(n_stages)
        self.is_first = self.position == 0
        self.is_last = self.position == self.n_stages - 1
        self.lr = float(lr)
        self.params = params
        self._stage_fn = stage_fn
        self._fwd = jax.jit(stage_fn)

        def _bwd(p, x, g):
            _, vjp = jax.vjp(stage_fn, p, x)
            return vjp(g)

        self._bwd = jax.jit(_bwd)
        if self.is_last:
            loss_fn = loss_fn or mse_loss

            def _loss(p, x, y):
                return loss_fn(stage_fn(p, x), y)

            self._lossgrad = jax.jit(jax.value_and_grad(_loss,
                                                        argnums=(0, 1)))
        self._apply = jax.jit(
            lambda p, g, s: jax.tree.map(lambda a, b: a - s * b, p, g))
        self._acc = None
        self._stash: dict = {}
        self._losses: list = []
        self.steps_done = 0
        self._dev_refs: list = []
        # eager calls arrive in submission order but may EXECUTE
        # concurrently (the actor leaves executor room for control
        # calls); the lock serializes them back into schedule order
        import threading

        self._eager_lock = threading.Lock()

    # ------------------------------------------------------------ common
    def _accumulate(self, dp) -> None:
        self._acc = dp if self._acc is None else jax.tree.map(
            jnp.add, self._acc, dp)

    def apply_grads(self, n_microbatches: int, _after=None) -> bool:
        if self._acc is not None:
            self.params = self._apply(self.params, self._acc,
                                      self.lr / n_microbatches)
            self._acc = None
        self.steps_done += 1
        return True

    def get_params(self):
        import numpy as np

        return jax.tree.map(np.asarray, self.params)

    # ------------------------------------------------- eager (RPC) drive
    # `_after` is a sequencing-only dependency: the driver threads each
    # stage's previous op ref through it so ops run in schedule order
    # even when the actor executes calls concurrently (lock wakeup order
    # is not FIFO; argument resolution is).
    def fwd_eager(self, mb: int, x, y=None, _after=None):
        with self._eager_lock:
            x = jnp.asarray(x)
            if self.is_last:
                loss, (dp, dx) = self._lossgrad(self.params, x,
                                                jnp.asarray(y))
                self._accumulate(dp)
                self._losses.append(float(loss))
                self._stash[mb] = dx
                return None
            act = self._fwd(self.params, x)
            self._stash[mb] = x
            import numpy as np

            return np.asarray(act)

    def bwd_eager(self, mb: int, g=None, _after=None):
        import numpy as np

        with self._eager_lock:
            if self.is_last:
                return np.asarray(self._stash.pop(mb))
            dp, dx = self._bwd(self.params, self._stash.pop(mb),
                               jnp.asarray(g))
            self._accumulate(dp)
            return None if self.is_first else np.asarray(dx)

    def pop_mean_loss(self, _after=None) -> float:
        losses, self._losses = self._losses, []
        return float(sum(losses) / max(1, len(losses)))

    # ------------------------------------------- compiled (channel) drive
    def _wrap(self, arr, transport, ring: int):
        import numpy as np

        if transport == "device":
            from ray_tpu.core.api import _global_client
            from ray_tpu.dag.runtime import DEVICE_DESC

            oref = _global_client().put_device(arr)
            # hold enough generations to cover the ring depth plus the
            # value a reader may still be fetching
            self._dev_refs.append(oref)
            while len(self._dev_refs) > 2 * ring + 2:
                self._dev_refs.pop(0)
            return {DEVICE_DESC: oref.binary()}
        return np.asarray(arr)

    def _schedule(self, M: int) -> list:
        """1F1B op order for this stage: warmup forwards, steady
        (forward, backward) pairs, cooldown backwards."""
        W = min(self.n_stages - 1 - self.position, M)
        ops = [("F", k) for k in range(W)]
        for k in range(M - W):
            ops.append(("F", W + k))
            ops.append(("B", k))
        ops.extend(("B", k) for k in range(M - W, M))
        return ops

    def _stage_span(self, carrier, t0: float):
        """Record this stage's forward span for a sampled microbatch
        (backdated over the compute it just ran) and return the child
        carrier the NEXT stage parents to — the per-hop link in the
        compiled 1F1B submit→stage→…→stage span chain. None when the
        microbatch is untraced."""
        if carrier is None:
            return None
        try:
            from ray_tpu.util import tracing

            dur = time.perf_counter() - t0
            with tracing.start_span(
                    f"pp.stage{self.position}.fwd", carrier=carrier,
                    attributes={"ray_tpu.op": "pp_stage",
                                "position": self.position}) as sp:
                if sp is None:
                    return None
                sp.start_ts = time.time() - dur
                return {"traceparent": sp.traceparent()}
        except Exception:
            return None

    def _publish_ring_telemetry(self, key: str, *endpoints) -> None:
        """Snapshot this stage's LOCAL ring handles (remote-reader edges
        are sampled by their hosting process) into the hot-path
        observatory, labelled by edge role."""
        from ray_tpu.dag.channel import Channel, publish_ring_stats

        snaps = {}
        for label, ep in zip(("in", "out", "gin", "gout"), endpoints):
            if isinstance(ep, Channel):
                try:
                    snaps[label] = ep.snapshot()
                except Exception:
                    pass
        if snaps:
            publish_ring_stats("pipeline", f"{key}/stage{self.position}",
                               snaps)

    def pp_stage_loop(self, cfg: dict) -> dict:
        """Attach this stage's pre-negotiated channel edges and run 1F1B
        steps until the upstream channel closes (driver teardown)."""
        from ray_tpu.dag.channel import (Channel, ChannelClosedError,
                                         RemoteChannelReader)
        from ray_tpu.dag.runtime import materialize_channel_value

        def endpoint(ref):
            if ref is None:
                return None
            kind, val = ref
            if kind == "chan":
                return Channel.attach(val)
            return RemoteChannelReader(*val)

        in_r = endpoint(cfg["in"])
        out_w = endpoint(cfg.get("out"))
        gin_r = endpoint(cfg.get("gin"))
        gout_w = endpoint(cfg.get("gout"))
        loss_w = endpoint(cfg.get("loss"))
        M = int(cfg["M"])
        ring = int(cfg.get("ring", 1))
        transport = cfg.get("transport")
        key = str(cfg.get("key", "pp"))
        ops = self._schedule(M)
        steps = 0
        last_telem = 0.0
        try:
            from ray_tpu.core import config as _cfg

            telem_interval = float(_cfg.get("ring_telemetry_interval_s"))
        except Exception:
            telem_interval = 0.0
        try:
            while True:
                losses = []
                for op, k in ops:
                    if op == "F":
                        # a sampled microbatch carries a W3C carrier as a
                        # third tuple element (CompiledPipeline.step /
                        # the upstream stage's _stage_span)
                        item = in_r.read()
                        carrier = item[2] if len(item) > 2 else None
                        x, y = item[0], item[1]
                        x = jnp.asarray(materialize_channel_value(x))
                        t0 = time.perf_counter()
                        if self.is_last:
                            loss, (dp, dx) = self._lossgrad(
                                self.params, x, jnp.asarray(y))
                            self._accumulate(dp)
                            losses.append(float(loss))
                            if gout_w is not None:
                                gout_w.write(self._wrap(dx, transport, ring))
                            self._stage_span(carrier, t0)
                        else:
                            act = self._fwd(self.params, x)
                            self._stash[k] = x
                            child = self._stage_span(carrier, t0)
                            payload = (self._wrap(act, transport, ring), y)
                            if child is not None:
                                payload = payload + (child,)
                            out_w.write(payload)
                    elif not self.is_last:
                        g = jnp.asarray(materialize_channel_value(
                            gin_r.read()))
                        dp, dx = self._bwd(self.params, self._stash.pop(k), g)
                        self._accumulate(dp)
                        if gout_w is not None:
                            gout_w.write(self._wrap(dx, transport, ring))
                self.apply_grads(M)
                if loss_w is not None:
                    loss_w.write(float(sum(losses) / max(1, len(losses))))
                steps += 1
                if telem_interval > 0 \
                        and time.monotonic() - last_telem > telem_interval:
                    last_telem = time.monotonic()
                    self._publish_ring_telemetry(key, in_r, out_w,
                                                 gin_r, gout_w)
        except ChannelClosedError:
            pass
        finally:
            # propagate shutdown downstream so every stage's loop exits
            for ch in (out_w, gout_w, loss_w):
                if ch is not None:
                    try:
                        ch.close()
                    except Exception:
                        pass
            self._stash.clear()
            self._dev_refs.clear()
        return {"steps": steps, "position": self.position}


class CompiledPipeline:
    """Driver handle for a channel-driven 1F1B pipeline over stage
    actors. `start()` negotiates every channel once (the only
    control-plane work); `step(x, y)` streams microbatches through the
    input ring and blocks on the loss ring — zero per-step RPCs when the
    stages are co-located, RemoteChannelReader edges otherwise."""

    def __init__(self, stage_actors, *, n_microbatches: int,
                 max_inflight: Optional[int] = None,
                 channel_capacity: int = 4 << 20,
                 tensor_transport: Optional[str] = None,
                 step_timeout: float = 120.0):
        if not stage_actors:
            raise ValueError("need at least one stage actor")
        self.stages = list(stage_actors)
        self.M = int(n_microbatches)
        F = len(self.stages)
        self.max_inflight = int(max_inflight or max(2, min(self.M, F + 1)))
        self.capacity = channel_capacity
        self.transport = tensor_transport
        self.step_timeout = step_timeout
        self._started = False
        self._closed = False
        self._loop_refs = []
        self._remote_created = []
        self.key = "pp"               # replaced by the start() tag
        self._trace_seq = 0
        self._last_telem = 0.0

    @staticmethod
    def build_stages(stage_fns, params_list, *, lr: float = 0.05,
                     loss_fn: Optional[Callable] = None,
                     actor_options: Optional[list] = None):
        """Create one ChannelPipelineStage actor per (stage_fn, params).
        `actor_options[i]` (e.g. {"resources": {...}}) pins placement."""
        import ray_tpu

        F = len(params_list)
        fns = (stage_fns if isinstance(stage_fns, (list, tuple))
               else [stage_fns] * F)
        actors = []
        for i, (fn, p) in enumerate(zip(fns, params_list)):
            opts = dict((actor_options[i] if actor_options else {}) or {})
            # the compiled stage loop occupies one executor thread for its
            # lifetime; leave room for control calls (get_params, eager)
            opts.setdefault("max_concurrency", 4)
            cls = ray_tpu.remote(**opts)(ChannelPipelineStage)
            actors.append(cls.remote(
                fn, p, position=i, n_stages=F, lr=lr,
                loss_fn=loss_fn if i == F - 1 else None))
        return actors

    # ------------------------------------------------------------ bring-up
    def start(self) -> None:
        import os as _os

        from ray_tpu.core.api import _global_client
        from ray_tpu.dag.channel import Channel, RemoteChannelReader

        client = _global_client()
        my_node = client.node_id.binary()
        my_addr = ("127.0.0.1", client.direct_port)
        F = len(self.stages)

        addr, node = [], []
        for s in self.stages:
            reply = client.head_request("get_actor_address",
                                        actor_id=s._actor_id.binary())
            if reply["state"] == "DEAD":
                raise RuntimeError("cannot compile over dead stage actor")
            node.append(reply.get("node_id") or my_node)
            addr.append(tuple(reply["address"]))

        tag = _os.urandom(4).hex()
        self.key = f"pp_{tag}"
        names = {"in": f"rtpu_pp_{tag}_in",
                 "loss": f"rtpu_pp_{tag}_loss"}
        for i in range(F - 1):
            names[f"act{i}"] = f"rtpu_pp_{tag}_a{i}"      # stage i -> i+1
            names[f"grad{i + 1}"] = f"rtpu_pp_{tag}_g{i + 1}"  # i+1 -> i

        # two-phase bring-up: every channel is created in its WRITER's
        # process before any stage loop starts
        self._input = Channel(name=names["in"], capacity=self.capacity,
                              num_readers=1, num_slots=self.max_inflight)

        def create_at(stage_idx: int, name: str) -> None:
            client.direct_request(
                addr[stage_idx], "dag_chan_create", name=name,
                capacity=self.capacity, num_readers=1,
                num_slots=self.max_inflight)
            self._remote_created.append((addr[stage_idx], name))

        for i in range(F - 1):
            create_at(i, names[f"act{i}"])
            create_at(i + 1, names[f"grad{i + 1}"])
        create_at(F - 1, names["loss"])

        def ref_for(name: str, writer_idx: Optional[int],
                    consumer_node: bytes):
            w_node = my_node if writer_idx is None else node[writer_idx]
            w_addr = my_addr if writer_idx is None else addr[writer_idx]
            if w_node == consumer_node:
                return ("chan", name)
            return ("rchan", (name, w_addr))

        for i, s in enumerate(self.stages):
            cfg = {"M": self.M, "ring": self.max_inflight,
                   "transport": self.transport, "key": self.key,
                   "in": (ref_for(names["in"], None, node[i]) if i == 0
                          else ref_for(names[f"act{i - 1}"], i - 1,
                                       node[i])),
                   "out": (ref_for(names[f"act{i}"], i, node[i])
                           if i < F - 1 else None),
                   "gin": (ref_for(names[f"grad{i + 1}"], i + 1, node[i])
                           if i < F - 1 else None),
                   "gout": (ref_for(names[f"grad{i}"], i, node[i])
                            if i > 0 else None),
                   "loss": (ref_for(names["loss"], F - 1, node[i])
                            if i == F - 1 else None)}
            self._loop_refs.append(s.pp_stage_loop.remote(cfg))

        if node[F - 1] == my_node:
            self._loss_r = Channel.attach(names["loss"])
        else:
            self._loss_r = RemoteChannelReader(names["loss"], addr[F - 1])
        self._started = True

    # ------------------------------------------------------------- control
    def _maybe_trace_step(self):
        """1-in-N sampled step tracing (`tracing_compiled_sample_n`, the
        same knob as the serve chain): the returned W3C carrier rides
        microbatch 0's ring tuple, so a sampled step yields the full
        submit→stage→…→stage span chain in the chrome timeline with
        zero extra RPCs. None for unsampled/untraced steps."""
        try:
            from ray_tpu.core import config as _cfg
            from ray_tpu.util import tracing

            n = int(_cfg.get("tracing_compiled_sample_n"))
            if n <= 0 or not tracing.is_recording():
                return None
            seq = self._trace_seq
            self._trace_seq = seq + 1
            if seq % n:
                return None
            with tracing.start_span(
                    "pp.step.submit",
                    attributes={"ray_tpu.op": "pp_submit",
                                "pipeline": self.key,
                                "microbatches": self.M}) as sp:
                if sp is None:
                    return None
                return {"traceparent": sp.traceparent()}
        except Exception:
            return None

    def _telemetry_tick(self) -> None:
        """Time-gated driver-side ring snapshots (input + loss rings,
        when local) into the hot-path observatory."""
        try:
            from ray_tpu.core import config as _cfg

            interval = float(_cfg.get("ring_telemetry_interval_s"))
        except Exception:
            return
        if interval <= 0 or time.monotonic() - self._last_telem < interval:
            return
        self._last_telem = time.monotonic()
        from ray_tpu.dag.channel import Channel, publish_ring_stats

        snaps = {}
        try:
            snaps["in"] = self._input.snapshot()
        except Exception:
            pass
        if isinstance(getattr(self, "_loss_r", None), Channel):
            try:
                snaps["loss"] = self._loss_r.snapshot()
            except Exception:
                pass
        if snaps:
            publish_ring_stats("pipeline", self.key, snaps)

    def step(self, x, y) -> float:
        """Stream one batch through the pipeline as M microbatches;
        returns the step's mean loss. Microbatch writes backpressure on
        the input ring, so up to max_inflight microbatches pipeline into
        the stages while earlier ones are still in flight."""
        if self._closed:
            raise RuntimeError("pipeline was closed")
        if not self._started:
            self.start()
        import numpy as np

        x, y = np.asarray(x), np.asarray(y)
        B = x.shape[0]
        if B % self.M:
            raise ValueError(f"batch {B} not divisible by M={self.M}")
        mb = B // self.M
        carrier = self._maybe_trace_step()
        for k in range(self.M):
            payload = (x[k * mb:(k + 1) * mb], y[k * mb:(k + 1) * mb])
            if k == 0 and carrier is not None:
                payload = payload + (carrier,)
            self._input.write(payload, timeout=self.step_timeout)
        loss = float(self._loss_r.read(timeout=self.step_timeout))
        self._telemetry_tick()
        return loss

    def get_params(self, timeout: float = 60.0) -> list:
        import ray_tpu

        return ray_tpu.get([s.get_params.remote() for s in self.stages],
                           timeout=timeout)

    def close(self, timeout: float = 30.0, kill_actors: bool = False) -> None:
        import ray_tpu

        if self._closed or not self._started:
            self._closed = True
            if kill_actors:
                for s in self.stages:
                    try:
                        ray_tpu.kill(s)
                    except Exception:
                        pass
            return
        self._closed = True
        from ray_tpu.core.api import _global_client

        self._input.close(unlink=True)
        for ref in self._loop_refs:
            try:
                ray_tpu.get(ref, timeout=timeout)
            except Exception:
                pass
        client = _global_client()
        for a, name in self._remote_created:
            try:
                client.direct_request(a, "dag_chan_close", name=name,
                                      unlink=True)
            except Exception:
                pass
        if kill_actors:
            for s in self.stages:
                try:
                    ray_tpu.kill(s)
                except Exception:
                    pass


def eager_pipeline_step(stage_actors, x, y, n_microbatches: int,
                        timeout: float = 120.0) -> float:
    """GPipe over ordinary actor calls — the dynamic-dispatch baseline
    the compiled 1F1B mode is benchmarked against. Every microbatch edge
    pays actor-call submission + result resolution through the task
    plane; returns the step's mean loss."""
    import numpy as np

    import ray_tpu

    stages = list(stage_actors)
    M = int(n_microbatches)
    x, y = np.asarray(x), np.asarray(y)
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by M={M}")
    mb = B // M
    # per-actor sequencing: each stage's ops chain on its previous op so
    # the GPipe order holds even under concurrent actor executors
    last_of: dict = {}

    def call(i, method, *args):
        ref = getattr(stages[i], method).remote(*args,
                                                _after=last_of.get(i))
        last_of[i] = ref
        return ref

    # forward sweep: chain refs stage to stage (dependencies resolve in
    # the workers; the driver still pays per-call dispatch for each edge)
    for k in range(M):
        r = None
        for i in range(len(stages)):
            xk = x[k * mb:(k + 1) * mb] if i == 0 else r
            yk = y[k * mb:(k + 1) * mb] if i == len(stages) - 1 else None
            r = call(i, "fwd_eager", k, xk, yk)
    ray_tpu.get(r, timeout=timeout)
    # backward sweep in reverse microbatch order
    last_done = None
    for k in reversed(range(M)):
        g = None
        for i in reversed(range(len(stages))):
            g = call(i, "bwd_eager", k, g)
        last_done = g
    if last_done is not None:
        ray_tpu.get(last_done, timeout=timeout)
    loss_ref = call(len(stages) - 1, "pop_mean_loss")
    ray_tpu.get([call(i, "apply_grads", M) for i in range(len(stages))],
                timeout=timeout)
    return float(ray_tpu.get(loss_ref, timeout=timeout))
