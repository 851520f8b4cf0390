"""LLM serving: continuous-batching decode engine on TPU + deployment glue.

Capability counterpart of the reference's serve.llm stack
(`python/ray/llm/_internal/serve/` — vLLM engine behind deployments). The
TPU-native engine is ours: a jitted cached decode step over a fixed slot
batch (a model module's `decode_step`; the preset's name picks the module,
`ray_tpu.models.serving_family`); requests are admitted into free slots as
others finish (continuous batching), so decode throughput stays at the
full batch width under load.

Real weights: `checkpoint=` loads a `gpt2.save_params` directory (what
the trainer writes), so replicas serve trained parameters, not random
init; `tokenizer=` accepts any encode/decode object (an HF tokenizer
adapter is provided, gated on a locally cached vocab — zero egress).
ByteTokenizer remains the self-contained fallback.
"""

from __future__ import annotations

import queue
import threading
import time
import uuid
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

import numpy as np

from ray_tpu.util import tracing

# What the engine's thread does in one pass of its loop, and it is always in
# exactly one of them. Each is an `engine.<phase>` span on the profiler's
# clock and two cumulative timers, wall and CPU seconds, in `engine_stats()`'s
# `phase_s` and `phase_cpu_s`. The loop runs one step ahead of what it has
# read: `put` hands the step's host arrays to the device, `dispatch` launches
# a step program and `sample` the selection of its tokens on the device;
# `fetch` is the wait for the ids of the step dispatched a pass earlier,
# `notify` and `publish` what follows from them; `release` is the pass's
# tail, where the step before's arrays go, up to the next pass's `calls`.
ENGINE_PHASES = ("calls", "admit", "plan", "put", "dispatch", "fetch",
                 "sample", "publish", "notify", "empty", "release")
# a pass of the loop longer than this is counted and kept by name
# (`engine_stats()["slow_passes"]`): no client's log holds a gap between two
# tokens this long unless the engine stalled, and the longest step program
# of any preset (a 180 ms chunk step) stays under it
SLOW_PASS_S = 0.25
# seconds; shared by the two request-lifecycle histograms
_LIFECYCLE_BOUNDARIES = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                         0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
_lifecycle_metrics = None


def _get_lifecycle_metrics() -> dict:
    """The process's two request-lifecycle histograms, for `/metrics`. An
    engine keeps its own count and sum beside them (`engine_stats()`)."""
    global _lifecycle_metrics
    if _lifecycle_metrics is None:
        from ray_tpu.util import metrics as m

        _lifecycle_metrics = {
            "queue_wait_s": m.Histogram(
                "serve_engine_queue_wait_seconds",
                "Request handed to the engine -> placed in a decode slot",
                boundaries=_LIFECYCLE_BOUNDARIES),
            "ttft_s": m.Histogram(
                "serve_engine_ttft_seconds",
                "Request handed to the engine -> its first generated token",
                boundaries=_LIFECYCLE_BOUNDARIES),
        }
    return _lifecycle_metrics


class _Phases:
    """The engine thread's lap timer. `to(name)` ends the phase the thread
    is in and opens `name`: one reading of each clock a boundary, charged to
    the phase that ends there, so the phases' wall seconds sum to the
    loop's. `cpu` is fed from `thread_time()` at the same boundaries: a
    phase's wall less its CPU seconds is the time the thread was in it and
    not running (the device's in `fetch`, the sleep's in `empty`, anywhere
    else the interpreter lock's or the scheduler's). Each phase is an
    `engine.<name>` annotation on the profiler's clock, closed before the
    next opens. Only the engine's thread calls `start` and `to`."""

    __slots__ = ("wall", "cpu", "t", "c", "_labels", "_name", "_ann")

    def __init__(self):
        self.wall: Dict[str, float] = dict.fromkeys(ENGINE_PHASES, 0.0)
        self.cpu: Dict[str, float] = dict.fromkeys(ENGINE_PHASES, 0.0)
        self._labels = {n: f"engine.{n}" for n in ENGINE_PHASES}
        self.t = self.c = 0.0      # the clocks at the newest boundary

    def start(self, name: str) -> None:
        self._name = name
        self._ann = tracing.annotate(self._labels[name])
        self._ann.__enter__()
        self.t, self.c = time.perf_counter(), time.thread_time()

    def to(self, name: str) -> float:
        t, c = time.perf_counter(), time.thread_time()
        self._ann.__exit__(None, None, None)
        was = self._name
        self.wall[was] += t - self.t
        self.cpu[was] += c - self.c
        self.t, self.c, self._name = t, c, name
        self._ann = tracing.annotate(self._labels[name])
        self._ann.__enter__()
        return t


class ByteTokenizer:
    """utf-8 bytes as token ids (0-255); eos = 0. Self-contained fallback so
    serving works without downloaded vocabularies."""

    eos_id = 0

    def encode(self, text: str) -> List[int]:
        return [b + 1 for b in text.encode("utf-8")][:2048]

    def decode(self, ids: List[int]) -> str:
        # ids beyond the byte range (larger model vocabs) wrap; this is a
        # demo tokenizer, not a real vocabulary
        return bytes((i - 1) % 256 for i in ids if i > 0).decode(
            "utf-8", errors="replace")


class HFTokenizer:
    """transformers tokenizer adapter (reference serve.llm uses the HF
    tokenizer of the served checkpoint). Requires the vocab to already be
    on disk/cache — this environment has no egress, so construction
    fails loudly rather than downloading."""

    def __init__(self, name_or_path: str):
        try:
            from transformers import AutoTokenizer
        except ImportError as e:  # pragma: no cover - env-dependent
            raise ImportError("HFTokenizer requires `transformers`") from e
        self._tok = AutoTokenizer.from_pretrained(name_or_path,
                                                  local_files_only=True)
        self.eos_id = self._tok.eos_token_id or 0

    def encode(self, text: str) -> List[int]:
        return self._tok.encode(text)

    def decode(self, ids: List[int]) -> str:
        return self._tok.decode(ids)


def _require_gpt2(family: str, what: str) -> None:
    """`what` is one of the paths built for GPT-2's tree and cache alone."""
    if family != "gpt2":
        raise NotImplementedError(
            f"{what} is built for the gpt2 family only; the {family} "
            f"family serves seeded or handed-over weights on one chip")


class _Request:
    def __init__(self, prompt_ids: List[int], max_tokens: int,
                 temperature: float, top_k: int = 0, top_p: float = 1.0,
                 prefix_future=None, prefix_wait_s: float = 30.0):
        self.prompt_ids = prompt_ids
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        # async prefill fetch: a concurrent.futures.Future resolving to a
        # KV blob (or None). The engine defers THIS request's slot
        # placement until the blob lands — other lanes keep decoding —
        # and falls back to local prefill at the deadline.
        self.prefix_future = prefix_future
        self.prefix_deadline = time.time() + prefix_wait_s
        self.generated: List[int] = []
        # tokens whose steps are dispatched: `generated` catches up when
        # the engine reads a step's ids, one step later
        self.scheduled = 0
        self.done = threading.Event()
        self.error: Optional[str] = None
        self.finish_reason: str = "stop"
        # streaming consumers: wakes on every appended token batch
        self.progress = threading.Condition()
        self._sent_text = ""  # cumulative text already shipped to the consumer
        # lifecycle, `time.time()`: handed to the engine, placed in a
        # slot, first generated token (TTFT), last one
        self.t_enqueue = time.time()
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.reused_tokens = 0       # prompt tokens found in the prefix pool
        # the caller's span context when it records (the replica's
        # `serve.replica` span): the engine's thread is not the
        # request's, so its spans are written at completion under this
        self.trace_carrier = tracing.inject_context()


def plan_chunk_budget(pending_lens: List[int], decoding: List[bool],
                      chunk_size: int, budget: int) -> List[int]:
    """Token-budget step plan for one continuous-batching tick: how many
    tokens each slot processes this step.

    Decode slots are reserved FIRST and unconditionally (one token each:
    a prefilling long prompt can never starve running generations), then
    the remaining budget is dealt to prefilling slots in slot order,
    capped at `chunk_size` per slot. When only prefills are live, at
    least one slot always makes progress regardless of budget (no
    livelock on a tiny budget). Pure — unit-tested directly.
    """
    n = len(pending_lens)
    takes = [0] * n
    for i in range(n):
        if decoding[i]:
            takes[i] = 1
            budget -= 1
    any_progress = any(takes)
    for i in range(n):
        if decoding[i] or pending_lens[i] <= 0:
            continue
        take = min(pending_lens[i], chunk_size, max(budget, 0))
        if take <= 0 and not any_progress:
            take = 1      # sole-prefill guarantee
        if take <= 0:
            continue
        takes[i] = take
        budget -= take
        any_progress = True
    return takes


class LLMEngine:
    """Continuous-batching decode engine over a fixed slot batch.

    Per-step join/evict with a token-budget step plan: new requests
    enter the running batch at the next decode step, finished sequences
    free their KV slot immediately, and long prompts prefill in
    `prefill_chunk_size`-token chunks (the model's `prefill_chunk`) under
    `max_num_batched_tokens` per step, with decode lanes reserved first
    so prefill can't starve decode. `scheduler` names that one loop:
    deployment configs carry the key, and any other value is refused.
    The default budget, `max(2 B, B + C)`, lets at most two slots prefill
    in a step beside the decode lanes. What a larger one costs is the
    family's chunk program's to say: one that runs all B x C lanes through
    its layers costs the same whatever the plan handed out; one that
    computes a slot's further lanes only where it has any grows with the
    slots that prefill (`chunk_prefilling_slots` in `engine_stats()`): a
    slot's attention a slot, and a pass of the layer's MLP or experts for
    every slot whose lanes no longer fit the first lanes' call of
    `max(2 B, B + C)` rows (`lm.lane_rounds`; `chunk_steps_one_dispatch`
    counts the steps that fitted, every step of the default budget with
    all slots busy). `benchmarks/kanana_chunk_lanes.py` has the table for
    the latent-attention family at Kanana's widths, 32 slots and chunks of
    128, beside the all-lanes form's 310 ms (PERF.md §7).

    The next token of every slot is chosen on the device
    (`serve/sampling.select_tokens`) and stays there as the next step's
    input, so the loop dispatches step n+1 before it reads step n's ids:
    the device never waits for the host to learn a token. A reply that
    ends by length gives up its slot before its last step has run; an EOS
    is learnt one step late, and the lane-step it cost is dropped.

    What differs between runs is an argument of a program, never a
    constant of it: the persistent compile cache hands a replica every
    program whatever `seed` it was started with.
    """

    @tracing.startup_span("engine.init")
    def __init__(self, preset: str = "gpt2-tiny", max_batch: int = 4,
                 max_seq_len: int = 128, seed: int = 0,
                 model_overrides: Optional[dict] = None,
                 checkpoint: Optional[str] = None,
                 tokenizer: Any = None,
                 enable_prefix_caching: bool = True,
                 kv_blocks: int = 64, kv_block_size: int = 16,
                 tensor_parallel_size: int = 1,
                 scheduler: str = "continuous",
                 prefill_chunk_size: int = 16,
                 max_num_batched_tokens: Optional[int] = None,
                 params_override=None, cfg_override=None,
                 weights_id: Optional[str] = None,
                 weight_store: bool = True):
        if scheduler != "continuous":
            raise ValueError(
                f'scheduler must be "continuous", got {scheduler!r}')
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import serving_family
        from ray_tpu.utils.platform import watch_compiles

        self._compile_watch = watch_compiles()

        def stage(name: str, since: float, **attributes) -> float:
            """Closes the start-up span `engine.<name>`; returns the next
            stage's start. The first three close when the host has
            dispatched their work, and only the last waits for the device:
            a wait between them changes the order in which the weights,
            the cache and the pool are allocated, and with it the decode
            step's time on the chip (+1.9%, PERF.md section 6, PR 35)."""
            now = time.time()
            tracing.record_startup(f"engine.{name}", since, now, **attributes)
            return now

        t_stage = time.time()
        self.family, model, config_cls = serving_family(preset)
        self.jax, self.jnp, self.model = jax, jnp, model
        if checkpoint:
            _require_gpt2(self.family, "checkpoint=")
        if tensor_parallel_size > 1:
            _require_gpt2(self.family, "tensor_parallel_size > 1")
        self.tensor_parallel_size = tensor_parallel_size
        overrides = dict(model_overrides or {})
        overrides.setdefault("max_seq_len", max_seq_len)
        if params_override is not None:
            # LoRA-merged (or otherwise prepared) weights from the caller.
            # The architecture must describe THOSE weights: callers that
            # derived them from a checkpoint-loaded base pass the base's
            # resolved cfg (re-deriving from the preset would mismatch
            # when the checkpoint's architecture differs — ADVICE r5).
            self.cfg = (cfg_override if cfg_override is not None
                        else config_cls.preset(preset, **overrides))
            source = params_override
            self.checkpoint = checkpoint
            weights_from = "caller"
        elif checkpoint:
            # REAL weights: architecture from the checkpoint sidecar,
            # runtime knobs (seq len etc.) from the preset/overrides.
            # Cold start tries the P2P weight plane FIRST — the manifest
            # resolves from the gossiped directory (zero head RPCs) and
            # the leaves stream from peer replicas under a bounded host
            # budget (serve/weight_store.py) — and degrades to the
            # central checkpoint-path read on any miss. The replica that
            # pays the path read publishes the tree back, so the NEXT
            # replica of this model pulls from peers.
            import time as _time

            base = config_cls.preset(preset, **overrides)
            source = None
            t0 = _time.perf_counter()
            if weight_store:
                try:
                    from ray_tpu.serve import weight_store as _ws

                    store = _ws.get_store()
                    loaded = (store.load_params(checkpoint, base_cfg=base)
                              if store is not None else None)
                    if loaded is not None:
                        source, self.cfg = loaded
                        weights_from = "peers"
                        _ws.observe_cold_start(
                            _time.perf_counter() - t0, "p2p")
                except Exception:
                    source = None   # never fail init on the store
            if source is None:
                source, self.cfg = model.load_params(checkpoint, cfg=base)
                weights_from = "checkpoint"
                if weight_store:
                    from ray_tpu.serve import weight_store as _ws

                    _ws.observe_cold_start(
                        _time.perf_counter() - t0, "checkpoint")
                    _ws.maybe_publish_params_async(
                        source, checkpoint,
                        arch={k: getattr(self.cfg, k)
                              for k in model._CFG_FIELDS})
            self.checkpoint = checkpoint
        else:
            self.cfg = config_cls.preset(preset, **overrides)
            source = model.init_params(jax.random.key(seed), self.cfg)
            self.checkpoint = None
            weights_from = "seed"
        t_stage = stage("weights", t_stage, preset=preset,
                        source=weights_from)
        # the replica's one copy of the weights on the device, and what
        # `_step` and `_chunk_step` take: converted once, here, to what the
        # step programs read (the model's `resident_params`). What was made,
        # loaded or handed over goes with this frame; the weight store
        # published the loader's tree, so a puller converts after loading
        # as well
        self.params = model.resident_params(source, self.cfg)
        del source
        t_stage = stage("resident", t_stage)
        # weight identity for the cluster prefix store: engines whose KV
        # is interchangeable must agree on it. Checkpoint path or
        # preset+seed derive it; params_override callers (LoRA adapters)
        # pass the BASE engine's id explicitly so adapters share
        # base-model prefix entries — an override without one gets a
        # unique id, which can never collide into a wrong-KV hit.
        if weights_id is not None:
            self.weights_id = weights_id
        elif params_override is not None:
            self.weights_id = f"override-{uuid.uuid4().hex[:12]}"
        else:
            self.weights_id = checkpoint or f"{preset}@seed{seed}"
        self.max_batch = max_batch
        # serving window: the caller's bound caps KV-cache memory even
        # when a checkpoint's architecture allows a longer context (the
        # sidecar must win for PARAM shapes, never for cache sizing)
        self.max_seq_len = min(max_seq_len, self.cfg.max_seq_len)
        self.cache = model.init_cache(self.cfg, max_batch, self.max_seq_len)
        cfg = self.cfg
        # the family's word on its cache (`models/__init__.py`): leaves with
        # a value a token, leaves that are a slot's recurrent state, or both
        self._state_leaves = tuple(getattr(model, "CACHE_STATE", ()))
        # what a token leaves in the cache, all layers, and what a slot's
        # state holds: two gauges, each 0 for a family without that kind
        self.kv_bytes_per_token = sum(
            self.cache[name].nbytes for name in model.CACHE_TOKEN_AXIS
        ) // (max_batch * self.max_seq_len)
        self.state_bytes_per_slot = sum(
            self.cache[name].nbytes for name in self._state_leaves
        ) // max_batch
        # paged prefix cache: shared-prompt requests skip prefill for the
        # cached span (reference: vLLM prefix caching behind serve.llm)
        self.kv = None
        if enable_prefix_caching:
            from ray_tpu.serve.kv_cache import PagedKVCache

            self.kv = PagedKVCache.for_cache(
                self.cache, model.CACHE_TOKEN_AXIS, num_blocks=kv_blocks,
                block_size=kv_block_size, state=self._state_leaves)
        t_stage = stage("cache", t_stage)

        # chunk must fit the serving window (prefill_chunk requires C <= T)
        self.prefill_chunk_size = max(1, min(prefill_chunk_size,
                                             self.max_seq_len - 1))
        from ray_tpu.models import lm

        # the default budget is the rows of the call a chunk program's
        # token-wise halves take a step's lanes in
        self._lanes_a_dispatch = lm.lanes_a_dispatch(
            max_batch, self.prefill_chunk_size)
        self.max_num_batched_tokens = (max_num_batched_tokens
                                       or self._lanes_a_dispatch)

        from ray_tpu.serve.sampling import select_tokens

        def _step(params, cache, tokens, pos, active):
            return model.decode_step(params, cache, tokens, pos, active, cfg)

        def _chunk(params, cache, tokens, pos0, length, active):
            return model.prefill_chunk(params, cache, tokens, pos0, length,
                                       active, cfg)

        def _select(logits, prev, produce, sampling, step, key):
            temperature, top_k, top_p = sampling
            return select_tokens(logits, prev, produce, temperature,
                                 top_k.astype(jnp.int32), top_p,
                                 jax.random.fold_in(key, step))

        def _reset(cache, slot):
            # a new sequence starts from no state: stale rows of a KV slot
            # lie past its position, stale state would be carried on
            with jax.named_scope("kv_update"):
                out = dict(cache)
                for name in self._state_leaves:
                    leaf = cache[name]
                    out[name] = jax.lax.dynamic_update_slice(
                        leaf, jnp.zeros((leaf.shape[0], 1) + leaf.shape[2:],
                                        leaf.dtype),
                        (0, slot) + (0,) * (leaf.ndim - 2))
                return out

        def _merge(tokens, ids, decoding):
            # a chunk step's decode lanes read their token where the last
            # selection left it; prefilling lanes keep the host's prompt
            return tokens.at[:, 0].set(
                jnp.where(decoding, ids, tokens[:, 0]))

        if tensor_parallel_size > 1:
            # TP-sharded engine (reference: vLLM TP workers in a
            # STRICT_PACK PG, `server_models.py:443-461`) — here TP is a
            # mesh axis: params shard by their logical axes, the KV cache
            # shards over heads, XLA inserts the ICI collectives. One
            # process drives all chips (single-controller SPMD).
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ray_tpu.parallel.mesh import (MeshConfig, build_mesh,
                                               traced_on, use_mesh)

            mesh = build_mesh(
                MeshConfig(tp=tensor_parallel_size),
                devices=jax.devices()[:tensor_parallel_size])
            self.mesh = mesh
            with use_mesh(mesh):
                pspecs = model.resident_specs(cfg)
            param_sh = jax.tree.map(
                lambda s: NamedSharding(mesh, s), pspecs)
            self.params = jax.tree.map(jax.device_put, self.params,
                                       param_sh)
            # KV cache [L, B, H, T, Dh]: shard attention heads over tp
            cache_sh = NamedSharding(mesh, P(None, None, "tp", None, None))
            self.cache = jax.tree.map(
                lambda a: jax.device_put(a, cache_sh), self.cache)
            rep = NamedSharding(mesh, P())
            self._step = jax.jit(  # its Pallas call takes a shard each
                traced_on(mesh, _step), donate_argnums=(1,),
                in_shardings=(param_sh, {"k": cache_sh, "v": cache_sh},
                              rep, rep, rep),
                out_shardings=(rep, {"k": cache_sh, "v": cache_sh}))
            self._chunk_step = jax.jit(
                _chunk, donate_argnums=(1,),
                in_shardings=(param_sh, {"k": cache_sh, "v": cache_sh},
                              rep, rep, rep, rep),
                out_shardings=(rep, {"k": cache_sh, "v": cache_sh}))
            # the ids are replicated, as the logits they are chosen from
            self._select = jax.jit(_select, out_shardings=rep)
            self._merge = jax.jit(_merge, out_shardings=rep)
        else:
            self.mesh = None
            rep = None
            self._step = jax.jit(_step, donate_argnums=(1,))
            self._chunk_step = jax.jit(_chunk, donate_argnums=(1,))
            self._select = jax.jit(_select)
            self._merge = jax.jit(_merge)
        self._reset_slot = jax.jit(_reset, donate_argnums=(0,))
        # each slot's newest token, where the selection left it: the next
        # step's decode lanes read it there, the host reads it a step late
        self._ids = jax.device_put(np.zeros((max_batch,), np.int32), rep)
        # the selection's base key, resident beside them: each step folds
        # its number into it on the device
        self._key = jax.device_put(jax.random.key(seed), rep)
        # weights and cache laid over the tensor-parallel mesh (one chip:
        # nothing moves), the step programs' wrappers made, and the
        # constructor's one wait for the device: whatever the stages
        # above left running there (`device_wait_s`)
        t_wait = time.time()
        jax.block_until_ready(
            (self.params, self.cache, self._ids, self._key))
        stage("place", t_stage, tensor_parallel_size=tensor_parallel_size,
              device_wait_s=time.time() - t_wait)
        # rows temperature, top_k, top_p of each slot's request
        self._sampling = np.zeros((3, max_batch), np.float32)
        self.tokenizer = tokenizer if tokenizer is not None else ByteTokenizer()

        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._streams: Dict[str, tuple] = {}   # sid -> (request, last_access)
        self._slots: List[Optional[_Request]] = [None] * max_batch
        self._slot_pos = [0] * max_batch
        self._slot_prefill: List[List[int]] = [[] for _ in range(max_batch)]
        # a family with state: the position at which each slot's state (and
        # the rows up to it, where the cache has both) is to be pooled,
        # between the chunk step that ends there and the next (0: nowhere).
        # The prompt's last whole block, as the pool's rows
        self._slot_snapshot_at = [0] * max_batch
        # async prefill fetch: requests whose KV blob is still in flight
        # park here (other lanes keep decoding); resolved ones re-enter
        # admission ahead of the queue
        self._deferred: List[_Request] = []
        self._ready: List[_Request] = []
        # a family of state: the boundaries at which live slots will pool
        # their snapshot (boundary's chain hash -> slot), and the requests
        # that wait for one of them (`_waits_for_prefix`)
        self._prefix_in_flight: Dict[bytes, int] = {}
        self._parked: List[_Request] = []
        self._stop = threading.Event()
        self.total_generated = 0
        self.engine_steps = 0          # jitted step calls (either kind)
        # steps dispatched while the step before them was still unread
        self.steps_dispatched_ahead = 0
        # lane-steps run for a request its EOS had already ended
        self.overrun_lane_steps = 0
        self.chunk_steps = 0           # steps that ran the chunked program
        # what the plan handed those steps: the lanes that were a token's,
        # and the slots that had more than one (a family whose chunk program
        # computes a slot's further lanes only where there are any ran
        # chunk_steps x B + chunk_prefilling_slots x (C - 1) lanes for them,
        # one that computes every lane chunk_steps x B x C)
        self.chunk_tokens = 0
        self.chunk_prefilling_slots = 0
        # the chunk steps whose further lanes rode the first lanes' call
        # through a layer's token-wise half, one round (`lm.lane_rounds`),
        # and the further lanes those steps carried
        self.chunk_steps_one_dispatch = 0
        self.chunk_lanes_packed = 0
        self.tokens_prefilled = 0      # prompt tokens processed
        # positions the steps' lanes attended over: each lane's last
        # position in its step, summed (what a cache of rows is read for)
        self.positions_attended = 0
        # positions whose rows the steps read for them, where the family
        # names the block its decode step's attention reads a slot's rows by
        # (`rows_read_block` of its module; 0 without, and nothing counted):
        # a decode lane's positions rounded up to a block, as
        # `ops/slot_rows.read_positions` has it; all T a lane of a chunk step
        read_block = getattr(model, "rows_read_block", None)
        self._rows_read_block = read_block(self.cache) if read_block else 0
        self.positions_read = 0
        self.prefix_imports = 0        # deferred blobs installed
        self.prefix_blocks_imported = 0
        self.prefix_wait_timeouts = 0  # deadline hit: local prefill
        self.slots_reset = 0           # slots zeroed for a new sequence
        self.snapshots_pooled = 0      # states copied into the pool
        self.snapshot_hits = 0         # requests that started from one
        # cumulative, so a reader takes deltas over its own window
        self._phases = _Phases()
        self.loop_busy_s = 0.0         # loop passes that ran a step
        # passes longer than SLOW_PASS_S: how many, their seconds, and the
        # newest sixteen by name (`_keep_slow_pass`)
        self.slow_passes = {"count": 0, "seconds": 0.0}
        self._slow_passes_kept: deque = deque(maxlen=16)
        # request lifecycle: observations and their sum, in seconds
        self._stats_lock = threading.Lock()
        self.lifecycle = {"queue_wait_s": {"count": 0, "sum": 0.0},
                          "ttft_s": {"count": 0, "sum": 0.0}}
        # callables other threads need run ON the engine thread (the KV
        # pool is engine-owned, unlocked state: exports must not race
        # _alloc's block eviction/reuse)
        self._engine_calls: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._engine_loop, daemon=True,
                                        name="llm-engine")
        self._thread.start()

    # ------------------------------------------------------------- public
    @property
    def prefix_model_key(self) -> Optional[str]:
        """Cluster prefix-store key: engines with interchangeable KV
        (same weights + cache geometry) agree; anything else differs."""
        if self.kv is None:
            return None
        _require_gpt2(self.family, "the cluster prefix store")
        from ray_tpu.serve.prefix_store import model_cache_key

        cfg = self.cfg
        return model_cache_key(self.weights_id, cfg.n_layer, cfg.n_head,
                               cfg.head_dim, self.jnp.dtype(cfg.dtype).name,
                               self.kv.block_size)

    def generate(self, prompt: str = "", prompt_ids: Optional[List[int]] = None,
                 max_tokens: int = 16, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 timeout: float = 120.0, prefix_future=None,
                 prefix_wait_s: float = 30.0) -> Dict[str, Any]:
        req = self._make_request(prompt, prompt_ids, max_tokens,
                                 temperature, top_k, top_p,
                                 prefix_future=prefix_future,
                                 prefix_wait_s=prefix_wait_s)
        ids = req.prompt_ids
        self._queue.put(req)
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if req.error:
            raise RuntimeError(req.error)
        return {"token_ids": req.generated,
                "text": self.tokenizer.decode(req.generated),
                "prompt_tokens": len(ids),
                "completion_tokens": len(req.generated)}

    # ----------------------------------------------------------- streaming
    def _make_request(self, prompt, prompt_ids, max_tokens, temperature,
                      top_k, top_p, prefix_future=None,
                      prefix_wait_s: float = 30.0) -> "_Request":
        ids = prompt_ids if prompt_ids is not None else \
            self.tokenizer.encode(prompt)
        ids = ids or [self.tokenizer.eos_id]
        ids = ids[-(self.max_seq_len - 2):]
        budget = self.max_seq_len - len(ids) - 1
        return _Request(ids, max(0, min(max_tokens, budget)), temperature,
                        top_k=top_k, top_p=top_p,
                        prefix_future=prefix_future,
                        prefix_wait_s=prefix_wait_s)

    def start_stream(self, prompt: str = "",
                     prompt_ids: Optional[List[int]] = None,
                     max_tokens: int = 16, temperature: float = 0.0,
                     top_k: int = 0, top_p: float = 1.0,
                     prefix_future=None,
                     prefix_wait_s: float = 30.0) -> str:
        """Admit a request for incremental consumption via stream_next
        (the engine path behind OpenAI `stream: true`)."""
        req = self._make_request(prompt, prompt_ids, max_tokens,
                                 temperature, top_k, top_p,
                                 prefix_future=prefix_future,
                                 prefix_wait_s=prefix_wait_s)
        sid = uuid.uuid4().hex
        self._streams[sid] = (req, time.time())
        self._queue.put(req)
        return sid

    def stream_next(self, stream_id: str, cursor: int = 0,
                    timeout: float = 1.0) -> Dict[str, Any]:
        """Tokens generated beyond `cursor`. Waits briefly (bounded: a
        long block would pin a replica actor thread per queued stream
        and starve health checks); an empty delta means "poll again".
        `text` is the CUMULATIVE decode — a per-batch decode would split
        multi-byte characters across chunk boundaries; consumers diff
        against their previous cumulative text. The stream entry is
        dropped once the consumer has read to the end."""
        ent = self._streams.get(stream_id)
        if ent is None:
            raise KeyError(f"unknown stream {stream_id}")
        req, _ = ent
        self._streams[stream_id] = (req, time.time())
        deadline = time.time() + timeout
        with req.progress:
            while (len(req.generated) <= cursor and not req.done.is_set()
                   and req.error is None):
                left = deadline - time.time()
                if left <= 0:
                    break
                req.progress.wait(left)
        if req.error:
            self._streams.pop(stream_id, None)
            return {"error": req.error, "done": True, "token_ids": [],
                    "text": "", "cursor": cursor}
        new = req.generated[cursor:]
        done = req.done.is_set() and cursor + len(new) >= len(req.generated)
        if done:
            self._streams.pop(stream_id, None)
        # delta computed HERE from the cumulative decode (multi-byte
        # characters must not split across chunk boundaries), decoded
        # only when tokens actually advanced — no per-poll O(L) work and
        # no cumulative string shipped per RPC
        delta = ""
        if new or done:
            full = self.tokenizer.decode(req.generated[:cursor + len(new)])
            if not done and full.endswith("\ufffd"):
                # trailing partial multi-byte sequence: hold it back until
                # its continuation bytes arrive
                full = full[:-1]
            delta = (full[len(req._sent_text):]
                     if full.startswith(req._sent_text) else full)
            req._sent_text = full
        return {"token_ids": new, "text": delta,
                "done": done, "cursor": cursor + len(new),
                "finish_reason": req.finish_reason if done else None}

    # --------------------------------------------- KV transfer (prefill/decode)
    def export_prefix(self, prompt: str = "",
                      prompt_ids: Optional[List[int]] = None):
        """Disaggregated serving, prefill side: run (or reuse) the
        prompt's prefill, then hand back a host blob of its pooled KV
        blocks for a DECODE engine to import (reference KV-transfer
        connectors: nixl/lmcache behind serve.llm)."""
        if self.kv is None:
            raise RuntimeError("prefix caching disabled: no KV to export")
        _require_gpt2(self.family, "disaggregated export")
        ids = prompt_ids if prompt_ids is not None else \
            self.tokenizer.encode(prompt)
        ids = ids[-(self.max_seq_len - 2):]
        blob = self.export_pooled(ids[:-1])
        if blob is None or len(blob["ids"]) < len(ids) - 1 - \
                (len(ids) - 1) % self.kv.block_size:
            # not pooled yet: run the prefill (generate 1 token) which
            # publishes the prompt's blocks, then export
            self.generate(prompt_ids=ids, max_tokens=1)
            blob = self.export_pooled(ids[:-1])
        return blob

    def export_pooled(self, ids: List[int], timeout: float = 30.0):
        """Export `ids`' pooled KV blocks ON the engine thread. The pool
        is unlocked engine-owned state: an export racing `_alloc`'s block
        eviction/reuse could copy another request's bytes under this
        prompt's content hash, so off-thread callers marshal through the
        engine-call queue. Falls back to a direct (pre-PR-13-semantics)
        export if the engine thread is wedged past `timeout`."""
        from concurrent.futures import TimeoutError as _FutTimeout

        from ray_tpu.serve.kv_cache import export_prefix as _export

        try:
            return self._on_engine_thread(
                lambda: _export(self.kv, list(ids)), timeout)
        except _FutTimeout:
            return _export(self.kv, list(ids))

    def _on_engine_thread(self, fn, timeout: float):
        """`fn()` run on the engine's thread between two passes (what it
        owns unlocked, the pool and the donated cache, cannot change under
        it there); here and now when this is that thread or it has ended.
        Raises `concurrent.futures.TimeoutError` when the loop is wedged."""
        if (threading.current_thread() is self._thread
                or not self._thread.is_alive()):
            return fn()
        from concurrent.futures import Future

        fut: Future = Future()

        def _do():
            try:
                fut.set_result(fn())
            except BaseException as e:   # engine thread must survive
                fut.set_exception(e)

        self._engine_calls.put(_do)
        return fut.result(timeout=timeout)

    def import_prefix(self, blob) -> int:
        """Decode side: install a prefill replica's exported KV blocks;
        subsequent matching prompts skip prefill for the covered span."""
        if self.kv is None:
            raise RuntimeError("prefix caching disabled: no KV to import")
        _require_gpt2(self.family, "disaggregated import")
        from ray_tpu.serve.kv_cache import import_prefix as _import

        return _import(self.kv, blob)

    def shutdown(self):
        self._stop.set()

    # ------------------------------------------------------------- engine
    def _admit(self):
        self._admit_deferred()
        for i in range(self.max_batch):
            if self._slots[i] is None:
                req = self._next_ready()
                while req is not None and self._waits_for_prefix(req):
                    self._parked.append(req)
                    req = self._next_ready()
                if req is None:
                    return
                self._place(i, req)

    def _waits_for_prefix(self, req: _Request) -> bool:
        """Whether a live slot is on its way to pooling a snapshot at a
        boundary of this prompt that the pool has no snapshot at yet: the
        request then waits for it and is a hit, where it would prefill the
        same prefix beside it. Many clients that start at once over a few
        shared preambles would each prefill their preamble, 128 slots x
        1,700 tokens a chunk step or two at a time (PERF.md, PR 53); one a
        preamble does, and the snapshot it leaves serves the rest."""
        if not self._prefix_in_flight:
            return False
        chain, hit, _ = self.kv.pooled_to(req.prompt_ids[:-1])
        return any(n > hit and h in self._prefix_in_flight
                   for h, n in chain)

    def _prefix_landed(self, i: int) -> None:
        """Slot i's snapshot is pooled (or never will be): whoever waited
        for it goes back to admission, ahead of the queue."""
        landed = [h for h, slot in self._prefix_in_flight.items()
                  if slot == i]
        for h in landed:
            del self._prefix_in_flight[h]
        if landed and self._parked:
            self._ready[:0], self._parked = self._parked, []

    def _next_ready(self) -> Optional[_Request]:
        """Next admittable request: resolved deferred requests first,
        then the queue. A queued request whose KV blob fetch is still in
        flight parks in `_deferred` (its slot goes to the next request —
        other lanes decode while the blob crosses the network) instead
        of blocking admission."""
        while True:
            if self._ready:
                return self._ready.pop(0)
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return None
            fut = req.prefix_future
            if fut is not None and not fut.done() \
                    and time.time() < req.prefix_deadline:
                self._deferred.append(req)
                continue
            self._resolve_prefix(req)
            return req

    def _admit_deferred(self) -> None:
        """Re-admit parked requests whose blob landed (import happens
        HERE, on the engine thread — the KV pool is engine-owned state)
        or whose wait deadline passed (degrade to local prefill)."""
        if not self._deferred:
            return
        now = time.time()
        still: List[_Request] = []
        for req in self._deferred:
            fut = req.prefix_future
            if fut is not None and not fut.done() \
                    and now < req.prefix_deadline:
                still.append(req)
                continue
            self._resolve_prefix(req)
            self._ready.append(req)
        self._deferred = still

    def _resolve_prefix(self, req: _Request) -> None:
        fut, req.prefix_future = req.prefix_future, None
        if fut is None:
            return
        blob = None
        if fut.done():
            try:
                blob = fut.result()
            except Exception:
                blob = None
        else:
            fut.cancel()    # deadline passed: prefill locally instead
            self.prefix_wait_timeouts += 1
        if blob and self.kv is not None:
            try:
                installed = self.import_prefix(blob)
                self.prefix_imports += 1
                self.prefix_blocks_imported += installed
            except Exception:
                pass        # bad blob: local prefill is always correct

    def _place(self, i: int, req: _Request) -> None:
        req.t_admit = time.time()
        self._observe("queue_wait_s", req.t_admit - req.t_enqueue)
        self._slots[i] = req
        self._slot_pos[i] = 0
        self._slot_prefill[i] = list(req.prompt_ids)
        # a new array, not a write into the old one: the transfer of a
        # dispatched step's arguments may still be reading that
        self._sampling = self._sampling.copy()
        self._sampling[:, i] = req.temperature, req.top_k, req.top_p
        if self.kv is not None and len(req.prompt_ids) > 1:
            # the last prompt token is always re-run (its logits
            # seed generation), so match against ids[:-1]
            n_hit, blocks = self.kv.match_prefix(
                req.prompt_ids[:-1])
            if n_hit:
                self.cache = self.kv.copy_into_slot(
                    self.cache, i, blocks)
                self._slot_pos[i] = n_hit
                self._slot_prefill[i] = list(
                    req.prompt_ids[n_hit:])
                req.reused_tokens = n_hit
        if self._state_leaves:
            self._place_state(i, req)

    def _place_state(self, i: int, req: _Request) -> None:
        """A slot of recurrent state takes a new sequence: the snapshot the
        pool had (copied over the whole state above) or zeros; and where
        its own snapshot is due, if the pool lacks it: at the prompt's last
        whole block, or, where the pool holds the prompt's first rows from
        another request without a snapshot at their end, at that end. The
        rows say how far the prefix is shared; a prompt whose own part is
        longer than a block (an agent's task after a pooled preamble) would
        leave every snapshot inside that part, where no other request finds
        it, and the shared prefix would never be a hit (PERF.md, PR 53)."""
        if req.reused_tokens:
            self.snapshot_hits += 1
        else:
            self.cache = self._reset_slot(self.cache, np.int32(i))
            self.slots_reset += 1
        self._slot_snapshot_at[i] = 0
        self._prefix_landed(i)
        if self.kv is not None:
            block = self.kv.block_size
            boundary = (len(req.prompt_ids) - 1) // block * block
            if self.kv.both:
                chain, _, rows = self.kv.pooled_to(req.prompt_ids[:-1])
                if rows > req.reused_tokens:
                    boundary = rows
            if boundary > req.reused_tokens:
                self._slot_snapshot_at[i] = boundary
                if self.kv.both:
                    self._prefix_in_flight[chain[boundary // block - 1][0]] = i

    def _sweep_streams(self) -> None:
        """Expire abandoned stream entries (client vanished): the sweep
        must not depend on some OTHER stream being polled."""
        now = time.time()
        for sid, (r, ts) in list(self._streams.items()):
            if r.done.is_set() and now - ts > 300:
                self._streams.pop(sid, None)

    def _engine_loop(self):
        """Runs one step ahead of what it has read: a pass admits, plans
        and dispatches step n+1 from what the host already knows (which
        slots are live, their positions, which end by length), and only
        then reads step n's ids. The tokens themselves stay on the device
        (`self._ids`), so nothing the next step needs waits for the host.

        A pass runs from one opening of `calls` to the next, and every
        moment of it lies in one phase (`_Phases`)."""
        phases = self._phases
        phases.start("release")
        last_sweep = t_pass = phases.t
        unread = None         # the step dispatched and not read yet
        busy = False          # the pass ran or read a step
        mark = self._pass_mark()
        while not self._stop.is_set():
            now = phases.to("calls")
            took = now - t_pass       # the pass that ended at this reading
            if busy:
                self.loop_busy_s += took
            if took > SLOW_PASS_S:
                self._keep_slow_pass(took, mark)
            t_pass, mark = now, self._pass_mark()
            # marshalled work (KV exports) runs between steps: the pool
            # can't mutate under an export that shares this thread
            for _ in range(8):
                try:
                    fn = self._engine_calls.get_nowait()
                except queue.Empty:
                    break
                try:
                    fn()
                except Exception:
                    pass
            phases.to("admit")
            self._admit()
            step = self._dispatch_step()
            if unread is not None:
                if step is not None:
                    self.steps_dispatched_ahead += 1
                self._read_step(*unread)
            elif step is None:
                phases.to("empty")
                time.sleep(0.005)
            # the pass's tail: `unread = step` lets the step before's
            # arrays go, and the loop's turn is inside the phase too
            now = phases.to("release")
            if now - last_sweep > 60:
                last_sweep = now
                self._sweep_streams()
            busy = step is not None or unread is not None
            unread = step

    def _pass_mark(self) -> tuple:
        """What a pass keeps of its start so that `_keep_slow_pass` can say
        what moved during it: the phases' wall seconds, the thread's CPU
        clock, and four counts."""
        return (tuple(self._phases.wall.values()), self._phases.c,
                self._compile_watch.count,
                self.lifecycle["queue_wait_s"]["count"], self.chunk_steps)

    def _keep_slow_pass(self, wall_s: float, mark: tuple) -> None:
        """The pass that just ended took longer than SLOW_PASS_S: count it
        and keep its record among the newest. `t_end` is on `time.time()`,
        the clock of a client's log, so a gap in which no client got a
        token can be laid beside it; `step` is `engine_steps` at its end,
        so a reader keeps those of its own window."""
        wall0, cpu0, compiles0, admitted0, chunk_steps0 = mark
        phases = self._phases
        self.slow_passes["count"] += 1
        self.slow_passes["seconds"] += wall_s
        self._slow_passes_kept.append({
            "step": self.engine_steps, "t_end": time.time(),
            "wall_s": wall_s, "cpu_s": phases.c - cpu0,
            "phases": {k: v - v0 for (k, v), v0
                       in zip(phases.wall.items(), wall0) if v - v0 > 0.001},
            "live": sum(r is not None for r in self._slots),
            "admitted": self.lifecycle["queue_wait_s"]["count"] - admitted0,
            "chunked": self.chunk_steps > chunk_steps0,
            "compiles": self._compile_watch.count - compiles0})

    def _dispatch_step(self):
        """Plan and dispatch one step for every live slot, then the
        selection of its tokens; nothing here waits for the device.
        Returns (ids, lanes, prompts) for `_read_step`, or None when no
        slot is live. `lanes` are the (slot, request, ends by length)
        whose logits are a token's. A request that ends by length leaves
        its slot here, before the step has run: the next pass admits into
        it. `prompts` are the (prompt ids, slot) this step finishes
        prefilling and whose blocks `_read_step` pools.

        With a prompt left in any live slot this is a token-budget step
        of the chunked program (decode lanes reserved first, one token
        each; prefilling lanes up to a chunk of their prompt), else one
        single-token step of the decode program."""
        jnp, phases = self.jnp, self._phases
        B, C = self.max_batch, self.prefill_chunk_size
        phases.to("plan")
        live = [i for i, r in enumerate(self._slots) if r is not None]
        if not live:
            return None
        pos = np.asarray(self._slot_pos, np.int32)
        decoding = np.zeros((B,), bool)
        for i in live:
            decoding[i] = not self._slot_prefill[i]
        chunked = not decoding[live].all()
        if chunked:
            pending = [len(self._slot_prefill[i])
                       if self._slots[i] is not None else 0
                       for i in range(B)]
            takes = plan_chunk_budget(pending, list(decoding), C,
                                      self.max_num_batched_tokens)
            tokens = np.zeros((B, C), np.int32)
            lengths = np.zeros((B,), np.int32)
            for i in live:
                # never step past the serving window (prefill_chunk
                # requires pos0 + length <= T; _make_request already
                # bounds prompts)
                take = min(takes[i], self.max_seq_len - self._slot_pos[i])
                # a chunk ends where the slot's state is to be pooled
                due = self._slot_snapshot_at[i] - self._slot_pos[i]
                if due > 0:
                    take = min(take, due)
                if take <= 0:
                    continue
                lengths[i] = take
                if not decoding[i]:
                    tokens[i, :take] = self._slot_prefill[i][:take]
            active = lengths > 0
            self.chunk_tokens += int(lengths.sum())
            self.chunk_prefilling_slots += int((lengths > 1).sum())
            further = int(np.maximum(lengths - 1, 0).sum())
            if B + further <= self._lanes_a_dispatch:
                self.chunk_steps_one_dispatch += 1
                self.chunk_lanes_packed += further
        else:
            lengths = active = decoding
        if self._rows_read_block:
            n, T = self._rows_read_block, self.max_seq_len
            self.positions_read += int(np.where(
                active, T if chunked else np.minimum(
                    (np.minimum(pos, T - 1) // n + 1) * n, T), 0).sum())
        lanes, prompts, last_prompts, snapshots = [], [], [], []
        produce = np.zeros((B,), bool)
        for i in live:
            take = int(lengths[i])
            if take <= 0:
                continue
            req = self._slots[i]
            self._slot_pos[i] += take
            self.positions_attended += self._slot_pos[i]
            if self._slot_pos[i] == self._slot_snapshot_at[i]:
                snapshots.append((req.prompt_ids[:self._slot_pos[i]], i))
                self._slot_snapshot_at[i] = 0
            if not decoding[i]:
                del self._slot_prefill[i][:take]
                self.tokens_prefilled += take
                if self._slot_prefill[i]:
                    continue  # chunk didn't cover the prompt yet
            # the chunk ends at the prompt's final token (or this is a
            # decode lane): its last-position logits are a token's
            produce[i] = True
            req.scheduled += 1
            ends = (req.scheduled >= req.max_tokens
                    or self._slot_pos[i] >= self.max_seq_len - 1)
            if ends:
                self._slots[i] = None
            if not decoding[i]:
                # pooled while the request holds the slot: when its
                # first token is read, or here if this step is its last
                (last_prompts if ends else prompts).append(
                    (req.prompt_ids, i))
            lanes.append((i, req, ends))
        # the step's own host arrays go over under their own phase, in the
        # order the programs take them; `sample`'s ride its call
        phases.to("put")
        pos = jnp.asarray(pos)
        if chunked:
            lengths = jnp.asarray(lengths)
        active = jnp.asarray(active)
        phases.to("dispatch")
        if chunked:
            logits, self.cache = self._chunk_step(
                self.params, self.cache,
                self._merge(tokens, self._ids, decoding), pos, lengths,
                active)
            self.chunk_steps += 1
        else:
            logits, self.cache = self._step(
                self.params, self.cache, self._ids, pos, active)
        phases.to("sample")
        self._ids = self._select(
            logits, self._ids, produce, self._sampling,
            np.uint32(self.engine_steps), self._key)
        self.engine_steps += 1
        if snapshots:
            # behind the chunk step that brought each slot to its boundary
            # and ahead of the next, which moves the state on: the device
            # keeps that order, and the state exists at no other time
            phases.to("publish")
            for ids, i in snapshots:
                self.snapshots_pooled += self.kv.store_prefix(
                    ids, self.cache, i)
                self._prefix_landed(i)
        self._pool_prompts(last_prompts)
        return self._ids, lanes, prompts

    def _pool_prompts(self, prompts):
        """Copy each prefilled prompt's blocks from its slot into the
        prefix pool: dispatched behind the step that wrote the rows and
        ahead of any that rewrites them; the device keeps that order. A
        family with state has pooled what it can by now: its snapshot, and
        with it the rows up to the snapshot's boundary, between two chunk
        steps (rows past a snapshot are no hit, so none are pooled here)."""
        if prompts and self.kv is not None and not self._state_leaves:
            self._phases.to("publish")
            for prompt_ids, i in prompts:
                self.kv.store_prefix(prompt_ids, self.cache, i)

    def _read_step(self, ids, lanes, prompts):
        """Read a dispatched step's ids (the wait for the device is here),
        append each lane's token and retire what ended. An EOS is learnt
        only now, with the next step already dispatched: that step's lane
        for the request is an overrun, and its token is dropped here.

        Only then are the step's finished prompts pooled. A prompt's
        copies into the pool are tens of programs: dispatched with the
        step they would hold the host, and the device, between the first
        token and its reader. Each request still holds its slot here."""
        self._phases.to("fetch")
        ids = np.asarray(ids)
        self._phases.to("notify")
        for i, req, ends in lanes:
            if req.done.is_set():
                self.overrun_lane_steps += 1
                continue
            nxt = int(ids[i])
            if req.t_first is None:
                req.t_first = time.time()
                self._observe("ttft_s", req.t_first - req.t_enqueue)
            req.generated.append(nxt)
            self.total_generated += 1
            stop = nxt == self.tokenizer.eos_id
            if stop or ends:
                req.finish_reason = "stop" if stop else "length"
                if self._slots[i] is req:
                    self._slots[i] = None
                req.t_done = time.time()
                if req.trace_carrier is not None:
                    self._record_request_spans(req)
                req.done.set()
            with req.progress:
                req.progress.notify_all()
        self._pool_prompts(prompts)

    @staticmethod
    def _record_request_spans(req: _Request) -> None:
        """A finished request's three stretches as children of the span
        its caller was in: a client's `traceparent` follows the request
        to its last token."""
        attrs = {"prompt_tokens": len(req.prompt_ids),
                 "reused_tokens": req.reused_tokens,
                 "generated": len(req.generated)}
        for name, t0, t1 in (
                ("engine.queue_wait", req.t_enqueue, req.t_admit),
                ("engine.prefill", req.t_admit, req.t_first),
                ("engine.decode", req.t_first, req.t_done)):
            tracing.record_span(name, t0, t1, carrier=req.trace_carrier,
                                attributes=attrs)

    def _observe(self, name: str, seconds: float) -> None:
        """One request's `queue_wait_s` or `ttft_s`: into the engine's own
        count and sum, and into the process's histogram for `/metrics`."""
        with self._stats_lock:
            own = self.lifecycle[name]
            own["count"] += 1
            own["sum"] += seconds
        _get_lifecycle_metrics()[name].observe(seconds)

    def _device_counters(self) -> dict:
        """What the step programs count themselves, in a leaf of the cache
        that is no token's (`deepseek.init_cache`'s `counts`): read here
        and only here, on the engine's thread between two passes (the leaf
        is donated to every step), at the cost of waiting for the step in
        flight. `moe_expert_rows` and `moe_experts_touched` sum both
        programs (the rows the experts held here were given and the held
        experts that got one; `moe_expert_rows_all`, where the family
        counts it, every valid lane's pairs, held here or not;
        `positions_indexed` and `rows_selected`, where a learned indexer
        chooses the rows attention reads: each valid lane's pos + 1 in its
        step and its min(pos + 1, topk)), `step_counts` has each program's
        columns. Each is a uint32 that wraps: take differences modulo
        2**32."""
        if "counts" not in self.cache:
            return {}

        from concurrent.futures import TimeoutError as _FutTimeout

        try:
            rows = self._on_engine_thread(
                lambda: np.asarray(self.cache["counts"]).tolist(), 30.0)
        except _FutTimeout:     # a wedged loop: the other stats still go
            return {}
        names = self.model.COUNTS
        by_program = {program: dict(zip(names, row))
                      for program, row in zip(("decode", "chunk"), rows)}
        sums = {"expert_rows": "moe_expert_rows",
                "experts_touched": "moe_experts_touched",
                "expert_rows_all": "moe_expert_rows_all",
                "positions_indexed": "positions_indexed",
                "rows_selected": "rows_selected"}
        return {"step_counts": by_program,
                **{stat: sum(p[k] for p in by_program.values()) % 2 ** 32
                   for k, stat in sums.items() if k in names}}

    def engine_stats(self) -> dict:
        from ray_tpu.utils.platform import device_report

        with self._stats_lock:
            queue_wait = dict(self.lifecycle["queue_wait_s"])
            ttft = dict(self.lifecycle["ttft_s"])
        # the gauges of the cache's kinds: bytes a token, bytes a slot
        kind = {}
        if self.kv_bytes_per_token or not self._state_leaves:
            kind["kv_bytes_per_token"] = self.kv_bytes_per_token
        if self._state_leaves:
            kind.update(state_bytes_per_slot=self.state_bytes_per_slot,
                        slots_reset=self.slots_reset,
                        snapshots_pooled=self.snapshots_pooled,
                        snapshot_hits=self.snapshot_hits)
        if self.kv is not None and self.kv.both:
            # prompt tokens whose rows the pool held and which were
            # prefilled again because no snapshot stood at their boundary
            kind["rows_without_snapshot_tokens"] = \
                self.kv.rows_without_snapshot_tokens
        watch, phases = self._compile_watch, self._phases
        return {**self._device_counters(), **kind,
                # what this engine's process runs JAX on
                "devices": device_report(),
                # programs this process has prepared (compiled or read
                # from the cache) and the newest: a count that rises under
                # load is a shape nobody warmed, and this is its name
                "compiles": watch.count,
                "last_compile": dict(watch.last) if watch.last else None,
                "total_generated": self.total_generated,
                "engine_steps": self.engine_steps,
                "steps_dispatched_ahead": self.steps_dispatched_ahead,
                "overrun_lane_steps": self.overrun_lane_steps,
                "chunk_steps": self.chunk_steps,
                "chunk_tokens": self.chunk_tokens,
                "chunk_prefilling_slots": self.chunk_prefilling_slots,
                "chunk_steps_one_dispatch": self.chunk_steps_one_dispatch,
                "chunk_lanes_packed": self.chunk_lanes_packed,
                "tokens_prefilled": self.tokens_prefilled,
                "positions_attended": self.positions_attended,
                # live positions over the rows the slots hold, a step: what
                # fixed slots of `max_seq_len` rows leave empty (cumulative;
                # a window's is the two counts' differences)
                **({"rows_live_pct": 100.0 * self.positions_attended / (
                    max(self.engine_steps, 1) * self.max_batch
                    * self.max_seq_len)} if self.kv_bytes_per_token else {}),
                **({"positions_read": self.positions_read}
                   if self._rows_read_block else {}),
                "prefix_imports": self.prefix_imports,
                "prefix_blocks_imported": self.prefix_blocks_imported,
                "prefix_wait_timeouts": self.prefix_wait_timeouts,
                "deferred": len(self._deferred),
                # cumulative since the engine started: read deltas. A
                # phase's wall less its CPU seconds is the time the thread
                # was in it and not running
                "phase_s": dict(phases.wall),
                "phase_cpu_s": dict(phases.cpu),
                "loop_busy_s": self.loop_busy_s,
                # CPU seconds, the engine's thread's at its newest phase
                # boundary and the whole process's now: the difference's
                # growth is what the replica's other threads burnt
                "cpu_s": {"engine_thread": phases.c,
                          "process": time.process_time()},
                "slow_passes": {**self.slow_passes,
                                "newest": list(self._slow_passes_kept)},
                "queue_wait_s": queue_wait,
                "ttft_s": ttft}


class LLMServer:
    """Deployment callable: OpenAI-completions-shaped request handling."""

    def __init__(self, preset: str = "gpt2-tiny", max_batch: int = 4,
                 max_seq_len: int = 128, model_overrides: Optional[dict] = None,
                 checkpoint: Optional[str] = None, tokenizer: Any = None,
                 cluster_prefix_cache: bool = False,
                 **engine_kwargs):
        self.engine = LLMEngine(preset=preset, max_batch=max_batch,
                                max_seq_len=max_seq_len,
                                model_overrides=model_overrides,
                                checkpoint=checkpoint, tokenizer=tokenizer,
                                **engine_kwargs)
        # cluster prefix tier: any replica warm-starts from prefixes
        # computed anywhere in the cluster (serve/prefix_store.py)
        self.prefix_store = None
        if cluster_prefix_cache and self.engine.kv is not None:
            from ray_tpu.serve import prefix_store as _ps

            self.prefix_store = _ps.store_for_engine(self.engine)
        self._prefix_pool = None
        self._prefix_pool_lock = threading.Lock()
        self._chain_pool = None

    # ------------------------------------------------- cluster prefix tier
    def _prefix_submit(self, fn, *args):
        if self._prefix_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            with self._prefix_pool_lock:
                if self._prefix_pool is None:
                    self._prefix_pool = ThreadPoolExecutor(
                        max_workers=2, thread_name_prefix="prefix-fetch")
        return self._prefix_pool.submit(fn, *args)

    def _warm_start_future(self, eng: "LLMEngine", ids: List[int],
                           tenant: str = "base"):
        """Residency-tier fall-through for one prompt: local engine pool
        (peek, no fetch wins below a block of gain) -> cluster store
        lookup (zero RPCs, cached directory) -> background data-plane
        fetch whose future the engine imports while other lanes decode.
        Returns the blob future, or None when nothing beats local."""
        store = self.prefix_store
        if store is None or eng.kv is None or len(ids) < 2:
            return None
        need = ids[:-1]
        covered = eng.kv.peek_prefix_len(need)
        if len(need) - covered < eng.kv.block_size:
            return None
        hit = store.lookup(need, tenant=tenant)
        if hit is None or hit["n"] <= covered:
            return None
        return self._prefix_submit(store.fetch, hit, tenant)

    def _publish_prefix(self, eng: "LLMEngine", ids: List[int]) -> None:
        """After a completed generation the prompt's blocks are pooled:
        announce them so any OTHER replica can warm-start (dedup'd —
        shared prefixes are stored once cluster-wide). Runs on the
        prefetch executor — the export's device->host copy + seal +
        announce must not be charged to the response's tail latency."""
        store = self.prefix_store
        if store is None or eng.kv is None or len(ids) < 2:
            return
        self._prefix_submit(self._publish_prefix_sync, store, eng, ids)

    @staticmethod
    def _publish_prefix_sync(store, eng: "LLMEngine", ids: List[int]) -> None:
        try:
            store.maybe_publish(eng.kv, ids[:-1],
                                exporter=eng.export_pooled)
        except Exception:
            pass   # publication is an optimization, never a failure path

    def _request_ids(self, eng: "LLMEngine", body: dict,
                     prompt: str = "") -> List[int]:
        ids = body.get("prompt_ids")
        if ids is None:
            ids = eng.tokenizer.encode(prompt or body.get("prompt", ""))
        ids = ids or [eng.tokenizer.eos_id]
        return ids[-(eng.max_seq_len - 2):]

    def __call__(self, request: Any) -> dict:
        body = request if isinstance(request, dict) else getattr(
            request, "json", None) or {}
        ids = self._request_ids(self.engine, body)
        out = self.engine.generate(
            prompt_ids=ids,
            max_tokens=int(body.get("max_tokens", 16)),
            temperature=float(body.get("temperature", 0.0)),
            prefix_future=self._warm_start_future(self.engine, ids))
        self._publish_prefix(self.engine, ids)
        return {
            "object": "text_completion",
            "choices": [{"text": out["text"], "index": 0,
                         "token_ids": out["token_ids"],
                         "finish_reason": "length"}],
            "usage": {"completion_tokens": len(out["token_ids"])},
        }

    def batch_call(self, requests: list) -> list:
        """Compiled-chain batch entry: admit every request of a ring
        entry to the engine CONCURRENTLY, so the continuous-batching
        scheduler joins them into shared steps — a sequential map here
        would silently serialize the engine and forfeit batching on the
        compiled path. Per-item failures come back as chain error
        markers (user errors never fail batch neighbours)."""
        from concurrent.futures import ThreadPoolExecutor

        from ray_tpu.serve.compiled_chain import CHAIN_ERR

        if self._chain_pool is None:
            with self._prefix_pool_lock:
                if self._chain_pool is None:
                    self._chain_pool = ThreadPoolExecutor(
                        max_workers=max(8, self.engine.max_batch),
                        thread_name_prefix="chain-batch")
        futs = [self._chain_pool.submit(self, r) for r in requests]
        out = []
        for f in futs:
            try:
                out.append(f.result())
            except Exception as e:
                out.append({CHAIN_ERR: repr(e), "infra": False})
        return out

    def stream_next(self, stream_id: str, cursor: int = 0) -> dict:
        """Incremental tokens for an SSE stream (proxy-driven pull)."""
        return self.engine.stream_next(stream_id, cursor=cursor)

    def stats(self) -> dict:
        out = self.engine.engine_stats()
        if self.engine.kv is not None:
            out["kv_cache"] = self.engine.kv.stats()
        if self.prefix_store is not None:
            out["prefix_store"] = self.prefix_store.stats()
        return out

    def check_health(self):
        if not self.engine._thread.is_alive():
            raise RuntimeError("engine loop died")


class OpenAIServer(LLMServer):
    """OpenAI-compatible API surface (reference: serve.llm router
    `llm/_internal/serve/deployments/routers/router.py` — /v1/completions,
    /v1/chat/completions, /v1/models). Mount with route_prefix="/v1"."""

    def __init__(self, model_id: str = "ray-tpu-llm",
                 lora_root: Optional[str] = None, max_loras: int = 2,
                 **kwargs):
        super().__init__(**kwargs)
        self.model_id = model_id
        # LoRA multiplexing (reference: multi-LoRA serve.llm deployments;
        # replica-granular here): request `model` = "<base>:<adapter>"
        # resolves {lora_root}/{adapter}.npz, merged into the base params
        # and served by a per-adapter engine under an LRU cap
        self.lora_root = lora_root
        self.max_loras = max_loras
        self._lora_engines: "OrderedDict[str, LLMEngine]" = OrderedDict()
        self._engine_kwargs = dict(kwargs)
        # sid -> (engine, prompt_ids): the ids publish the prompt's
        # prefix into the cluster store when the stream completes
        self._stream_owner: Dict[str, tuple] = {}

    def loaded_lora_ids(self):
        return list(self._lora_engines)

    def _tenant_of(self, body: dict) -> str:
        """Adapter id of the request (`model="<base>:<adapter>"`), or
        "base" — the per-tenant tag on prefix-store hit counters."""
        model = (body or {}).get("model")
        if model and ":" in str(model):
            return str(model).rsplit(":", 1)[1]
        return "base"

    def _engine_for(self, body: dict) -> "LLMEngine":
        model = (body or {}).get("model")
        if (not self.lora_root or not model or model == self.model_id
                or ":" not in str(model)):
            return self.engine
        _require_gpt2(self.engine.family, "LoRA multiplexing")
        adapter_id = str(model).rsplit(":", 1)[1]
        eng = self._lora_engines.get(adapter_id)
        if eng is not None:
            self._lora_engines.move_to_end(adapter_id)
            return eng
        from ray_tpu.models.gpt2 import apply_lora, load_lora_npz
        from ray_tpu.serve import weight_store as _ws
        from ray_tpu.utils import fs as _lfs

        # hot-swap path: adapter deltas are first-class weight-plane
        # objects — the first replica to load an adapter publishes it,
        # every later replica pulls it P2P instead of touching lora_root
        # (byte-identical merge: the delta arrays are the same bytes).
        # Any miss falls back to the adapter npz on disk, then publishes.
        adapter = None
        store = _ws.get_store()
        akey = _ws.adapter_store_key(self.engine.weights_id, adapter_id)
        if store is not None:
            try:
                adapter = store.fetch_adapter(akey, tenant=adapter_id)
            except Exception:
                adapter = None
        if adapter is None:
            path = _lfs.join(self.lora_root, f"{adapter_id}.npz")
            adapter = load_lora_npz(path)
            if store is not None:
                try:
                    store.publish_adapter(akey, adapter)
                except Exception:
                    pass
        merged = apply_lora(self.engine.params, adapter)
        kwargs = dict(self._engine_kwargs)
        kwargs.pop("checkpoint", None)
        kwargs.pop("cluster_prefix_cache", None)
        # the merged params have the BASE engine's architecture (which may
        # come from a checkpoint sidecar, not the preset): hand its
        # resolved cfg over instead of re-deriving from the preset.
        # weights_id is the BASE's: adapters share base-model prefix
        # entries in the cluster store (one blob per prefix, hits
        # counted per adapter). DELIBERATE approximation: an adapter
        # whose LoRA retargets attention projections produces slightly
        # different prefix KV than the base — sharing trades that
        # deviation for cluster-wide TTFT, the same trade cross-adapter
        # prompt caches make. Tenants needing exact per-adapter KV pass
        # their own weights_id through engine kwargs to opt out.
        eng = LLMEngine(params_override=merged,
                        cfg_override=self.engine.cfg,
                        weights_id=self.engine.weights_id, **kwargs)
        while len(self._lora_engines) >= self.max_loras:
            _, old = self._lora_engines.popitem(last=False)
            old.shutdown()   # LRU eviction must stop the engine thread
        self._lora_engines[adapter_id] = eng
        return eng

    def stream_next(self, stream_id: str, cursor: int = 0) -> dict:
        eng, ids = self._stream_owner.get(stream_id, (self.engine, None))
        try:
            out = eng.stream_next(stream_id, cursor=cursor)
        except KeyError:
            self._stream_owner.pop(stream_id, None)   # expired engine-side
            raise
        if out.get("done"):
            self._stream_owner.pop(stream_id, None)
            # stream-heavy deployments must feed the cluster store too:
            # the prompt's blocks are pooled once the request finishes
            if ids is not None and not out.get("error"):
                self._publish_prefix(eng, ids)
        return out

    def _note_stream(self, sid: str, eng, ids=None) -> None:
        # abandoned SSE clients leave entries behind; bound the map (the
        # engines sweep their own stream state independently)
        if len(self._stream_owner) > 1024:
            for k in list(self._stream_owner)[:512]:
                self._stream_owner.pop(k, None)
        self._stream_owner[sid] = (eng, ids)

    def __call__(self, request: Any) -> dict:
        path = getattr(request, "path", "/v1/completions")
        if path.endswith("/models"):
            data = [{"id": self.model_id, "object": "model",
                     "owned_by": "ray_tpu"}]
            data += [{"id": f"{self.model_id}:{a}", "object": "model",
                      "owned_by": "ray_tpu", "parent": self.model_id}
                     for a in self.loaded_lora_ids()]
            return {"object": "list", "data": data}
        body = request if isinstance(request, dict) else \
            getattr(request, "json", None) or {}
        max_tokens = int(body.get("max_tokens", 16))
        temperature = float(body.get("temperature", 1.0))
        top_p = float(body.get("top_p", 1.0))
        top_k = int(body.get("top_k", 0))
        stream = bool(body.get("stream"))
        eng = self._engine_for(body)
        # multi-tenant prefix sharing: all adapter engines key the store
        # by the BASE weights, so a system prompt prefilled under one
        # adapter warm-starts every other; hits are counted per tenant
        tenant = self._tenant_of(body)
        if path.endswith("/chat/completions"):
            msgs = body.get("messages", [])
            prompt = "".join(f"<|{m.get('role', 'user')}|>{m.get('content', '')}"
                             for m in msgs) + "<|assistant|>"
            ids = self._request_ids(eng, {}, prompt)
            fut = self._warm_start_future(eng, ids, tenant=tenant)
            if stream:
                sid = eng.start_stream(
                    prompt_ids=ids, max_tokens=max_tokens,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    prefix_future=fut)
                self._note_stream(sid, eng, ids)
                return {"__sse_stream__": {"stream_id": sid,
                                           "model": self.model_id,
                                           "mode": "chat"}}
            out = eng.generate(prompt_ids=ids, max_tokens=max_tokens,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, prefix_future=fut)
            self._publish_prefix(eng, ids)
            finish = ("length" if out["completion_tokens"] >= max_tokens
                      else "stop")
            return {
                "id": f"chatcmpl-{int(time.time() * 1e3)}",
                "object": "chat.completion", "model": self.model_id,
                "choices": [{"index": 0,
                             "message": {"role": "assistant",
                                         "content": out["text"]},
                             "finish_reason": finish}],
                "usage": {"prompt_tokens": out["prompt_tokens"],
                          "completion_tokens": out["completion_tokens"],
                          "total_tokens": out["prompt_tokens"]
                          + out["completion_tokens"]},
            }
        # /v1/completions
        prompt = body.get("prompt", "")
        if isinstance(prompt, list):
            prompt = prompt[0] if prompt else ""
        ids = self._request_ids(eng, body, prompt)
        fut = self._warm_start_future(eng, ids, tenant=tenant)
        if stream:
            sid = eng.start_stream(
                prompt_ids=ids,
                max_tokens=max_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, prefix_future=fut)
            self._note_stream(sid, eng, ids)
            return {"__sse_stream__": {"stream_id": sid,
                                       "model": self.model_id,
                                       "mode": "completion"}}
        out = eng.generate(prompt_ids=ids,
                           max_tokens=max_tokens,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, prefix_future=fut)
        self._publish_prefix(eng, ids)
        finish = ("length" if out["completion_tokens"] >= max_tokens
                  else "stop")
        return {
            "id": f"cmpl-{int(time.time() * 1e3)}",
            "object": "text_completion", "model": self.model_id,
            "choices": [{"index": 0, "text": out["text"],
                         "token_ids": out["token_ids"],
                         "finish_reason": finish}],
            "usage": {"prompt_tokens": out["prompt_tokens"],
                      "completion_tokens": out["completion_tokens"],
                      "total_tokens": out["prompt_tokens"]
                      + out["completion_tokens"]},
        }


def build_openai_app(preset: str = "gpt2-tiny", max_batch: int = 4,
                     max_seq_len: int = 128, num_replicas: int = 1,
                     model_id: str = "ray-tpu-llm",
                     model_overrides: Optional[dict] = None,
                     num_tpu_chips: int = 0,
                     checkpoint: Optional[str] = None,
                     slo_config: Optional[dict] = None,
                     **engine_kwargs):
    """Deployment graph for an OpenAI-compatible server (reference
    `ray.serve.llm.build_openai_app`); run with
    `serve.run(app, route_prefix="/v1")`."""
    from ray_tpu.serve.api import deployment

    actor_options = {"num_cpus": 1}
    if num_tpu_chips:
        actor_options["num_tpu_chips"] = num_tpu_chips
    dep = deployment(OpenAIServer, name=f"openai-{model_id}",
                     num_replicas=num_replicas,
                     ray_actor_options=actor_options,
                     max_ongoing_requests=max_batch * 2,
                     slo_config=slo_config)
    return dep.bind(model_id=model_id, preset=preset, max_batch=max_batch,
                    max_seq_len=max_seq_len, model_overrides=model_overrides,
                    checkpoint=checkpoint, **engine_kwargs)


def build_llm_deployment(preset: str = "gpt2-tiny", max_batch: int = 4,
                         max_seq_len: int = 128, num_replicas: int = 1,
                         name: str = "llm",
                         model_overrides: Optional[dict] = None,
                         num_tpu_chips: int = 0,
                         checkpoint: Optional[str] = None,
                         slo_config: Optional[dict] = None,
                         **engine_kwargs):
    """Deployment for an LLM server (reference build_openai_app analog)."""
    from ray_tpu.serve.api import deployment

    actor_options = {"num_cpus": 1}
    if num_tpu_chips:
        actor_options["num_tpu_chips"] = num_tpu_chips
    dep = deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        ray_actor_options=actor_options,
        max_ongoing_requests=max_batch * 2,
        slo_config=slo_config)
    return dep.bind(preset=preset, max_batch=max_batch,
                    max_seq_len=max_seq_len, model_overrides=model_overrides,
                    checkpoint=checkpoint, **engine_kwargs)
