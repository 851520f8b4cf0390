"""Platform helpers: where compiled programs are cached, what the devices
report, and the virtual multi-device CPU backend the tests run on.

The reference tests distributed logic on one machine with fake resources
(SURVEY.md §4.2); our analog is an N-device virtual CPU mesh.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import List, Optional


def ensure_virtual_cpu(n_devices: int) -> None:
    """Make `jax.devices()` return >= n_devices CPU devices, resetting the
    already-initialized backend if necessary. Call before creating any arrays
    (live buffers on a cleared backend become invalid)."""
    import jax
    import jax.extend.backend
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.devices()[0].platform == "cpu" and len(jax.devices()) >= n_devices:
            return
        jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", max(n_devices, 1))
    got = len(jax.devices())
    if got < n_devices:
        raise RuntimeError(
            f"could not create {n_devices} virtual CPU devices (got {got})")


def compile_cache_dir() -> str:
    """Where this installation keeps JAX's persistent compilation cache:
    `JAX_COMPILATION_CACHE_DIR` verbatim when the environment sets it, else
    `.jax_cache` beside the package (the checkout's root). The path is part
    of the cache key, so it is never derived from a temp name, pid, session
    or time: every process of every run agrees on it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process — and, through the environment, every child it
    starts — at `compile_cache_dir()`. Called at start-up by every process
    that compiles (workers before user code, chip_smoke.py's phases);
    imports no JAX itself, since JAX reads the variables when it is
    imported.

    The cache's key takes in each program's metadata (operation names with
    their `jax.named_scope`s, source lines). JAX leaves it out by default,
    and an executable found in the cache then carries the names of whichever
    version of the code compiled it first: a device trace of this version
    would show another's scopes, or none (PR 23: a parent commit that ran
    first on a shared cache left the serving programs unnamed). The price is
    a compile after an edit that moves the model's lines.

    An operation's location is its own line, not the stack of calls that led
    to it: with ten frames of traceback in a location (JAX's default) the
    metadata, and so the key, differs between two processes that reach the
    same program by different callers, and a benchmark's reference check
    compiled every program again that its replica had compiled a minute
    before (13 programs, ~20 s of a cold traced run: PERF.md, PRs 33, 38).
    One frame, not `jax_include_full_tracebacks_in_locations=False`: that
    drops the named scopes from the operations' names with the frames."""
    path = os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] = "true"
    os.environ["JAX_TRACEBACK_IN_LOCATIONS_LIMIT"] = "1"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        jax.config.update("jax_traceback_in_locations_limit", 1)
    return path


class CompileWatch:
    """What JAX's own monitoring events say of every program this process
    prepares: one start-up span `compile.<fun_name>` a program (on the
    event's own `time.time()` stamps), the two `/metrics` counters, and
    the count and newest entry that `engine_stats()` hands out. JAX calls
    the listeners on the thread that compiles, a program's tracing,
    lowering and compilation in turn, so what the first two said is kept
    by thread until the third closes the span."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"
    CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
    # spans kept a process; later programs are counted and leave no span,
    # so that an endless run of new shapes cannot crowd out the stages
    SPANS_MAX = 256

    def __init__(self):
        self.count = 0
        self.last: Optional[dict] = None
        self._pending = threading.local()
        self._lock = threading.Lock()       # two threads may compile at once
        self._counters = None

    def on_event(self, event: str, **_kw) -> None:
        # a hit is announced before the span closes; a miss when the new
        # executable is written to the cache, which its thresholds allow
        if event == self.CACHE_HIT:
            self._pending.cache = "hit"
        elif event == self.CACHE_MISS:
            self._pending.cache = "miss"

    def on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == self.CACHE_READ:
            self._pending.cache_read_s = duration

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_kw) -> None:
        if event == self.TRACE:
            # by name: lowering a program traces the functions inside it
            vars(self._pending).setdefault("traces", {})[fun_name] = \
                end - start
        elif event == self.LOWER:
            self._pending.lower = (fun_name, end - start)
        elif event == self.COMPILE:
            try:
                self._compiled(fun_name, start, end)
            except Exception:  # noqa: BLE001 - a record never fails a compile
                pass

    def _compiled(self, module: str, start: float, end: float) -> None:
        from ray_tpu.util import tracing

        # `jit(_step)` is the function `_step`, as a log of compiles says
        fun = (module[4:-1] if module.startswith("jit(")
               and module.endswith(")") else module)

        pending = dict(vars(self._pending))
        vars(self._pending).clear()
        # neither event: compiled and not written (the cache is off, or
        # the program is under the cache's thresholds)
        attrs = {"cache": pending.get("cache", "off")}
        # another function's tracing or lowering (an `eval_shape`, a
        # `lower()` never compiled) is not this program's
        traces, lowered = pending.get("traces", {}), pending.get("lower")
        if fun in traces:
            attrs["trace_s"] = traces[fun]
        if lowered and lowered[0] == module:
            attrs["lower_s"] = lowered[1]
        if "cache_read_s" in pending:
            attrs["cache_read_s"] = pending["cache_read_s"]
        with self._lock:
            self.count += 1
            keep_span = self.count <= self.SPANS_MAX
            self.last = {"fun": fun, "at": end, "seconds": end - start,
                         "cache": attrs["cache"]}
            if self._counters is None:
                self._counters = self._make_counters()
        if keep_span:
            tracing.record_startup(f"compile.{fun}", start, end, **attrs)
        self._counters[0].inc(tags={"fun": fun, "cache": attrs["cache"]})
        self._counters[1].inc(max(end - start, 0.0), tags={"fun": fun})

    @staticmethod
    def _make_counters() -> tuple:
        from ray_tpu.util import metrics

        return (
            metrics.Counter(
                "jax_compiles_total",
                "Programs this process prepared to run, by function and by "
                "what the persistent compilation cache did (hit, miss, off)",
                tag_keys=("fun", "cache")),
            metrics.Counter(
                "jax_compile_seconds_total",
                "Seconds inside XLA's compile-or-read-from-cache of those "
                "programs", tag_keys=("fun",)))


_compile_watch: Optional[CompileWatch] = None
_compile_watch_lock = threading.Lock()


def watch_compiles() -> CompileWatch:
    """Listen to JAX's compile events in this process, once however often
    it is called; returns the process's one `CompileWatch`. Called where
    the program itself first needs JAX in a worker (`LLMEngine.__init__`,
    `spmd.compile_train`, `parallel/mesh.build_mesh`, a trainer worker's
    set-up). The listeners fire only when JAX prepares a program, which a
    steady loop never does."""
    global _compile_watch
    with _compile_watch_lock:
        if _compile_watch is None:
            import jax.monitoring

            watch = CompileWatch()
            jax.monitoring.register_event_listener(watch.on_event)
            jax.monitoring.register_event_duration_secs_listener(
                watch.on_duration)
            jax.monitoring.register_event_time_span_listener(watch.on_span)
            _compile_watch = watch
    return _compile_watch


def device_report() -> List[dict]:
    """What `jax.devices()` is in this process, for results that must name
    the device they ran on: id, platform, kind, the memory counters the
    backend keeps (peak and limit in bytes; absent on the CPU), and the chip
    ids libtpu was narrowed to when the scheduler granted this process part
    of a host (`process_chips`; None for a whole host)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "platform": d.platform,
                    "kind": d.device_kind,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                    "process_chips": os.environ.get("TPU_VISIBLE_CHIPS")})
    return out


# Root for all on-disk runtime state (job logs, runtime_env extractions,
# spill files, CLI address file). Deliberately NOT "/tmp/ray_tpu": a dir
# named like the package becomes an importable namespace package that
# shadows the real library for any script run from /tmp.
STATE_DIR = "/tmp/ray_tpu_state"
