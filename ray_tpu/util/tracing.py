"""Distributed tracing: spans around task submit/execute with W3C context
propagation.

Parity: `python/ray/util/tracing/tracing_helper.py` — the driver opens a
submission span and injects a W3C `traceparent` into the task spec; the
executing worker extracts it and opens a child execution span, so one trace
follows a task across processes.

The tracer is self-contained and imports nothing of OpenTelemetry:
128-bit trace ids, 64-bit span ids, W3C traceparent inject/extract,
finished spans buffered in-process (drain with `get_finished_spans()`) and
queued for the metrics push to the head, which merges every process's
spans into `timeline()`. An exporter object with an `export(spans)`
method, handed to `enable_tracing`, is called at each span end; that is
the one way out of the process besides the push, and nothing is mirrored
to an OpenTelemetry SDK.

Two clocks. `Span`s carry `time.time()`. The serving engine's loop also
opens `annotate(...)` spans on the JAX profiler's clock, which a device
trace shares. The vocabulary is in the README's observability section.

Start-up is on the first clock. `startup_span` / `record_startup` keep one
start-up record a process: ordinary `Span`s on `time.time()`, because that
is the clock a cold start is felt on and the only one the driver, the
head, a worker and JAX's own compile events (`utils/platform.
watch_compiles`) share; no device trace runs while a process starts, so
the profiler's clock has nothing to offer there. The record is always on
(a slow start is looked into after the fact), bounded, nowhere near a hot
path, and written a line a span to
`<STATE_DIR>/<session>/logs/startup-<role>-<pid>.jsonl`, beside the worker
logs, when the span closes.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import os
from ray_tpu.core import config as _config
import secrets
import sys
import threading
import time
from typing import Dict, List, Optional

_enabled = False
_lock = threading.Lock()
_finished: List["Span"] = []
# spans waiting to ride the next metrics push to the head (workload
# tracing: the head accumulates every process's spans so timeline() can
# merge one cross-process trace) — bounded separately from _finished
_push_queue: List[dict] = []
_dropped_counter = None
_exporter = None
_current: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "ray_tpu_span", default=None)


@dataclasses.dataclass
class Span:
    name: str
    trace_id: str            # 32 hex chars
    span_id: str             # 16 hex chars
    parent_id: Optional[str]
    attributes: Dict[str, object]
    start_ts: float = 0.0
    end_ts: float = 0.0

    @property
    def duration_s(self) -> float:
        return self.end_ts - self.start_ts

    def traceparent(self) -> str:
        return f"00-{self.trace_id}-{self.span_id}-01"

    def to_dict(self) -> dict:
        """JSON-safe form (rides the metrics push to the head)."""
        attrs = {k: (v if isinstance(v, (str, int, float, bool)) else str(v))
                 for k, v in self.attributes.items()}
        return {"name": self.name, "trace_id": self.trace_id,
                "span_id": self.span_id, "parent_id": self.parent_id,
                "start_ts": self.start_ts, "end_ts": self.end_ts,
                "attributes": attrs}


def enable_tracing(exporter=None) -> None:
    """Turn tracing on (idempotent). `exporter`: optional object with
    `.export(list_of_spans)` called at each span end."""
    global _enabled, _exporter
    _enabled = True
    if exporter is not None:
        _exporter = exporter


def is_enabled() -> bool:
    global _enabled
    if not _enabled and _config.get("tracing"):
        _enabled = True
    return _enabled


def current_span() -> Optional[Span]:
    return _current.get()


def is_recording() -> bool:
    """True when a span opened now would record: tracing is enabled
    process-wide, or we are inside an active trace context (a remote
    caller's context adopted per-request — the OTel sampling model:
    the root decides, children follow the parent)."""
    return is_enabled() or _current.get() is not None


def get_finished_spans(clear: bool = False) -> List[Span]:
    with _lock:
        out = list(_finished)
        if clear:
            _finished.clear()
    return out


@contextlib.contextmanager
def start_span(name: str, *, carrier: Optional[Dict[str, str]] = None,
               attributes: Optional[dict] = None):
    """Open a span as current; parents to `carrier` (W3C traceparent dict)
    if given, else to the current in-process span.

    Records when tracing is enabled process-wide, OR when a parent
    context exists (a carrier, or an in-process current span): a traced
    request's children record in every process it crosses without
    flipping any process-wide switch — per-request tracing stays
    per-request."""
    span = _new_span(name, carrier, attributes, time.time())
    if span is None:
        yield None
        return
    token = _current.set(span)
    try:
        yield span
    finally:
        _current.reset(token)
        span.end_ts = time.time()
        _finish(span)


def _parse_carrier(carrier: Optional[Dict[str, str]]):
    """(trace id, parent span id, sampled) of a W3C carrier; Nones and
    False for none or a malformed one. Strict: a malformed header (LBs
    and APM agents inject these freely) must NOT force recording, and
    neither must a valid one whose W3C sampled flag is 00."""
    if not carrier or "traceparent" not in carrier:
        return None, None, False
    try:
        _, t, s, flags = carrier["traceparent"].split("-")
    except ValueError:
        return None, None, False
    if len(t) != 32 or len(s) != 16:
        return None, None, False
    return t, s, flags != "00"


def _new_span(name: str, carrier: Optional[Dict[str, str]],
              attributes: Optional[dict], start_ts: float) -> Optional[Span]:
    """A span parented to `carrier` if it names one, else to the current
    span; None when nothing records (see `start_span`)."""
    parent_trace, parent_span, sampled = _parse_carrier(carrier)
    cur = _current.get()
    if not (is_enabled() or cur is not None or sampled):
        return None
    if parent_trace is None and cur is not None:
        parent_trace, parent_span = cur.trace_id, cur.span_id
    return Span(name=name,
                trace_id=parent_trace or secrets.token_hex(16),
                span_id=secrets.token_hex(8), parent_id=parent_span,
                attributes=dict(attributes or {}), start_ts=start_ts)


def _finish(span: Span) -> None:
    """A finished span into the in-process buffer, the push queue and the
    exporter."""
    cap = max(int(_config.get("tracing_buffer_spans")), 2)
    dropped = 0
    with _lock:
        _finished.append(span)
        if len(_finished) > cap:
            # drop the oldest half: amortized O(1) per span, and the
            # newest spans are the ones a live debugging session needs
            del _finished[:cap // 2]
        _push_queue.append(span.to_dict())
        if len(_push_queue) > cap:
            dropped = cap // 2
            del _push_queue[:dropped]
    if dropped:
        _count_dropped(dropped)
    if _exporter is not None:
        try:
            _exporter.export([span])
        except Exception:
            pass


def record_span(name: str, start_ts: float, end_ts: float,
                carrier: Optional[Dict[str, str]] = None,
                attributes: Optional[dict] = None) -> Optional[Span]:
    """A finished span with explicit times, for work whose thread is not
    the request's (the serving engine writes a request's spans when it
    completes) or whose start is known only at its end (a train step is
    the window between two reports). Parents and records as `start_span`
    does: to `carrier` if given, else to the current span; None when
    nothing records. Never becomes current."""
    span = _new_span(name, carrier, attributes, start_ts)
    if span is not None:
        span.end_ts = end_ts
        _finish(span)
    return span


def annotate(name: str):
    """A span on the JAX profiler's clock, the one a device trace is on:
    the only way hot-path code opens one. Written to a running profile
    and to nothing else; with no profile running it costs one flag check.
    A null context in a process that has not imported JAX (the proxy and
    the head never do, and must not for this)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def _count_dropped(n: int) -> None:
    """Spans dropped before reaching the head are invisible losses unless
    counted — `trace_spans_dropped_total` makes the budget observable."""
    global _dropped_counter
    try:
        if _dropped_counter is None:
            from ray_tpu.util import metrics as _m

            _dropped_counter = _m.Counter(
                "trace_spans_dropped_total",
                "Finished spans dropped from the push buffer before the "
                "head could collect them (raise tracing_buffer_spans)")
        _dropped_counter.inc(n)
    except Exception:
        pass


def drain_push_spans(limit: int = 512) -> List[dict]:
    """Pop up to `limit` finished-span dicts for the metrics push (the
    head accumulates them for cross-process timeline export)."""
    with _lock:
        out = _push_queue[:limit]
        del _push_queue[:limit]
    return out


def requeue_push_spans(spans: List[dict]) -> None:
    """Put drained spans back after a failed push so a transient head
    outage doesn't silently hole the cross-process timeline; overflow
    (oldest first) is counted as dropped like any other loss."""
    if not spans:
        return
    cap = max(int(_config.get("tracing_buffer_spans")), 2)
    with _lock:
        _push_queue[:0] = spans
        overflow = len(_push_queue) - cap
        if overflow > 0:
            del _push_queue[:overflow]
    if overflow > 0:
        _count_dropped(overflow)


@contextlib.contextmanager
def adopt_context(carrier: Optional[Dict[str, str]]):
    """Make `carrier`'s span current WITHOUT recording a new span: code
    that runs on behalf of a remote caller (dependency fetches before the
    execute span opens, a daemon serving a pull) parents any spans it
    opens to the caller's context. A carrier's presence means the origin
    traces, so tracing is enabled here (same contract as execute_span)."""
    if not carrier or "traceparent" not in carrier:
        yield None
        return
    try:
        _, trace_id, span_id, _ = carrier["traceparent"].split("-")
    except ValueError:
        yield None
        return
    synthetic = Span(name="(remote)", trace_id=trace_id, span_id=span_id,
                     parent_id=None, attributes={})
    token = _current.set(synthetic)
    try:
        yield synthetic
    finally:
        _current.reset(token)


def inject_context() -> Optional[Dict[str, str]]:
    """Current span context as a W3C carrier (rides in the task spec).
    Keyed on the CURRENT span, not the process-wide switch: a span only
    becomes current when it recorded, so per-request traces propagate
    without enabling tracing for unrelated work."""
    cur = _current.get()
    if cur is None:
        return None
    return {"traceparent": cur.traceparent()}


def submit_span(task_name: str):
    if not is_recording():
        return contextlib.nullcontext()
    return start_span(f"{task_name}.remote",
                      attributes={"ray_tpu.op": "submit"})


def execute_span(task_name: str, carrier: Optional[Dict[str, str]]):
    if carrier is None:
        return contextlib.nullcontext()
    # the carrier's presence means the ORIGIN traces this operation;
    # start_span records on it without flipping this process's switch,
    # so one traced request doesn't turn tracing on for everything else
    return start_span(task_name, carrier=carrier,
                      attributes={"ray_tpu.op": "execute",
                                  "ray_tpu.pid": os.getpid()})


def request_span(name: str, carrier: Optional[Dict[str, str]],
                 attributes: Optional[dict] = None):
    """Root/continuation span for an ingress request (serve HTTP/gRPC
    proxies): a client-supplied W3C `traceparent` traces THIS request
    even when the cluster flag is off (the carrier clause in start_span
    — no process-wide state changes); without a carrier this opens a
    root span only when tracing is already enabled."""
    if not carrier and not is_enabled():
        return contextlib.nullcontext()
    return start_span(name, carrier=carrier, attributes=attributes)


# ---------------------------------------------------------------- start-up
# One record a process of where its start-up went (module docstring).
STARTUP_SPANS_MAX = 512
_startup: List[Span] = []
_startup_lines: List[str] = []        # closed before the session was known
_startup_role = "process"
_startup_session: Optional[str] = None
_startup_current: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("ray_tpu_startup_span", default=None)


def startup_identity(role: str, session: Optional[str]) -> None:
    """Who this process is in its cluster: `role` names its file
    (`driver`, `head`, `node`, `worker`) and `session` the directory. A
    process that learns its session late (a driver: `init()` makes or is
    told it) has buffered until now; a second session in one process (a
    driver that calls `init()` again) starts the record anew."""
    global _startup_role, _startup_session
    with _lock:
        if _startup_session is not None and session != _startup_session:
            _startup.clear()
            _startup_lines.clear()
        _startup_role, _startup_session = role, session
        lines = list(_startup_lines) if session else []
        if lines:
            _startup_lines.clear()
    if lines:
        _write_startup(lines)


def startup_file() -> Optional[str]:
    """Where this process's start-up spans are written; None until it
    knows its session."""
    if _startup_session is None:
        return None
    from ray_tpu.utils.platform import STATE_DIR

    return os.path.join(STATE_DIR, _startup_session, "logs",
                        f"startup-{_startup_role}-{os.getpid()}.jsonl")


def _write_startup(lines: List[str]) -> None:
    # open, append, close: a worker ends by signal, nothing waits for atexit
    try:
        path = startup_file()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "a") as f:
            f.write("".join(lines))
    except OSError:
        pass


def startup_spans() -> List[Span]:
    """This process's closed start-up spans, oldest first (at most
    STARTUP_SPANS_MAX: later ones are counted where they are counted,
    `jax_compiles_total`, and kept nowhere)."""
    with _lock:
        return list(_startup)


def _new_startup(name: str, start_ts: float, attributes: dict) -> Span:
    parent = _startup_current.get()
    return Span(name=name,
                trace_id=parent.trace_id if parent else secrets.token_hex(16),
                span_id=secrets.token_hex(8),
                parent_id=parent.span_id if parent else None,
                attributes=dict(attributes), start_ts=start_ts)


def _close_startup(span: Span, end_ts: float) -> None:
    span.end_ts = end_ts
    # who closed it: a driver learns its role inside its first span
    span.attributes.update(role=_startup_role, pid=os.getpid())
    with _lock:
        if len(_startup) >= STARTUP_SPANS_MAX:
            return
        _startup.append(span)
        line = json.dumps(span.to_dict()) + "\n"
        if _startup_session is None:
            _startup_lines.append(line)
            line = None
    if line is not None:
        _write_startup([line])
    if is_recording():
        _finish(span)


class startup_span(contextlib.ContextDecorator):
    """A stage of this process's start-up: an ordinary `Span`, parented to
    the start-up span open around it, recorded whether or not tracing is
    on. `with startup_span(name, **attributes) as span` (its `attributes`
    may be added to until it closes), or `@startup_span(name)` around a
    whole function (plain data, so that an actor's class still pickles)."""

    def __init__(self, name: str, **attributes):
        self.name, self.attributes = name, attributes

    def _recreate_cm(self) -> "startup_span":
        return startup_span(self.name, **self.attributes)   # one a call

    def __enter__(self) -> Span:
        self._span = _new_startup(self.name, time.time(), self.attributes)
        self._token = _startup_current.set(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        _startup_current.reset(self._token)
        _close_startup(self._span, time.time())


def startup_attributes(**attributes) -> None:
    """Adds to the start-up span open around the caller (a function under
    `@startup_span(...)` learns its join keys inside); nothing without
    one."""
    span = _startup_current.get()
    if span is not None:
        span.attributes.update(attributes)


def record_startup(name: str, start_ts: float, end_ts: float,
                   **attributes) -> Span:
    """A start-up stage with explicit times, for work that crosses
    callbacks or whose ends are only known afterwards (as `record_span` is
    to `start_span`). Never becomes current."""
    span = _new_startup(name, start_ts, attributes)
    _close_startup(span, end_ts)
    return span


def process_start_ts() -> Optional[float]:
    """When the kernel started this process, on `time.time()`'s clock
    (`/proc/self/stat`'s start time against `/proc/uptime`, both in clock
    ticks of 10 ms): the interpreter's start and every import before a
    process's first line lie between it and that line."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None
