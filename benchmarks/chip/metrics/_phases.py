"""The idlest device's gaps, laid to the engine loop's own phases.

`serve/llm.py` opens an `engine.<phase>` span (`jax.profiler`'s clock, the
device trace's) around each thing its thread does. A gap of the device is
laid to the phase whose **whole** interval covers it, not to a self time:
the Python tracer's frames beneath a phase must not steal it. Phases never
overlap one another. What no phase covers (between two phases, or with the
thread off the CPU outside one) is not laid to any.
"""

from __future__ import annotations

import functools

import trace_reduce as tr

from . import _events

PREFIX = "engine."


def phase_intervals(host_events: list) -> dict:
    """phase -> disjoint sorted intervals, from one thread's events."""
    out: dict = {}
    for s, e, name in host_events:
        if name.startswith(PREFIX):
            out.setdefault(name[len(PREFIX):], []).append([s, e])
    return {k: tr.union(v) for k, v in out.items()}


def idle_by_phase(gaps: list, host_events: list) -> dict:
    """phase -> ns of the disjoint sorted `gaps` inside that phase."""
    return {phase: tr.overlap(gaps, spans)
            for phase, spans in phase_intervals(host_events).items()}


@functools.lru_cache(maxsize=2)
def _idle_pct_of(path: str):
    devices, host_lines = _events.load(path)
    if not devices:
        return None
    _, gaps, window = _events.idlest(path)
    idle = idle_by_phase(gaps, tr.dispatch_thread(host_lines))
    if not idle or not window:
        return None                   # a program without the phases
    return {phase: 100.0 * ns / window for phase, ns in idle.items()}


def idle_pct(record, phases=None, but=()):
    """Per cent of the traced window in which the idlest device is idle
    under one of `phases` (all of them when None) and none of `but`. None
    when the run was not traced or the program opens no such span."""
    path = _events.path_of(record)
    if not path:
        return None
    try:
        idle = _idle_pct_of(path)
    except (OSError, ValueError, IndexError):
        return None
    if idle is None:
        return None
    return sum(v for k, v in idle.items()
               if (phases is None or k in phases) and k not in but)
