"""The traced run's file, read once (`_xmeta`) for every reader that needs
more than the reduced trace holds: each device's operations with their
metadata and its module executions, and the host's threads, as
`(start_ns, end_ns, what)` on one clock. An operation's `what` is the id
of its metadata in the device's `meta` table (`meta[id]["name"]` is the
name `trace_reduce` shows); a module's and a host event's is its name."""

from __future__ import annotations

import functools

import trace_reduce as tr

from . import _xmeta


def path_of(record):
    """The record's `.xplane.pb`, or None when the run was not traced."""
    trace_dir = record.get("trace_dir")
    return tr.newest_xplane(trace_dir) if trace_dir else None


def _named(events: list, meta: dict) -> list:
    return [(s, e, meta[i]["name"] if i in meta else "")
            for s, e, i in events]


@functools.lru_cache(maxsize=2)
def load(path: str) -> tuple:
    """({device plane: {"ops": [...], "modules": [...], "meta": {...}}},
    [host lines])."""
    devices, host_lines = {}, []
    for name, plane in _xmeta.read(path).items():
        if tr.DEVICE_PLANE.match(name):
            lines = dict(plane["lines"])
            if lines.get(tr.OPS_LINE):
                devices[name] = {
                    "ops": lines[tr.OPS_LINE],
                    "modules": _named(lines.get(tr.MODULES_LINE, []),
                                      plane["meta"]),
                    "meta": plane["meta"]}
        elif name == tr.HOST_PLANE:
            host_lines = [_named(events, plane["meta"])
                          for _, events in plane["lines"]]
    return devices, host_lines


def idlest(devices: dict) -> tuple:
    """(plane name, its idle gaps, the window's ns) as `trace_reduce`
    takes them: the window runs from the first operation's start to the
    last one's end over all devices, the idlest device is the least busy."""
    t0 = min(s for d in devices.values() for s, _, _ in d["ops"])
    t1 = max(e for d in devices.values() for _, e, _ in d["ops"])
    busy = {name: tr.union([[s, e] for s, e, _ in d["ops"]])
            for name, d in devices.items()}
    worst = min(sorted(busy), key=lambda k: tr.length(busy[k]))
    return worst, tr.subtract([[t0, t1]], busy[worst]), t1 - t0
