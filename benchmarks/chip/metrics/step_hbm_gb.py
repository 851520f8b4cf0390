"""Bytes one execution of the engine's decode program (the XLA module of
`_step`) accesses, by the compiler's own estimate: the sum of
`bytes_accessed` (kept with an operation's metadata) over the operations
that ran inside one module execution on the idlest device, loops counted
by their bodies; the median over the traced executions."""

import bisect
from statistics import median

from . import _events

MODULE = "jit__step"


def leaves(ops: list) -> list:
    """The `(start, end, name)` events that enclose no other."""
    order = sorted(ops, key=lambda ev: (ev[0], -ev[1]))
    return [ev for ev, nxt in zip(order, order[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]]


def bytes_per_execution(ops: list, modules: list, meta: dict) -> list:
    ops = leaves(ops)                  # sorted by start
    starts = [s for s, _, _ in ops]
    out = []
    for m0, m1, name in modules:
        if MODULE in name:
            inside = ops[bisect.bisect_left(starts, m0):
                         bisect.bisect_left(starts, m1)]
            out.append(sum(meta.get(op, {}).get("bytes_accessed", 0)
                           for _, e, op in inside if e <= m1))
    return out


def read(record):
    path = _events.path_of(record)
    if not path:
        return None
    try:
        devices, _ = _events.load(path)
        if not devices:
            return None
        worst, _, _ = _events.idlest(devices)
        d = devices[worst]
        runs = bytes_per_execution(d["ops"], d["modules"], d["meta"])
    except (OSError, ValueError, IndexError, UnicodeDecodeError):
        return None
    runs = [b for b in runs if b]
    return median(runs) / 1e9 if runs else None
