"""Bytes one execution of the engine's decode program (the XLA module of
`_step`) accesses, by the compiler's own estimate: the sum of
`bytes_accessed` (kept with an operation's metadata) over the operations
that ran inside one module execution on the idlest device, loops counted
by their bodies; the median over the traced executions."""

import bisect
from statistics import median

from . import _events

MODULE = "jit__step"


def bytes_per_execution(ops: list, modules: list, meta: dict) -> list:
    """`ops`: a device's leaves by start (`_events.walked`'s `leaves`)."""
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
        worst, _, _ = _events.idlest(path)
        d = devices[worst]
        runs = bytes_per_execution(_events.walk(path)[worst]["leaves"],
                                   d["modules"], d["meta"])
    except (OSError, ValueError, IndexError, UnicodeDecodeError):
        return None
    runs = [b for b in runs if b]
    return median(runs) / 1e9 if runs else None
