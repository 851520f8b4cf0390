"""Time the engine's thread was inside a phase and not running, for one
step: the growth of `phase_s` less that of `phase_cpu_s` over every phase
but `fetch` (there it is the device's) and `empty` (the sleep's), over the
engine steps taken. What is left is the interpreter lock's or the
scheduler's: a thread that gave the lock up in a transfer or a destructor
and got it back late."""

from . import _engine, _phase_ms


def read(record):
    steps = _engine.delta(record, "engine_steps")
    wall = _phase_ms.grown(record)
    if not steps or wall is None:
        return None
    cpu = _phase_ms.grown(record, "phase_cpu_s")
    off = sum(wall[k] - cpu[k] for k in wall if k not in ("fetch", "empty"))
    return off * 1e3 / steps
