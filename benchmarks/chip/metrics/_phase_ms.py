"""What the readers of the engine thread's accounting share: one phase's
seconds a step from the engine's cumulative lap timers (`serve/llm.py`
`_Phases`: `phase_s` wall, `phase_cpu_s` CPU, the same keys), between the
two readings of its counters. None on a record of a program that keeps no
CPU seconds beside its wall seconds: there `dispatch` still held the
transfers that `put` has now, and no phase held the pass's tail."""

from . import _engine


def grown(record, timers="phase_s"):
    """phase -> seconds `timers` grew over the window, or None."""
    c = record.get("counters")
    if not c or "phase_cpu_s" not in c["after"]:
        return None
    before, after = c["before"][timers], c["after"][timers]
    return {k: after[k] - before[k] for k in after}


def read(record, phase):
    steps = _engine.delta(record, "engine_steps")
    wall = grown(record)
    if not steps or wall is None:
        return None
    return wall[phase] * 1e3 / steps
