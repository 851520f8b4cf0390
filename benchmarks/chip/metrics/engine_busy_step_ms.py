"""Seconds of the engine loop's passes that ran a step (`loop_busy_s`)
over the engine steps taken, between the two readings of its counters:
what `engine_step_ms` would be without the engine's empty moments."""

from . import _engine


def read(record):
    steps = _engine.delta(record, "engine_steps")
    busy = _engine.delta(record, "loop_busy_s")
    if not steps or busy is None:
        return None
    return busy * 1e3 / steps
