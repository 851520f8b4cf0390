"""Seconds between the two readings of the engine's counters (at the
window's edges, timed in the replica) over the engine steps taken between
them (both kinds of step)."""

from . import _engine


def read(record):
    steps = _engine.delta(record, "engine_steps")
    if not steps:
        return None
    c = record["counters"]
    return (c["after_at"] - c["before_at"]) * 1e3 / steps
