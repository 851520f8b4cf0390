"""Tokens generated per engine step in the window: how full the batch ran."""

from . import _engine


def read(record):
    steps = _engine.delta(record, "engine_steps")
    if not steps:
        return None
    return _engine.delta(record, "total_generated") / steps
