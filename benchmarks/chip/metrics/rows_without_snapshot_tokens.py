"""Prompt tokens whose rows the prefix pool held and which were prefilled
again because no snapshot of the state stood at their boundary, over the
window: the engine's counter for a cache of both kinds (0 where every
pooled prefix has both: a guard on the two kinds agreeing, not a gauge)."""

from . import _engine


def read(record):
    return _engine.delta(record, "rows_without_snapshot_tokens")
