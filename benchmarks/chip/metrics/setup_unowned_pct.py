"""Of the stretch of set-up in which only the program and JAX work
(`init` -> the replica answered, or -> the train step compiled), the share
that no start-up span of any of the run's processes covers: how much of
set-up still has no owner."""

from . import _startup


def read(record):
    found = _startup.spans(record)
    marks = record["marks"]
    end = marks.get("replica_up", marks.get("compiled"))
    if not found or end is None:
        return None
    lo = marks["init"]
    owned = _startup.tr.length(_startup.owned(record, lo, end))
    return 100.0 * (1.0 - owned / (end - lo))
