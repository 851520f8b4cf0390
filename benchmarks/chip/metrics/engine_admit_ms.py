"""Admission for one step: wall seconds of the engine thread's `admit`
phase (placing queued requests in free slots, a pooled prefix's copy into
the slot) over the engine steps taken."""

from . import _phase_ms


def read(record):
    return _phase_ms.read(record, "admit")
