"""The merge and the step program's launch for one step: wall seconds of
the engine thread's `dispatch` phase, which no longer holds the transfers
(`engine_put_ms`), over the engine steps taken."""

from . import _phase_ms


def read(record):
    return _phase_ms.read(record, "dispatch")
