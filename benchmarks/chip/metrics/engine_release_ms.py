"""The pass's tail for one step: wall seconds of the engine thread's
`release` phase (from the end of reading a step to the next pass's `calls`,
where the step before's arrays go) over the engine steps taken."""

from . import _phase_ms


def read(record):
    return _phase_ms.read(record, "release")
