"""Share of the traced window in which the idlest device is idle while the
engine's thread is inside `engine.put` or `engine.dispatch` (`_phases`):
the step's transfers, the merge and the program's launch."""

from . import _phases


def read(record):
    return _phases.idle_pct(record, phases=("put", "dispatch"))
