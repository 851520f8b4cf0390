"""Share of the traced window in which the idlest device is idle while the
engine's thread is inside `engine.admit` (`_phases`)."""

from . import _phases


def read(record):
    return _phases.idle_pct(record, phases=("admit",))
