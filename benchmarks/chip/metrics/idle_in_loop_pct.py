"""Share of the traced window in which the idlest device is idle while the
engine's thread is inside any `engine.<phase>` but `fetch`, `sample` and
`empty`: calls, admit, plan, dispatch, publish, notify (`_phases`)."""

from . import _phases


def read(record):
    return _phases.idle_pct(record, but=("fetch", "sample", "empty"))
