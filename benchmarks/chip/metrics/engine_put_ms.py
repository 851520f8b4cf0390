"""The step's explicit host-to-device transfers for one step: wall seconds
of the engine thread's `put` phase (the `jnp.asarray` of positions, lengths
and active lanes) over the engine steps taken."""

from . import _phase_ms


def read(record):
    return _phase_ms.read(record, "put")
