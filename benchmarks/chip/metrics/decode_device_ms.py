"""Median device time of one execution of the engine's decode program
(the XLA module of `_step`)."""

from . import _trace


def read(record):
    return _trace.module_ms(record, "jit__step")
