"""Median device time of one execution of the train step's XLA module."""

from . import _trace


def read(record):
    return _trace.module_ms(record, "_step")
