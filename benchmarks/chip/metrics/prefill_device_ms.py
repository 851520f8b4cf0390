"""Median device time of one execution of the engine's chunked-prefill
program (the XLA module of `_chunk`)."""

from . import _trace


def read(record):
    return _trace.module_ms(record, "jit__chunk")
