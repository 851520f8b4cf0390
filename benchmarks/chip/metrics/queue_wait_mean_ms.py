"""Mean time from a request's hand-over to the engine to its placement in
a decode slot, over the requests placed in the window (the engine's
`queue_wait_s` histogram)."""

from . import _lifecycle


def read(record):
    return _lifecycle.mean_ms(record, "queue_wait_s")
