"""Mean time from a request's hand-over to the engine to its first
generated token, over the first tokens of the window (the engine's
`ttft_s` histogram). The client's TTFT less this and `stream_open_ms` is
the relay: the proxy's poll and the SSE write."""

from . import _lifecycle


def read(record):
    return _lifecycle.mean_ms(record, "ttft_s")
