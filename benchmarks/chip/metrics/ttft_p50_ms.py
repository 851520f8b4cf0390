"""Median, over the requests due in the window, of first token seen minus
when the request was due, on the client's clock; recorded, not judged
(`ttft_mean_ms` is)."""

from harness import client_log

from . import _client


def read(record):
    return _client.over_counted(record, client_log.ttft_ms, 50)
