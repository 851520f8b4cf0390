"""Median of request sent -> SSE response headers: proxy, router and the
admission call, no engine compute."""

from harness import client_log

from . import _client


def read(record):
    return _client.over_counted(record, client_log.headers_ms, 50)
