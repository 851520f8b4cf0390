"""90th percentile of time to first token; recorded, not judged."""

from harness import client_log

from . import _client


def read(record):
    return _client.over_counted(record, client_log.ttft_ms, 90)
