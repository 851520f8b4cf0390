"""90th percentile of time per output token; recorded, not judged."""

from harness import client_log

from . import _client


def read(record):
    return _client.over_counted(record, client_log.tpot_ms, 90)
