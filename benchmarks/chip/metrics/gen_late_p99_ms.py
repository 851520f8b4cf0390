"""How late the load generator ran: 99th percentile of sent - due."""

from harness import client_log

from . import _client


def read(record):
    return client_log.percentile(
        [client_log.late_ms(e) for e in _client.counted(record)], 99)
