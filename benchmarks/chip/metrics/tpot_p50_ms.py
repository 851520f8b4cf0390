"""Median over requests of (last token - first token) / (tokens - 1)."""

from harness import client_log

from . import _client


def read(record):
    return _client.over_counted(record, client_log.tpot_ms, 50)
