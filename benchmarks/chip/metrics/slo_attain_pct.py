"""Share of the counted requests that met both of the mix's limits; a
failed request met neither."""

from harness import client_log

from . import _client


def read(record):
    entries = _client.counted(record)
    if not entries or not record.get("limits"):
        return None
    return 100.0 * sum(client_log.met(e, record["limits"])
                       for e in entries) / len(entries)
