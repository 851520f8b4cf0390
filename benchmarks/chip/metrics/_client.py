"""What the client-side readers share: the requests a cell counts."""

from harness import client_log


def counted(record):
    ids = set(record["counted_ids"])
    return [e for e in record["client"] if e["id"] in ids]


def answered(record, fn):
    """`fn` of every counted request that was answered and has a value."""
    return [v for e in counted(record) if not client_log.failed(e)
            and (v := fn(e)) is not None]


def over_counted(record, fn, q):
    return client_log.percentile(answered(record, fn), q)


def mean_over_counted(record, fn):
    values = answered(record, fn)
    return sum(values) / len(values) if values else None
