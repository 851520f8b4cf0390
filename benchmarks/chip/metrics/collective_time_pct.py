"""Union of the collective operations' device time over the traced window,
worst device."""


def read(record):
    trace = record.get("trace")
    if not trace or record["chips"] < 2:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
