"""1 - union of the device's operation intervals over the traced window,
worst device."""


def read(record):
    trace = record.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_worst_s"] / trace["window_s"]
