"""What the trace readers share."""


def module_ms(record, key):
    """Median device time of one execution of the XLA module whose name
    contains `key`, over the traced window."""
    trace = record.get("trace")
    if not trace:
        return None
    hits = [m for name, m in trace["modules"].items() if key in name]
    if not hits:
        return None
    return max(hits, key=lambda m: m["total_s"])["median_ms"]
