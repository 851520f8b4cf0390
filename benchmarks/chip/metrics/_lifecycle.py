"""What the readers of the engine's request-lifecycle histograms share:
the mean of the observations made between the two readings."""


def mean_ms(record, key):
    c = record.get("counters")
    if not c or key not in c["after"]:
        return None
    before, after = c["before"][key], c["after"][key]
    n = after["count"] - before["count"]
    if not n:
        return None
    return (after["sum"] - before["sum"]) * 1e3 / n
