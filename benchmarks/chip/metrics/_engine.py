"""What the engine-counter readers share: deltas over the window."""


def delta(record, key):
    c = record.get("counters")
    if not c or key not in c["after"]:
        return None
    return c["after"][key] - c["before"][key]


def kv_delta(record, key):
    c = record.get("counters")
    if not c or key not in c["after"].get("kv_cache", {}):
        return None
    return c["after"]["kv_cache"][key] - c["before"]["kv_cache"][key]
