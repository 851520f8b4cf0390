"""Share of the seconds between the two readings of the engine's counters
that lay in passes of its loop longer than `serve/llm.py`'s `SLOW_PASS_S`
(`slow_passes.seconds`; the record keeps the newest by name)."""


def read(record):
    c = record.get("counters")
    if not c or "slow_passes" not in c["after"]:
        return None
    slow = (c["after"]["slow_passes"]["seconds"]
            - c["before"]["slow_passes"]["seconds"])
    return 100.0 * slow / (c["after_at"] - c["before_at"])
