"""The part of the collectives' time during which no other operation ran
on that device, over the traced window, worst device."""


def read(record):
    trace = record.get("trace")
    if not trace or record["chips"] < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
