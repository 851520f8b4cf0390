"""Share of the window the loop spent inside `next(batches)` and
`device_put`."""


def read(record):
    w = record["window"]
    return 100.0 * sum(w["wait_s"].values()) / (w["t1"] - w["t0"])
