"""Process start to window open: cluster, worker and chip, weights made on
the device from the seed, the cell's own shapes warmed."""


def read(record):
    return record["window"]["t0"] - record["t_start"]
