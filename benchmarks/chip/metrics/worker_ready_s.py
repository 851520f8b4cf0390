"""`fit()` / `serve.run` called -> the first line of the benchmark's code
in the worker after JAX answered there with its devices."""


def read(record):
    m = record["marks"]
    if "fit" in m:
        return m["worker_ready"] - m["fit"]
    return m["replica_chip"] - m["run"]
