"""Output tokens that reached the clients inside the window, over the
window's seconds."""

from harness import client_log


def read(record):
    w = record["window"]
    return (client_log.tokens_between(record["client"], w["t0"], w["t1"])
            / (w["t1"] - w["t0"]))
