"""Tokens of the steps that finished inside the window, over the window's
seconds and the chips; the window opens and closes on `block_until_ready`."""


def read(record):
    loop, w = record["loop"], record["window"]
    return (w["steps"] * loop["step_tokens"] / (w["t1"] - w["t0"])
            / record["chips"])
