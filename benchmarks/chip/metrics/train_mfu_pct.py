"""Model FLOP/s utilization of the steady step: tokens per step over the
median time between one step's landing and the next (on the host's clock,
so a stall while the profiler starts or stops does not count), times the
family's FLOPs per token (recomputation not counted), over the chips' bf16
peak. Without a trace it equals the tokens per second per chip times a
constant."""

from harness import client_log


def read(record):
    peak = record["peaks"].get("bf16_flops_per_s")
    landed = record["window"]["landed_at"]
    gaps = [b - a for a, b in zip(landed, landed[1:])]
    if not peak or not gaps:
        return None
    loop = record["loop"]
    return (100.0 * loop["step_tokens"] / client_log.median(gaps)
            * loop["flops_per_token"] / (record["chips"] * peak))
