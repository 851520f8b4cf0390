"""Arithmetic on the client's log: the only place latencies and token
counts are computed.

A log entry is one request as the client saw it, times on the client's
clock in seconds:

    {"id", "due", "sent", "headers", "status", "events": [[t, n_tokens]...],
     "finish_reason", "done", "error", "cut", "max_tokens"}

`due` is when the schedule wanted the request sent (closed loop: when it
was sent). An event is one SSE message that carried text; with the
benchmark's one-character-a-token tokenizer its text length is its token
count. `cut` is set where the client ended a stream that was still open.
"""

from __future__ import annotations


def percentile(values: list, q: float):
    """Linear-interpolated percentile of a non-empty list; q in [0, 100]."""
    if not values:
        return None
    v = sorted(values)
    at = (len(v) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (at - lo)


def median(values: list):
    return percentile(values, 50.0)


def n_tokens(entry: dict) -> int:
    return sum(n for _, n in entry["events"])


def failed(entry: dict) -> bool:
    """Refused, errored, or not finished when the client stopped waiting."""
    return (entry.get("status") != 200 or bool(entry.get("error"))
            or entry.get("done") is None)


def ttft_ms(entry: dict):
    """First token seen minus when the request was due; None if no token."""
    if not entry["events"]:
        return None
    return (entry["events"][0][0] - entry["due"]) * 1e3


def tpot_ms(entry: dict):
    """(last token - first token) / (tokens - 1); None under two tokens."""
    n = n_tokens(entry)
    if n < 2:
        return None
    return (entry["events"][-1][0] - entry["events"][0][0]) / (n - 1) * 1e3


def headers_ms(entry: dict):
    if entry.get("headers") is None:
        return None
    return (entry["headers"] - entry["sent"]) * 1e3


def late_ms(entry: dict):
    return (entry["sent"] - entry["due"]) * 1e3


def due_in(log: list, t0: float, t1: float) -> list:
    return [e for e in log if t0 <= e["due"] < t1]


def ended_in(log: list, t0: float, t1: float) -> list:
    """Requests that ended in the window, well or badly, whenever they
    were sent (a closed loop's count: those the client cut at the close
    are not in it)."""
    out = []
    for e in log:
        end = e.get("done") or e.get("failed_at")
        if e.get("cut") is None and end is not None and t0 <= end < t1:
            out.append(e)
    return out


def tokens_between(log: list, t0: float, t1: float) -> int:
    """Tokens that reached the clients inside [t0, t1)."""
    return sum(n for e in log for t, n in e["events"] if t0 <= t < t1)


def met(entry: dict, limits: dict) -> bool:
    """Whether a request met both limits; a failed one met neither."""
    if failed(entry):
        return False
    ttft, tpot = ttft_ms(entry), tpot_ms(entry)
    return (ttft is not None and ttft <= limits["ttft_ms"]
            and (tpot is None or tpot <= limits["tpot_ms"]))
