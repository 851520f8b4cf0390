"""Seconds of set-up in which the chip's process was preparing a program:
the union of its `compile.*` spans that closed before the window opened,
each from where JAX began to trace it (`trace_s`, `lower_s`), clipped to
[process start, window open]. The three longest go to the run's log."""

from . import _startup


def read(record):
    found = _startup.compiles(record)
    if not found:
        return None
    longest = sorted(found, key=_startup.seconds, reverse=True)[:3]
    _startup.log("longest compiles: " + ", ".join(
        f"{s['name'][len('compile.'):]} {_startup.seconds(s):.2f}s "
        f"({s['attributes'].get('cache')})" for s in longest))
    return _startup.union_seconds(
        [(_startup.prepared_from(s), s["end_ts"]) for s in found],
        record["t_start"], record["window"]["t0"])
