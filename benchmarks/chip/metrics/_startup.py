"""The program's own start-up record, for the readers of `setup_s`'s layers.

Every process of a cluster writes its start-up spans (`ray_tpu/util/
tracing.py`: `startup_span`, `record_startup`; JAX's compiles by name from
`utils/platform.watch_compiles`) to
`<STATE_DIR>/<session>/logs/startup-<role>-<pid>.jsonl`, one JSON object a
line, on `time.time()`: the clock of the record's `marks`, `t_start` and
`window`. The run's sessions are in `<workdir>/cluster_sessions.txt`
(`harness/procs.start_cluster`), and `workdir` is the directory that holds
the record's `trace_dir`. A program that keeps no such record (a parent
commit) leaves no file: every reader then finds nothing and returns None.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys

import trace_reduce as tr

SESSIONS_FILE = "cluster_sessions.txt"
# the stages only the process that holds the chip goes through
CHIP_STAGES = ("engine.init", "train.compile")


@functools.lru_cache(maxsize=8)
def _load(workdir: str, state_dir: str) -> tuple:
    try:
        with open(os.path.join(workdir, SESSIONS_FILE)) as f:
            sessions = f.read().split()
    except OSError:
        return ()
    out = []
    for session in sessions:
        pattern = os.path.join(glob.escape(os.path.join(
            state_dir, session, "logs")), "**", "startup-*.jsonl")
        for path in sorted(glob.glob(pattern, recursive=True)):
            try:
                with open(path) as f:
                    lines = f.read().splitlines()
            except OSError:
                continue
            for line in lines:
                try:
                    span = json.loads(line)
                    span["start_ts"], span["end_ts"], span["attributes"]
                except (ValueError, KeyError, TypeError):
                    continue        # a line cut short by the process's end
                out.append(span)
    return tuple(out)


def spans(record: dict) -> tuple:
    """Every start-up span of every process of the run's clusters; empty
    when the run was not traced or the program wrote none."""
    trace_dir = record.get("trace_dir")
    if not trace_dir:
        return ()
    from ray_tpu.utils.platform import STATE_DIR

    return _load(os.path.dirname(trace_dir),
                 record.get("state_dir") or STATE_DIR)


def pid_of(span: dict):
    return span["attributes"].get("pid")


def named(record: dict, name: str, pid=None, role=None) -> list:
    """The run's spans called `name`, oldest first; of one process, or of
    one kind of process, where asked."""
    out = [s for s in spans(record) if s["name"] == name
           and (pid is None or pid_of(s) == pid)
           and (role is None or s["attributes"].get("role") == role)]
    return sorted(out, key=lambda s: s["start_ts"])


def chip_pid(record: dict):
    """The process that held the chip: the one that built an engine or
    compiled a train program. None when no process did."""
    for s in sorted(spans(record), key=lambda s: s["start_ts"]):
        if s["name"] in CHIP_STAGES:
            return pid_of(s)
    return None


def seconds(span: dict) -> float:
    return span["end_ts"] - span["start_ts"]


def total(record: dict, names, pid=None):
    """Seconds in the first span of each of `names` (of one process where
    asked); None unless every one is there."""
    out = 0.0
    for name in names:
        found = named(record, name, pid=pid)
        if not found:
            return None
        out += seconds(found[0])
    return out


def covered(intervals, lo: float, hi: float) -> list:
    """The union of `intervals` (pairs of times) clipped to [lo, hi]:
    sorted and disjoint."""
    return tr.union([(max(s, lo), min(e, hi)) for s, e in intervals])


def union_seconds(intervals, lo: float, hi: float) -> float:
    return tr.length(covered(intervals, lo, hi))


def compiles(record: dict) -> list:
    """The chip's process's `compile.*` spans that closed before the
    window opened, oldest first."""
    pid = chip_pid(record)
    if pid is None:
        return []
    t0 = record["window"]["t0"]
    out = [s for s in spans(record) if s["name"].startswith("compile.")
           and pid_of(s) == pid and s["end_ts"] <= t0]
    return sorted(out, key=lambda s: s["start_ts"])


def prepared_from(span: dict) -> float:
    """Where a span's work began: for a `compile.*` span its start less
    the tracing and lowering that JAX reported just before it, for any
    other its start."""
    a = span["attributes"]
    return span["start_ts"] - (a.get("trace_s") or 0.0) \
        - (a.get("lower_s") or 0.0)


def owned(record: dict, lo: float, hi: float) -> list:
    """What the run's spans cover of [lo, hi]: disjoint intervals in
    order."""
    return covered([(prepared_from(s), s["end_ts"]) for s in spans(record)],
                   lo, hi)


def log(message: str) -> None:
    print(f"[startup] {message}", file=sys.stderr, flush=True)
