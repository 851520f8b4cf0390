"""Phases as child sessions that leave nothing behind.

The command's own process never imports JAX (a process that has touched
JAX holds the chip). Each phase is a child in a session of its own: the
driver of a `ray_tpu` cluster whose worker owns the chip, or a plain JAX
process once the cluster is down. The child and all it started are gone,
and the `/dev/shm` segments of the clusters it started removed, before the
next phase may open the chip. The sessions are `chip_smoke.py`'s (PR 21).
Its way with `/dev/shm` is not: it unlinks whatever appeared there during
a phase, and `/dev/shm` is shared with every other checkout on the
machine, so a phase here names the clusters it starts (`start_cluster`)
and only their segments are removed.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time


SESSIONS_FILE = "cluster_sessions.txt"
SHM_DIR = "/dev/shm"


def start_cluster(workdir: str) -> dict:
    """`ray_tpu.init()` in a phase, with the new cluster's session written
    into the run's directory before anything else happens, so that the
    parent can remove that cluster's `/dev/shm` segments, and no others,
    however the phase ends."""
    import ray_tpu

    info = ray_tpu.init()
    with open(os.path.join(workdir, SESSIONS_FILE), "a") as f:
        f.write(info["session"] + "\n")
    return info


def remove_cluster_shm(workdir: str) -> int:
    """Unlinks what the clusters named in `workdir` left in `/dev/shm`.
    The program names a cluster's arena `rtpu_arena_[<node>_]<session>`
    and an object's segment `rtpu_[<node>_]<session[:8]>_<object>_...`
    (`core/head_main.py` clears a dead predecessor's by the same pattern).
    A session is `s` and 12 random hex digits and every other part is hex,
    so the pattern meets no other run's segments."""
    try:
        with open(os.path.join(workdir, SESSIONS_FILE)) as f:
            sessions = f.read().split()
    except OSError:
        return 0
    removed = 0
    for session in sessions:
        for seg in glob.glob(os.path.join(
                SHM_DIR, f"rtpu_*{glob.escape(session[:8])}*")):
            try:
                os.unlink(seg)
                removed += 1
            except OSError:
                pass
    return removed


def _session_pids(sid: int) -> list:
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        # after the "(comm)" field: state ppid pgrp session ...
        if int(fields[3]) == sid and fields[0] != "Z":
            pids.append(int(name))
    return pids


def _wait_session_empty(sid: int, seconds: float) -> list:
    deadline = time.monotonic() + seconds
    while (left := _session_pids(sid)) and time.monotonic() < deadline:
        time.sleep(0.1)
    return left


def _end_session(sid: int) -> int:
    left = _wait_session_empty(sid, 10)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    _wait_session_empty(sid, 10)
    return len(left)


def run_phase(argv: list, result_path: str, limit_s: float, log) -> dict:
    """Runs `argv` in a new session, waits for it and for everything it
    started, removes the `/dev/shm` segments its clusters left, and
    returns what it wrote to `result_path` with `ok` false if it failed,
    overran or left a process that would not die."""
    t0 = time.monotonic()
    proc = subprocess.Popen(argv, start_new_session=True,
                            stdout=sys.stderr, stderr=sys.stderr)
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        rc = None
    left_procs = _end_session(proc.pid)
    if rc is None:
        proc.wait()
    left_shm = remove_cluster_shm(os.path.dirname(result_path))
    try:
        with open(result_path) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"ok": False, "error": "the phase wrote no result"}
    if rc is None:
        result.update(ok=False, error=f"the phase exceeded its {limit_s:.0f}s")
    elif rc != 0:
        result["ok"] = False
        result.setdefault("error", f"the phase exited with code {rc}")
    still = _session_pids(proc.pid)
    if still:
        result.update(ok=False, error=f"processes {still} outlived the phase")
    log(f"{'ok' if result['ok'] else 'FAILED: ' + result['error']} "
        f"[{time.monotonic() - t0:.1f}s; left behind and removed: "
        f"{left_procs} processes, {left_shm} /dev/shm segments]")
    return result
