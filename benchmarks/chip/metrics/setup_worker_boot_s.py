"""The chip's worker from the kernel starting its process to its
registration with the head: the interpreter, the imports before `main()`
and `worker.boot` itself (the span's `proc_start_ts` -> its end)."""

from . import _startup


def read(record):
    pid = _startup.chip_pid(record)
    found = _startup.named(record, "worker.boot", pid=pid) if pid else []
    if not found:
        return None
    boot = found[0]
    start = boot["attributes"].get("proc_start_ts") or boot["start_ts"]
    return boot["end_ts"] - start
