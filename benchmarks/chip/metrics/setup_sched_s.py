"""The scheduler's part in the start of the worker that held the chip:
`sched.place` (the request for chips arrived -> a worker chosen or a spawn
decided) + `sched.spawn` (`Popen` -> that worker registered), both in the
head's record and tied to the worker by its pid."""

from . import _startup


def read(record):
    pid = _startup.chip_pid(record)
    if pid is None:
        return None
    mine = [s for name in ("sched.place", "sched.spawn")
            for s in _startup.named(record, name, role="head")
            if s["attributes"].get("worker_pid") == pid]
    if not any(s["name"] == "sched.place" for s in mine):
        return None
    return sum(_startup.seconds(s) for s in mine)
