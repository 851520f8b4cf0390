"""The engine thread's own work for one step: the growth of the engine's
cumulative phase timers over the window, less `fetch` (waiting for the
device) and `empty` (nothing to run), over the engine steps taken."""

from . import _engine


def read(record):
    steps = _engine.delta(record, "engine_steps")
    c = record.get("counters")
    if not steps or not c or "phase_s" not in c["after"]:
        return None
    before, after = c["before"]["phase_s"], c["after"]["phase_s"]
    host = sum(after[k] - before[k] for k in after
               if k not in ("fetch", "empty"))
    return host * 1e3 / steps
