"""Programs the chip's process prepared before the window that the
persistent compilation cache did not hand it (`cache` is `miss`: compiled
and written; or `off`: compiled and not written). 0 on every run of a cell
after its first in a checkout; the names go to the run's log."""

from . import _startup


def read(record):
    found = _startup.compiles(record)
    if not found:
        return None
    missed = [s for s in found if s["attributes"].get("cache") != "hit"]
    for s in missed:
        _startup.log(f"not from the cache: {s['name'][len('compile.'):]} "
                     f"{_startup.seconds(s):.2f}s "
                     f"({s['attributes'].get('cache')})")
    return float(len(missed))
