"""`ray_tpu.init()` in the driver, whole: the head spawned and answering,
its node's chips detected, the driver connected (start-up span
`startup.init` of the driver's process)."""

from . import _startup


def read(record):
    found = _startup.named(record, "startup.init", role="driver")
    return _startup.seconds(found[0]) if found else None
