"""`LLMEngine.__init__`, whole (start-up span `engine.init`): the weights
made from the seed, converted to what the step programs read, the cache and
the prefix pool, each waited for on the device."""

from . import _startup


def read(record):
    return _startup.total(record, ["engine.init"])
