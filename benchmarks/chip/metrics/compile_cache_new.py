"""Files added to the persistent compilation cache during the run; 0 on
every run of a cell after its first in a checkout."""


def read(record):
    return record["cache_new"]
