"""The indexer in a decode step against the chip's roofline: the least the
step must move or multiply there (the family's `dsa_index_cost`: the one
indexer key of every position up to each lane's own read once, 128 bytes,
or the 16 heads' products; whichever bounds), every layer, over the step's
device time under the `dsa_index` scope. The positions are the decode
program's own count (`positions_indexed`)."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.roofline_pct(record, "dsa_index",
                                    "dsa_index_per_position",
                                    "positions_indexed")
