"""Attention over the chosen rows in a decode step against the chip's
roofline: the least the step must move or multiply there (the family's
`dsa_attend_cost`: each chosen row's key and value read once, 2,048 bytes,
or the products' operations; whichever bounds), every layer, over the
step's device time under the `dsa_attend` scope. The rows are the decode
program's own count (`rows_selected`: min(pos + 1, topk) a lane)."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.roofline_pct(record, "dsa_attend",
                                    "dsa_attend_per_row", "rows_selected")
