"""The selection in a decode step against the chip's roofline: reading the
float32 score of every position up to each lane's own once (the family's
`dsa_select_cost`), every layer, over the step's device time under the
`dsa_select` scope: the selection is an operation of its own
(`lax.top_k`), not fused into the indexer's."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.roofline_pct(record, "dsa_select",
                                    "dsa_select_per_position",
                                    "positions_indexed")
