"""Of the positions the indexer scored in the window's decode steps, the
share whose rows attention then read (`rows_selected` over
`positions_indexed`, the decode program's own counts): 100 below the
indexer's topk, topk over the position above it."""

from . import _dsa_scopes


def read(record):
    found = _dsa_scopes.decode_counts(record)
    if not found or not found[0]["positions_indexed"]:
        return None
    return 100.0 * found[0]["rows_selected"] / found[0]["positions_indexed"]
