"""Share of the traced window's device self time under the program's
`dsa_index` scope (`_dsa_scopes`): the indexer's projections and its
scores over a slot's `ik` rows."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.share(record, "dsa_index")
