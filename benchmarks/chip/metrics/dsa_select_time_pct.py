"""Share of the traced window's device self time under the program's
`dsa_select` scope (`_dsa_scopes`): the exact choice of the 2,048 largest of
a lane's scores."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.share(record, "dsa_select")
