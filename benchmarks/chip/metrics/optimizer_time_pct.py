"""Share of the traced window's device self time under the program's
`optimizer` scope (`_scopes`)."""

from . import _scopes


def read(record):
    return _scopes.share(record, "optimizer")
