"""Share of the traced window's device self time under the program's
`kv_update` scope (`_scopes`)."""

from . import _scopes


def read(record):
    return _scopes.share(record, "kv_update")
