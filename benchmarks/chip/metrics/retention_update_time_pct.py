"""Share of the traced window's device self time under the program's
`retention_update` scope (`_retention_scopes`): the decode program's pass
over the recurrent state."""

from . import _retention_scopes


def read(record):
    return _retention_scopes.share(record, "retention_update")
