"""Share of the traced window's device self time under the program's
`retention_chunk` scope (`_retention_scopes`): the chunk program's form of
power retention."""

from . import _retention_scopes


def read(record):
    return _retention_scopes.share(record, "retention_chunk")
