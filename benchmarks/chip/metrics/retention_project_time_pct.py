"""Share of the traced window's device self time under the program's
`retention_project` scope (`_retention_scopes`): q, k, v, the gates, their
norms and RoPE, and the output projection."""

from . import _retention_scopes


def read(record):
    return _retention_scopes.share(record, "retention_project")
