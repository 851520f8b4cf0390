"""Share of the traced window's device self time under the program's
`mla_project` scope (`_mla_scopes`)."""

from . import _mla_scopes


def read(record):
    return _mla_scopes.share(record, "mla_project")
