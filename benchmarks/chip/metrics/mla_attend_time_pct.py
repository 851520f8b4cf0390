"""Share of the traced window's device self time under the program's
`mla_attend` scope (`_mla_scopes`)."""

from . import _mla_scopes


def read(record):
    return _mla_scopes.share(record, "mla_attend")
