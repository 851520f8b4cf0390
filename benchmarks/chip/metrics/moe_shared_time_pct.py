"""Share of the traced window's device self time under the program's
`moe_shared` scope (`_mla_scopes`)."""

from . import _mla_scopes


def read(record):
    return _mla_scopes.share(record, "moe_shared")
