"""Share of the traced window's device self time under the program's
`moe_router` scope (`_moe_scopes`)."""

from . import _moe_scopes


def read(record):
    return _moe_scopes.share(record, "moe_router")
