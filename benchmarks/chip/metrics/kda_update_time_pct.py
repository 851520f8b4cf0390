"""Share of the traced window's device self time under the program's
`kda_update` scope (`_kda_scopes`): the decode program's pass over the delta-rule state (the decay, the state-update kernel, the read-out)."""

from . import _kda_scopes


def read(record):
    return _kda_scopes.share(record, "kda_update")
