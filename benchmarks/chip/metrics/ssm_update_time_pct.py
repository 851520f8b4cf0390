"""Share of the traced window's device self time under the program's
`ssm_update` scope (`_ssm_scopes`): the decode program's pass over the SSM state (the decay, the state-update kernel, D x)."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.share(record, "ssm_update")
