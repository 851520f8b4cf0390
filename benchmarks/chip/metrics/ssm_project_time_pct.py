"""Share of the traced window's device self time under the program's
`ssm_project` scope (`_ssm_scopes`): W_in, dt's softplus, the gated norm and W_out of the Mamba-2 layers."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.share(record, "ssm_project")
