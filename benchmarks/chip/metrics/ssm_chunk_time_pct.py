"""Share of the traced window's device self time under the program's
`ssm_chunk` scope (`_ssm_scopes`): the chunk program's SSD form of the Mamba-2 mixer."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.share(record, "ssm_chunk")
