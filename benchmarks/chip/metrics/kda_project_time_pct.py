"""Share of the traced window's device self time under the program's
`kda_project` scope (`_kda_scopes`): the three projections and their convolution, both gates, the output norm, gate and W_o."""

from . import _kda_scopes


def read(record):
    return _kda_scopes.share(record, "kda_project")
