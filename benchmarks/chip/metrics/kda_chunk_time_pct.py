"""Share of the traced window's device self time under the program's
`kda_chunk` scope (`_kda_scopes`): the chunk program's chunked delta rule over a prefilling slot's further lanes."""

from . import _kda_scopes


def read(record):
    return _kda_scopes.share(record, "kda_chunk")
