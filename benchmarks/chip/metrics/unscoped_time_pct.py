"""Share of the traced window's device self time under the program's
`unscoped` scope (`_scopes`): operations without a `tf_op`
(`copy-done`, `slice-done`, `while`) or outside every named scope."""

from . import _scopes


def read(record):
    return _scopes.share(record, "unscoped")
