"""Share of the traced window's device self time under the program's
`attn` scope (`_scopes`) in a serving cell judged on tokens per second:
the reading `attn_time_pct` gives a training cell,
under a name of its own (that reader's entries are counted by a test of
PR 23's, which a later PR may not edit)."""

from . import _scopes


def read(record):
    return _scopes.share(record, "attn")
