"""Share of the traced window's device self time under the program's
`layers` scope and no inner one: what the scan over the layers does itself
(a layer's weights and cache sliced in, results stacked out, the carries),
not the layers' own operations (`_scopes`)."""

from . import _scopes


def read(record):
    return _scopes.share(record, "layers")
