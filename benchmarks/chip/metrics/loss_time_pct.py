"""Share of the traced window's device self time under the program's
`unembed_loss` scope: the final LayerNorm's neighbours, the unembedding
matmul and the cross-entropy (`_scopes`)."""

from . import _scopes


def read(record):
    return _scopes.share(record, "unembed_loss")
