"""Share of the traced window's device self time under the program's
`gqa_attend` scope (`_ssm_scopes`): scores, softmax and weighted values over the cached keys and values of the attention layers."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.share(record, "gqa_attend")
