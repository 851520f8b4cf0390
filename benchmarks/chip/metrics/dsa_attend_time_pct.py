"""Share of the traced window's device self time under the program's
`dsa_attend` scope (`_dsa_scopes`): the gather of the chosen rows of k and
v, scores, softmax and weighted values over them."""

from . import _dsa_scopes


def read(record):
    return _dsa_scopes.share(record, "dsa_attend")
