"""Share of the traced window's device self time under the program's
`ssm_conv` scope (`_ssm_scopes`): the convolution's window shifted, the depthwise convolution and its silu."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.share(record, "ssm_conv")
