"""Share of the traced window's device self time in the Pallas calls under
the program's `attn` scope (`_moe_scopes`): the attention kernels alone.
`attn_time_pct` beside it holds the projections and the residual add too.
A step with neither such a call nor a Mixture-of-Experts scope (GPT-2 on
XLA's dense attention) reads nothing."""

from . import _moe_scopes


def read(record):
    return _moe_scopes.share(record, "flash_attn")
