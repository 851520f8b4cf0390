"""The three Pallas attention calls of a train step (forward, dq, dk/dv)
against the chip's roofline: the family's `flash_attention_cost` (the
FLOPs the kernels execute, skipped causal blocks left out, and the bytes
they move) over their device time in one step. The larger of the compute
and the memory share is reported; `_moe_scopes.bound_seconds` says
which."""

from . import _moe_scopes


def read(record):
    return _moe_scopes.roofline_pct(record, "flash_attn", "flash_attention")
