"""The experts' grouped matmuls against the chip's bf16 peak: the FLOPs
one step's tokens need in them, forward and backward (18·d·F·K a token:
the family's `experts_flops_per_token`; recomputation not counted), over
the step's device time under the `moe_experts` scope. The SwiGLU's
elementwise work between the products is in the time and not in the
FLOPs."""

from . import _moe_scopes


def read(record):
    return _moe_scopes.roofline_pct(record, "moe_experts", "experts")
