"""Grouped-head attention over the cached rows in a decode step against the
chip's roofline: the least the step must move or multiply there (the
family's `gqa_attend_cost`: keys and values by the 8 key-value heads up to
each slot's position read once, or the products' operations; whichever
bounds), every attention layer, over the step's device time under the
`gqa_attend` scope. The positions a step attended over are the engine's own
count (`positions_attended`: each lane's position in its step, summed) over
the window's steps."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.roofline_pct(
        record, "gqa_attend", "gqa_attend_per_position", "gqa_layers",
        _ssm_scopes.per_step(record, "positions_attended"))
