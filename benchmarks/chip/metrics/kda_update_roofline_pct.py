"""The one-token delta-rule update in a decode step against the chip's
roofline: the least the step must move there (the family's
`kda_update_cost`: every live slot's state S and convolution window of every
KDA layer read once and written once, float32; the operations bound
nothing), whatever implements it, over the step's device time under the
`kda_update` scope (the decay, the kernel and its read-out; the window's own
pass is under `kda_project`, so the share under-reads by its 6.6% of the
bytes)."""

from . import _kda_scopes


def read(record):
    return _kda_scopes.update_roofline_pct(record)
