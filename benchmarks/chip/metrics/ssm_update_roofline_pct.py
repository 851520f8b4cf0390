"""The one-token update of the SSM state in a decode step against the
chip's roofline: the least the step must move there (the family's
`ssm_update_cost`: every live slot's SSM state and convolution window of
every Mamba-2 layer read once and written once, float32; the operations
bound nothing), whatever implements it, over the step's device time under
the `ssm_update` scope (the kernel, the decay before it and D x after it;
the window's own pass is under `ssm_conv`, so the share under-reads by its
2.4% of the bytes). The slots a step had live are the window's tokens a
step, which counts the few chunk steps' tokens too and cannot pass the slots
the engine has."""

from . import _ssm_scopes


def read(record):
    return _ssm_scopes.roofline_pct(
        record, "ssm_update", "ssm_update_per_slot", "ssm_layers",
        _ssm_scopes.per_step(record, "total_generated"))
