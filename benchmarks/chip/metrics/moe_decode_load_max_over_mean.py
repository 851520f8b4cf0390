"""The busiest expert's rows over the mean expert's, an expert layer of a
decode step, as the decode program counts them itself
(`_mla_scopes.decode_step_counts`): 1.0 is perfect balance. Of the window's
own steps, where `moe_load_max_over_mean` is of a training cell's first
batch."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found or not found[0]["expert_rows"]:
        return None
    counts, costs = found
    return (counts["busiest_expert_rows"] * costs["routed_experts"]
            / counts["expert_rows"])
