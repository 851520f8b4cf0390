"""The experts' grouped matmuls in a decode step against the chip's
roofline: the least the step must move or multiply there (the family's
`moe_experts_decode_cost`: the weights of the experts that got a row, by
the decode program's own count, not of all of them; the rows in and out;
6 d F operations a row; whichever bounds), over the step's device time
under the `moe_experts` scope, all expert layers."""

from . import _moe_scopes, _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found:
        return None
    counts, costs = found
    row, expert = (costs["moe_experts_per_row"],
                   costs["moe_experts_per_touched_expert"])
    cost = {k: counts["expert_rows"] * row[k]
            + counts["experts_touched"] * expert[k] for k in ("flops",
                                                              "bytes")}
    return _mla_scopes.roofline_pct(
        record, cost, _moe_scopes.step_seconds(record, "moe_experts"))
