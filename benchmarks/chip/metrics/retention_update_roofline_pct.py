"""The one-token state update of a decode step against the chip's
roofline: the least the step must move there (the family's
`retention_update_cost`: every live slot's S and z of every key-value head
and layer read once and written once, float32; the operations bound
nothing), over the step's device time under the `retention_update` scope
(the kernel, the expansions before it and the division after it). The
slots a step had live are the window's tokens a step, which counts the few
chunk steps' tokens too and cannot pass the slots the engine has."""

from . import _engine, _retention_scopes
from ._moe_scopes import bound_seconds


def read(record):
    costs = ((record.get("counters") or {}).get("after") or {}).get(
        "roofline_costs") or {}
    per_slot = costs.get("retention_update_per_slot")
    steps = _engine.delta(record, "engine_steps")
    seconds = _retention_scopes.step_seconds(record, "retention_update")
    if not per_slot or not steps or not seconds or not record.get("peaks"):
        return None
    slots = _engine.delta(record, "total_generated") / steps
    cost = {k: v * slots * costs["retention_layers"]
            for k, v in per_slot.items()}
    return 100.0 * bound_seconds(cost, record["peaks"])[1] / seconds
