"""Device self time under the scopes the Kimi serving programs add.

`models/kimi.py` puts, inside `attn` (the mixer's place), for a KDA layer
`kda_project` (the three projections and their convolution, both gates, the
output norm, gate and W_o), `kda_update` (the decode program's decay, the
Pallas kernel `ops/kda_update.py` over the state and its read-out) and
`kda_chunk` (the chunk program's chunked delta rule); an MLA layer keeps
`mla_project`, `mla_attend` and `kv_update`, the expert block `moe_router`,
`moe_dispatch`, `moe_experts` and `moe_shared`, which `_mla_scopes` and
`_moe_scopes` read. `_scopes.SCOPES` knows none of the three (to it they are
`attn` and `ln`, which is right), so this file keeps its own set and
`_events`' arithmetic: self time, a loop's duration less its body's; an
operation belongs to the innermost of these scopes on its path. A program
without them gives None, not a number.
"""

from __future__ import annotations

from . import _engine, _events
from ._moe_scopes import bound_seconds

KDA_SCOPES = ("kda_update", "kda_chunk", "kda_project")
STEP_MODULE = "jit__step"


def kda_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/kda_update/mul` -> `kda_update`;
    None outside the three."""
    return _events.innermost(tf_op, KDA_SCOPES)


share, step_seconds = _events.readers(kda_scope_of, STEP_MODULE)


def update_roofline_pct(record):
    """The least seconds the chip could take for a decode step's pass over
    the delta-rule state (the family's `kda_update_cost` a slot, carried in
    the replica's `stats()`, times the slots a step had live and the KDA
    layers) over the step's device time under `kda_update`, in per cent;
    None where any of it is missing. The slots a step had live are the
    window's tokens a step, which counts the few chunk steps' tokens too
    and cannot pass the slots the engine has."""
    costs = ((record.get("counters") or {}).get("after") or {}).get(
        "roofline_costs") or {}
    seconds = step_seconds(record, "kda_update")
    made = _engine.delta(record, "total_generated")
    steps = _engine.delta(record, "engine_steps")
    if (not costs.get("kda_update_per_slot") or not made or not steps
            or not seconds or not record.get("peaks")):
        return None
    units = made / steps * costs["kda_layers"]
    cost = {k: v * units for k, v in costs["kda_update_per_slot"].items()}
    return 100.0 * bound_seconds(cost, record["peaks"])[1] / seconds
