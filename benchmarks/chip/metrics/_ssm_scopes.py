"""Device self time under the scopes the Granite serving programs add.

`models/granite.py` puts, inside `attn` (the mixer's place), for a Mamba-2
layer `ssm_project` (W_in, dt's softplus, the gated norm, W_out), `ssm_conv`
(the window's shift, the convolution, silu), `ssm_update` (the decode
program's recurrence and read-out: the decay, the Pallas kernel
`ops/ssm_update.py` over the state, D x) and `ssm_chunk` (the chunk
program's SSD form), and for an attention layer `gqa_project` (q, k, v,
W_o) and `gqa_attend` (scores, softmax and weighted values over the cached
rows; the write stays `kv_update`). `_scopes.SCOPES` knows none of these
(to it they are `attn` and `ln`, which is right), so this file keeps its own
set and `_events`' arithmetic: self time, a loop's duration less its
body's; an operation belongs to the innermost of these scopes on its path.
A program without them gives None, not a number.
"""

from __future__ import annotations

from . import _engine, _events
from ._moe_scopes import bound_seconds

SSM_SCOPES = ("ssm_update", "ssm_conv", "ssm_project", "ssm_chunk",
              "gqa_attend", "gqa_project")
STEP_MODULE = "jit__step"


def ssm_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/ssm_update/mul` -> `ssm_update`;
    None outside the six."""
    return _events.innermost(tf_op, SSM_SCOPES)


share, step_seconds = _events.readers(ssm_scope_of, STEP_MODULE)


def roofline_pct(record, scope: str, per_unit: str, layers: str, units):
    """The least seconds the chip could take for a decode step's work under
    `scope` (the family's cost a unit, carried in the replica's `stats()`,
    times `units` a step and the layers of that kind) over the step's
    device time there, in per cent; None where any of it is missing."""
    costs = ((record.get("counters") or {}).get("after") or {}).get(
        "roofline_costs") or {}
    seconds = step_seconds(record, scope)
    if (not costs.get(per_unit) or not units or not seconds
            or not record.get("peaks")):
        return None
    cost = {k: v * units * costs[layers] for k, v in costs[per_unit].items()}
    return 100.0 * bound_seconds(cost, record["peaks"])[1] / seconds


def per_step(record, key: str):
    """A cumulative engine counter's rise over the window, a step."""
    rise, steps = _engine.delta(record, key), _engine.delta(record,
                                                            "engine_steps")
    return rise / steps if rise is not None and steps else None
