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
`_ssm_scopes`' arithmetic: self time, a loop's duration less its body's; an
operation belongs to the innermost of these scopes on its path. A program
without them gives None, not a number.
"""

from __future__ import annotations

import bisect
import functools
import re
from statistics import median

import trace_reduce as tr

from . import _engine, _events
from ._moe_scopes import bound_seconds

KDA_SCOPES = ("kda_update", "kda_chunk", "kda_project")
STEP_MODULE = "jit__step"
_WORD = re.compile(r"[A-Za-z_]\w*")


def kda_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/kda_update/mul` -> `kda_update`;
    None outside the three."""
    if not tf_op or "/" not in tf_op:
        return None
    for word in reversed(_WORD.findall(tf_op.rsplit("/", 1)[0])):
        if word in KDA_SCOPES:
            return word
    return None


@functools.lru_cache(maxsize=2)
def _times_of(path: str):
    """({scope: share of the window's device self time in per cent},
    {scope: median ns of self time inside one execution of the decode
    program's module}) over all devices, or None without the scopes."""
    devices, _ = _events.load(path)
    total: dict = {}
    whole = 0.0
    per_step: dict = {}
    for d in devices.values():
        steps = sorted((s, e) for s, e, name in d["modules"]
                       if STEP_MODULE in name)
        starts = [s for s, _ in steps]
        inside = [dict() for _ in steps]
        for ident, own in tr.self_intervals(d["ops"]):
            ns = tr.length(own)
            whole += ns
            scope = kda_scope_of(d["meta"].get(ident, {}).get("tf_op"))
            if scope is None or not own:
                continue
            total[scope] = total.get(scope, 0.0) + ns
            # the step that began last before it (a device runs one at a
            # time), if the operation ended inside it
            k = bisect.bisect_right(starts, own[0][0]) - 1
            if k >= 0 and own[-1][1] <= steps[k][1]:
                inside[k][scope] = inside[k].get(scope, 0.0) + ns
        for scope in total:
            per_step.setdefault(scope, []).extend(
                step.get(scope, 0.0) for step in inside)
    if not whole or not total:
        return None
    return ({k: 100.0 * v / whole for k, v in total.items()},
            {k: median(v) for k, v in per_step.items() if v})


def _times(record):
    path = _events.path_of(record)
    if not path:
        return None
    try:
        return _times_of(path)
    except (OSError, ValueError, IndexError, UnicodeDecodeError):
        return None


def share(record, scope: str):
    """Per cent of the traced window's device self time under `scope`;
    None when the run was not traced or the program has no such scopes."""
    times = _times(record)
    return None if times is None else times[0].get(scope, 0.0)


def step_seconds(record, scope: str):
    """Median device self time under `scope` inside one execution of the
    decode program, in seconds; None as above or without a whole step."""
    times = _times(record)
    ns = None if times is None else times[1].get(scope)
    return ns / 1e9 if ns else None


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
