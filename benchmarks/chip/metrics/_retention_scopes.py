"""Device self time under the scopes the Brumby serving programs add.

`models/brumby.py` puts, inside `attn`, `retention_project` (q, k, v and the
gates, their norms and RoPE, the output projection), `retention_update`
(the decode program's one-token recurrence: the expansions, the Pallas
kernel over the state, the normalisers and the division) and
`retention_chunk` (the chunk program's form of it). `_scopes.SCOPES` knows
none of these (to it they are `attn` and `ln`, which is right), so this
file keeps its own set and `_mla_scopes`' arithmetic: self time, a loop's
duration less its body's; an operation belongs to the innermost of these
scopes on its path. A program without them gives None, not a number.
"""

from __future__ import annotations

import functools
import re
from statistics import median

import trace_reduce as tr

from . import _events

RETENTION_SCOPES = ("retention_update", "retention_chunk",
                    "retention_project")
STEP_MODULE = "jit__step"
_WORD = re.compile(r"[A-Za-z_]\w*")


def retention_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/retention_update/mul` ->
    `retention_update`; None outside the three."""
    if not tf_op or "/" not in tf_op:
        return None
    for word in reversed(_WORD.findall(tf_op.rsplit("/", 1)[0])):
        if word in RETENTION_SCOPES:
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
        inside = [dict() for _ in steps]
        for ident, own in tr.self_intervals(d["ops"]):
            ns = tr.length(own)
            whole += ns
            scope = retention_scope_of(d["meta"].get(ident, {}).get("tf_op"))
            if scope is None or not own:
                continue
            total[scope] = total.get(scope, 0.0) + ns
            for k, (s, e) in enumerate(steps):
                if s <= own[0][0] and own[-1][1] <= e:
                    inside[k][scope] = inside[k].get(scope, 0.0) + ns
                    break
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
