"""Device self time under the scopes the DeepSeek-V3 serving programs add,
and what the engine's own counters say of a decode step.

`models/deepseek.py` puts, inside `attn`, `mla_project` (q, the
down-projection, its norm, RoPE, the absorption into the query and out of
the latent, the output projection) and `mla_attend` (scores, softmax and
weighted latents over the cache; the cache write stays `kv_update`), and
inside `mlp`, beside `models/moe.py`'s three, `moe_shared` (the shared
experts' SwiGLU). `_scopes.SCOPES` and `_moe_scopes.MOE_SCOPES` know none of
these (to them they are `attn`, `ln` and `mlp`, which is right), so this
file keeps its own set and the same arithmetic: self time, a loop's
duration less its body's; an operation belongs to the innermost of these
scopes on its path. A program without them gives None, not a number.

The step programs count what they did in a leaf of the cache, which the
engine's `stats()` reads (`step_counts`, each a uint32 that wraps) and the
family's server sends its unit costs beside (`roofline_costs`):
`decode_step_counts` gives the decode program's counts over the window,
a step.
"""

from __future__ import annotations

import functools
import re
from statistics import median

import trace_reduce as tr

from . import _events
from ._moe_scopes import bound_seconds

MLA_SCOPES = ("mla_attend", "mla_project", "moe_shared")
STEP_MODULE = "jit__step"
_WORD = re.compile(r"[A-Za-z_]\w*")


def mla_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/mla_project/ln/mul` ->
    `mla_project`; None outside the three."""
    if not tf_op or "/" not in tf_op:
        return None
    for word in reversed(_WORD.findall(tf_op.rsplit("/", 1)[0])):
        if word in MLA_SCOPES:
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
            scope = mla_scope_of(d["meta"].get(ident, {}).get("tf_op"))
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


def decode_step_counts(record):
    """({column: the decode program's count a step over the window}, the
    family's unit costs), or None where the engine counts nothing (a
    program without the counters) or ran no decode step."""
    c = record.get("counters") or {}
    before = (c.get("before") or {}).get("step_counts")
    after = (c.get("after") or {}).get("step_counts")
    costs = (c.get("after") or {}).get("roofline_costs")
    if not before or not after or not costs:
        return None
    steps = ((c["after"]["engine_steps"] - c["after"]["chunk_steps"])
             - (c["before"]["engine_steps"] - c["before"]["chunk_steps"]))
    if steps <= 0:
        return None
    return ({k: ((after["decode"][k] - before["decode"][k]) % 2 ** 32) / steps
             for k in after["decode"]}, costs)


def roofline_pct(record, cost: dict, seconds):
    """The least seconds the chip could take for `cost` (`bound_seconds`)
    over `seconds`, in per cent."""
    if not seconds or not record.get("peaks"):
        return None
    return 100.0 * bound_seconds(cost, record["peaks"])[1] / seconds
