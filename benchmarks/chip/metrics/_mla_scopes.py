"""Device self time under the scopes the DeepSeek-V3 serving programs add,
and what the engine's own counters say of a decode step.

`models/deepseek.py` puts, inside `attn`, `mla_project` (q, the
down-projection, its norm, RoPE, the absorption into the query and out of
the latent, the output projection) and `mla_attend` (scores, softmax and
weighted latents over the cache; the cache write stays `kv_update`), and
inside `mlp`, beside `models/moe.py`'s three, `moe_shared` (the shared
experts' SwiGLU). `_scopes.SCOPES` and `_moe_scopes.MOE_SCOPES` know none of
these (to them they are `attn`, `ln` and `mlp`, which is right), so this
file keeps its own set and `_events`' arithmetic: self time, a loop's
duration less its body's; an operation belongs to the innermost of these
scopes on its path. A program without them gives None, not a number.

The step programs count what they did in a leaf of the cache, which the
engine's `stats()` reads (`step_counts`, each a uint32 that wraps) and the
family's server sends its unit costs beside (`roofline_costs`):
`decode_step_counts` gives the decode program's counts over the window,
a step.
"""

from __future__ import annotations

from . import _events
from ._moe_scopes import bound_seconds

MLA_SCOPES = ("mla_attend", "mla_project", "moe_shared")
STEP_MODULE = "jit__step"


def mla_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/mla_project/ln/mul` ->
    `mla_project`; None outside the three."""
    return _events.innermost(tf_op, MLA_SCOPES)


share, step_seconds = _events.readers(mla_scope_of, STEP_MODULE)


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
