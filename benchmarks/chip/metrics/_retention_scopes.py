"""Device self time under the scopes the Brumby serving programs add.

`models/brumby.py` puts, inside `attn`, `retention_project` (q, k, v and the
gates, their norms and RoPE, the output projection), `retention_update`
(the decode program's one-token recurrence: the expansions, the Pallas
kernel over the state, the normalisers and the division) and
`retention_chunk` (the chunk program's form of it). `_scopes.SCOPES` knows
none of these (to it they are `attn` and `ln`, which is right), so this
file keeps its own set and `_events`' arithmetic: self time, a loop's
duration less its body's; an operation belongs to the innermost of these
scopes on its path. A program without them gives None, not a number.
"""

from __future__ import annotations

from . import _events

RETENTION_SCOPES = ("retention_update", "retention_chunk",
                    "retention_project")
STEP_MODULE = "jit__step"


def retention_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/retention_update/mul` ->
    `retention_update`; None outside the three."""
    return _events.innermost(tf_op, RETENTION_SCOPES)


share, step_seconds = _events.readers(retention_scope_of, STEP_MODULE)
