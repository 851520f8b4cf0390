"""Device self time under the scopes the Keye serving programs add, and the
programs' own counts of what the sparse attention read.

`models/keye.py` puts, inside `attn`, `dsa_index` (the indexer's
projections, its key's norm and rotation, and its scores over a slot's `ik`
rows), `dsa_select` (the choice of the 2,048 largest: `lax.top_k`, an
operation of its own) and `dsa_attend` (the gather of the chosen rows of k
and v, scores, softmax and weighted values; in the chunk program the same
set as a mask over a slot's rows), beside `gqa_project` (q, k, v, their
norms and rotation, W_o) and `kv_update` (three leaves). `_scopes.SCOPES`
knows none of the three (to it they are `attn` and `ln`, which is right),
so this file keeps its own set and `_events`' arithmetic: self time, a
loop's duration less its body's; an operation belongs to the innermost of
these scopes on its path. A program without them gives None, not a number.

The step programs count, once a step over the valid lanes, the positions
the indexer scored (`positions_indexed`: pos + 1 a lane) and the rows the
choice left (`rows_selected`: min(pos + 1, topk) a lane), in the cache's
`counts` leaf (`_mla_scopes.decode_step_counts` reads the decode program's,
a step); the family's server sends its unit costs beside
(`roofline_costs`: a scored position's, a chosen row's, a layer).
"""

from __future__ import annotations

from . import _events, _mla_scopes

DSA_SCOPES = ("dsa_index", "dsa_select", "dsa_attend")
STEP_MODULE = "jit__step"


def dsa_scope_of(tf_op):
    """`jit(_step)/layers/while/body/attn/dsa_index/ln/mul` -> `dsa_index`;
    None outside the three."""
    return _events.innermost(tf_op, DSA_SCOPES)


share, step_seconds = _events.readers(dsa_scope_of, STEP_MODULE)


def decode_counts(record):
    """(the decode program's counts a step, the family's unit costs) where
    the program counts what the indexer scored; None elsewhere."""
    found = _mla_scopes.decode_step_counts(record)
    if not found or "positions_indexed" not in found[0] \
            or "dsa_layers" not in found[1]:
        return None
    return found


def roofline_pct(record, scope: str, per_unit: str, count: str):
    """The least seconds the chip could take for a decode step's work under
    `scope` (the family's cost a unit times the step's `count` and the
    layers) over the step's device time there, in per cent; None where any
    of it is missing."""
    found = decode_counts(record)
    if not found or not found[0][count] or per_unit not in found[1]:
        return None
    counts, costs = found
    cost = {k: v * counts[count] * costs["dsa_layers"]
            for k, v in costs[per_unit].items()}
    return _mla_scopes.roofline_pct(record, cost,
                                    step_seconds(record, scope))
