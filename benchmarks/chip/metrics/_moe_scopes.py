"""Device self time inside the Mixture-of-Experts block and the attention
kernels, by what the program's operation names say (`_xmeta`: an
operation's `tf_op` is its JAX name stack).

`models/moe.py` puts the block's parts under `jax.named_scope`s inside the
`mlp` scope: `moe_router` (router product, softmax, top-k, the two router
losses), `moe_dispatch` (sort, gathers, the weighted sum back) and
`moe_experts` (the three grouped matmuls and the SwiGLU between them).
`ops/flash_attention.py`'s three Pallas calls are the operations whose
primitive is `pallas_call` under the `attn` scope. `_scopes.SCOPES` knows
none of these (to it they are `mlp` and `attn`), so this file keeps its
own set and `_events`' arithmetic: self time, a loop's duration less its
body's. A program without the scopes gives None, not a number.
"""

from __future__ import annotations

import re

from . import _events

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts")
STEP_MODULE = "_step"
_WORD = re.compile(r"[A-Za-z_]\w*")


def moe_scope_of(tf_op):
    """`jit(_step)/jvp(layers)/while/body/mlp/moe_experts/jit(gmm)/
    pallas_call` -> `moe_experts`; None outside the block."""
    return _events.innermost(tf_op, MOE_SCOPES)


def is_flash_call(tf_op) -> bool:
    """A Pallas kernel called from the `attn` scope."""
    if not tf_op or "/" not in tf_op:
        return False
    path, primitive = tf_op.rsplit("/", 1)
    return primitive.rstrip(":") == "pallas_call" \
        and "attn" in _WORD.findall(path)


def _label(tf_op):
    return "flash_attn" if is_flash_call(tf_op) else moe_scope_of(tf_op)


share, step_seconds = _events.readers(_label, STEP_MODULE)


def bound_seconds(cost: dict, peaks: dict) -> tuple:
    """(`flops` or `bytes`, the least seconds the chip could take for
    `cost`): the larger of operations over the bf16 peak and bytes over
    the memory bandwidth, and which of the two it is."""
    by = {"flops": cost["flops"] / peaks["bf16_flops_per_s"],
          "bytes": cost.get("bytes", 0.0) / peaks["hbm_bytes_per_s"]}
    which = max(by, key=by.get)
    return which, by[which]


def roofline_pct(record, label: str, cost_key: str):
    """`bound_seconds` of the family's cost for one step (the train
    cell's reference check carries `costs`, computed by the family's
    functions) over the step's device time under `label`, in per cent."""
    seconds = step_seconds(record, label)
    cost = (record["loop"]["reference_check"].get("costs") or {}).get(
        cost_key)
    if not seconds or not cost or not record.get("peaks"):
        return None
    return 100.0 * bound_seconds(cost, record["peaks"])[1] / seconds
