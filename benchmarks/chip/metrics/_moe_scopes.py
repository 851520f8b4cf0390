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
own set and the same arithmetic: self time, a loop's duration less its
body's. A program without the scopes gives None, not a number.
"""

from __future__ import annotations

import functools
import re
from statistics import median

import trace_reduce as tr

from . import _events

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts")
STEP_MODULE = "_step"
_WORD = re.compile(r"[A-Za-z_]\w*")


def moe_scope_of(tf_op):
    """`jit(_step)/jvp(layers)/while/body/mlp/moe_experts/jit(gmm)/
    pallas_call` -> `moe_experts`; None outside the block."""
    if not tf_op or "/" not in tf_op:
        return None
    for word in reversed(_WORD.findall(tf_op.rsplit("/", 1)[0])):
        if word in MOE_SCOPES:
            return word
    return None


def is_flash_call(tf_op) -> bool:
    """A Pallas kernel called from the `attn` scope."""
    if not tf_op or "/" not in tf_op:
        return False
    path, primitive = tf_op.rsplit("/", 1)
    return primitive.rstrip(":") == "pallas_call" \
        and "attn" in _WORD.findall(path)


def _label(tf_op):
    return "flash_attn" if is_flash_call(tf_op) else moe_scope_of(tf_op)


@functools.lru_cache(maxsize=2)
def _times_of(path: str):
    """({label: share of the window's device self time in per cent},
    {label: median ns of self time inside one execution of the step's
    module}) over all devices, or None for a program without the
    scopes."""
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
            label = _label(d["meta"].get(ident, {}).get("tf_op"))
            if label is None or not own:
                continue
            total[label] = total.get(label, 0.0) + ns
            for k, (s, e) in enumerate(steps):
                if s <= own[0][0] and own[-1][1] <= e:
                    inside[k][label] = inside[k].get(label, 0.0) + ns
                    break
        for label in total:
            per_step.setdefault(label, []).extend(
                step.get(label, 0.0) for step in inside)
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


def share(record, label: str):
    """Per cent of the traced window's device self time under `label`;
    None when the run was not traced or the program has no such scopes."""
    times = _times(record)
    return None if times is None else times[0].get(label, 0.0)


def step_seconds(record, label: str):
    """Median device self time under `label` inside one execution of the
    train step's module, in seconds; None as above, or when the trace
    holds no whole step."""
    times = _times(record)
    ns = None if times is None else times[1].get(label)
    return ns / 1e9 if ns else None


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
