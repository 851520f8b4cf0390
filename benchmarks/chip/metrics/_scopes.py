"""Device self time by the program's named scope.

The step programs put every part under a `jax.named_scope`
(`ray_tpu/models/gpt2.py`, `train/spmd.py`, `serve/kv_cache.py`); the scope
shows in an operation's `tf_op` path (`_xmeta`). An operation belongs to
the innermost known scope of its path (`attn/weights_cast/convert...` is
`weights_cast`); one with no `tf_op` or no known scope is `unscoped`. A
fusion carries one operation's path, so a share is as exact as the
compiler's choice of it. Self time as in `trace_reduce`: a loop's duration
less its body's, so nothing counts twice and the shares sum to 100.
"""

from __future__ import annotations

import functools
import re

from . import _events

SCOPES = frozenset({"embed", "ln", "attn", "mlp", "unembed_loss",
                    "optimizer", "layers", "kv_update", "weights_cast",
                    "prefix_pool"})
UNSCOPED = "unscoped"
_WORD = re.compile(r"[A-Za-z_]\w*")


def scope_of(tf_op) -> str:
    """`jit(_step)/transpose(jvp(attn))/while/body/checkpoint/attn/
    weights_cast/convert_element_type:` -> `weights_cast`. The last
    component is the primitive, never a scope."""
    if not tf_op:
        return UNSCOPED
    path = tf_op.rsplit("/", 1)[0] if "/" in tf_op else ""
    for word in reversed(_WORD.findall(path)):
        if word in SCOPES:
            return word
    return UNSCOPED


def self_time_by_scope(own: list, meta: dict) -> dict:
    """ns of self time by scope, for one device's walk (`_events.walked`'s
    `own`) and its table of metadata."""
    out: dict = {}
    scopes: dict = {}                 # by metadata id: one lookup an id
    for ident, _, ns in own:
        if ident not in scopes:
            scopes[ident] = scope_of(meta.get(ident, {}).get("tf_op"))
        out[scopes[ident]] = out.get(scopes[ident], 0.0) + ns
    return out


@functools.lru_cache(maxsize=2)
def _shares_of(path: str):
    devices, _ = _events.load(path)
    walked = _events.walk(path)
    total: dict = {}
    for name, d in devices.items():
        for scope, ns in self_time_by_scope(walked[name]["own"],
                                            d["meta"]).items():
            total[scope] = total.get(scope, 0.0) + ns
    whole = sum(total.values())
    if not whole or set(total) <= {UNSCOPED}:
        return None                   # a program without the scopes
    return {scope: 100.0 * ns / whole for scope, ns in total.items()}


def share(record, scope: str):
    """Per cent of the traced window's device self time (all devices)
    spent in `scope`; 0.0 when the program has scopes and this one took
    no time; None when the run was not traced or the program has none."""
    path = _events.path_of(record)
    if not path:
        return None
    try:
        shares = _shares_of(path)
    except (OSError, ValueError, IndexError, UnicodeDecodeError):
        return None
    return None if shares is None else shares.get(scope, 0.0)
