"""The traced run's file, read once (`_xmeta`) for every reader that needs
more than the reduced trace holds: each device's operations with their
metadata and its module executions, and the host's threads, as
`(start_ns, end_ns, what)` on one clock. An operation's `what` is the id
of its metadata in the device's `meta` table (`meta[id]["name"]` is the
name `trace_reduce` shows); a module's and a host event's is its name.

And walked once (`walk`, `idlest`, `scope_times`): what every reader
of device self time needs of the half million operations of a traced
window is the same order, the same own intervals and the same idle gaps,
so they are made here, cached by the file's path as `load` is, and a
reader keeps only its own map from an operation's `tf_op` to a label."""

from __future__ import annotations

import bisect
import functools
import re
from statistics import median

import trace_reduce as tr

from . import _xmeta

_WORD = re.compile(r"[A-Za-z_]\w*")


def path_of(record):
    """The record's `.xplane.pb`, or None when the run was not traced."""
    trace_dir = record.get("trace_dir")
    return tr.newest_xplane(trace_dir) if trace_dir else None


def _named(events: list, meta: dict) -> list:
    return [(s, e, meta[i]["name"] if i in meta else "")
            for s, e, i in events]


@functools.lru_cache(maxsize=2)
def load(path: str) -> tuple:
    """({device plane: {"ops": [...], "modules": [...], "meta": {...}}},
    [host lines])."""
    devices, host_lines = {}, []
    for name, plane in _xmeta.read(path).items():
        if tr.DEVICE_PLANE.match(name):
            lines = dict(plane["lines"])
            if lines.get(tr.OPS_LINE):
                devices[name] = {
                    "ops": lines[tr.OPS_LINE],
                    "modules": _named(lines.get(tr.MODULES_LINE, []),
                                      plane["meta"]),
                    "meta": plane["meta"]}
        elif name == tr.HOST_PLANE:
            host_lines = [_named(events, plane["meta"])
                          for _, events in plane["lines"]]
    return devices, host_lines


def walked(ops: list) -> dict:
    """One device's `(start, end, metadata id)` operations, walked:
    {"own": [(metadata id, its own intervals, their ns) ...] in
    `trace_reduce.start_order`, "leaves": the operations that enclose no
    other, in the same order}."""
    order = tr.start_order(ops)
    return {"own": [(ident, own, tr.length(own))
                    for ident, own in tr.self_intervals(ops, order)],
            "leaves": tr.leaves(ops, order)}


@functools.lru_cache(maxsize=2)
def walk(path: str) -> dict:
    """{device plane: its operations `walked`}, once a file."""
    return {name: walked(d["ops"]) for name, d in load(path)[0].items()}


@functools.lru_cache(maxsize=8)
def _scope_times_of(path: str, label_of, step_module: str):
    devices, walked = load(path)[0], walk(path)
    total: dict = {}
    whole = 0.0
    per_step: dict = {}
    for name, d in devices.items():
        steps = sorted((s, e) for s, e, module in d["modules"]
                       if step_module in module)
        starts = [s for s, _ in steps]
        inside = [dict() for _ in steps]
        labels: dict = {}             # by metadata id: one lookup an id
        for ident, own, ns in walked[name]["own"]:
            whole += ns
            if ident not in labels:
                labels[ident] = label_of(d["meta"].get(ident, {}).get("tf_op"))
            label = labels[ident]
            if label is None or not own:
                continue
            total[label] = total.get(label, 0.0) + ns
            # the step that began last before it (a device runs one at a
            # time), if the operation ended inside it
            k = bisect.bisect_right(starts, own[0][0]) - 1
            if k >= 0 and own[-1][1] <= steps[k][1]:
                inside[k][label] = inside[k].get(label, 0.0) + ns
        for label in total:
            per_step.setdefault(label, []).extend(
                step.get(label, 0.0) for step in inside)
    if not whole or not total:
        return None
    return ({k: 100.0 * v / whole for k, v in total.items()},
            {k: median(v) for k, v in per_step.items() if v})


def scope_times(record, label_of, step_module: str):
    """({label: share of the traced window's device self time in per
    cent}, {label: median ns of self time inside one execution of the
    module whose name holds `step_module`}) over all devices, where
    `label_of` maps an operation's `tf_op` to a label or to None: self
    time, a loop's duration less its body's. None when the run was not
    traced or no operation has a label (a program without the scopes)."""
    path = path_of(record)
    if not path:
        return None
    try:
        return _scope_times_of(path, label_of, step_module)
    except (OSError, ValueError, IndexError, UnicodeDecodeError):
        return None


def readers(label_of, step_module: str) -> tuple:
    """(`share`, `step_seconds`) of one family of labels, what a scope
    reader's file hands its metric files: `share(record, label)`, per cent
    of the traced window's device self time under `label` (0.0 for one
    that took no time), and `step_seconds(record, label)`, the median
    device self time under it inside one execution of the step's module
    (None without a whole step); both None where `scope_times` is."""
    def share(record, label: str):
        times = scope_times(record, label_of, step_module)
        return None if times is None else times[0].get(label, 0.0)

    def step_seconds(record, label: str):
        times = scope_times(record, label_of, step_module)
        ns = None if times is None else times[1].get(label)
        return ns / 1e9 if ns else None

    return share, step_seconds


def innermost(tf_op, scopes):
    """`jit(_step)/layers/while/body/attn/kda_update/mul` -> `kda_update`
    for `scopes` that hold it: the innermost of them on the operation's
    path (its last component is the primitive, never a scope); None
    outside them all."""
    if not tf_op or "/" not in tf_op:
        return None
    for word in reversed(_WORD.findall(tf_op.rsplit("/", 1)[0])):
        if word in scopes:
            return word
    return None


@functools.lru_cache(maxsize=2)
def idlest(path: str) -> tuple:
    """(plane name, its idle gaps, the window's ns) as `trace_reduce`
    takes them: the window runs from the first operation's start to the
    last one's end over all devices, the idlest device is the least busy."""
    devices = load(path)[0]
    t0 = min(s for d in devices.values() for s, _, _ in d["ops"])
    t1 = max(e for d in devices.values() for _, e, _ in d["ops"])
    busy = {name: tr.union([[s, e] for s, e, _ in d["ops"]])
            for name, d in devices.items()}
    worst = min(sorted(busy), key=lambda k: tr.length(busy[k]))
    return worst, tr.subtract([[t0, t1]], busy[worst]), t1 - t0
