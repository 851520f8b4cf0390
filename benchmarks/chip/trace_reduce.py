"""From a profiler trace (`*.xplane.pb`) to the numbers the benchmark reads.

The JAX profiler writes one plane per device (`/device:TPU:<n>`) with a
line of XLA operations (`XLA Ops`, possibly nested: a `while` spans its
body's operations) and a line of XLA modules (`XLA Modules`, one event for
each execution of a jitted program), and a host plane (`/host:CPU`) with a
line for each thread. Times are nanoseconds on one clock.

All arithmetic is on intervals `(start, end)`:

- busy: the union of a device's operation intervals; idle = window - busy,
  where the window runs from the first operation's start to the last
  one's end over all devices;
- an operation's self time: its duration less that of the operations
  nested directly in it, so that a loop is not counted on top of its body;
- collective time: the union of the collective operations' self
  intervals, with those of the `Async XLA Ops` line (a collective in
  flight from its start to its done); its exposed part: that union less
  the union of every other operation's self intervals on the same device;
- idle gaps are laid to what the dispatching host thread was doing in
  them: the thread that launches the device's programs (it carries the
  `PjitFunction(...)` events), its events' self time inside the gaps of
  the idlest device, summed by name; what no event of that thread covers
  is `unattributed`.

Checked against a recorded trace in `tests/chip_bench/test_trace_reduce.py`.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from statistics import median

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
DISPATCH_MARKS = ("PjitFunction(", "PJRT_LoadedExecutable_Execute")
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast")


# ------------------------------------------------------- interval arithmetic

def union(intervals: list) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def length(disjoint: list) -> float:
    return sum(e - s for s, e in disjoint)


def subtract(a: list, b: list) -> list:
    """Points of the disjoint sorted intervals `a` not in those of `b`."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append([at, e])
    return out


def overlap(intervals: list, disjoint: list, starts=None) -> float:
    """Length of the part of `intervals` inside the disjoint sorted ones;
    `starts`, their starts, where the caller asks many times."""
    if starts is None:
        starts = [s for s, _ in disjoint]
    total = 0.0
    for s, e in intervals:
        k = max(bisect.bisect_right(starts, s) - 1, 0)
        while k < len(disjoint) and disjoint[k][0] < e:
            total += max(0.0, min(e, disjoint[k][1]) - max(s, disjoint[k][0]))
            k += 1
    return total


def start_order(events: list) -> list:
    """The indices of one line's events by start, an enclosing event
    before what it encloses: the one order every walk of a line takes."""
    return sorted(range(len(events)),
                  key=lambda i: (events[i][0], -events[i][1]))


def self_intervals(events: list, order=None) -> list:
    """For `(start, end, name)` events of one line, nested or not:
    `(name, [intervals])` in `start_order` with each event's own
    intervals, its directly nested events cut out."""
    if order is None:
        order = start_order(events)
    children: dict = {}
    stack: list = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            children.setdefault(stack[-1], []).append([s, e])
        stack.append(i)
    out = []
    for i in order:
        s, e, name = events[i]
        if i in children:
            own = subtract([[s, e]], union(children[i]))
        else:
            own = [[s, e]] if s < e else []
        out.append((name, own))
    return out


def leaves(events: list, order=None) -> list:
    """The events that enclose no other, in `start_order`."""
    if order is None:
        order = start_order(events)
    ordered = [events[i] for i in order]
    return [ev for ev, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]]


# ------------------------------------------------------------- the reduction

def _events(line) -> list:
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _clean(name: str) -> str:
    """An HLO instruction's text cut to its name and first result shape;
    a Python frame without the tracer's `$`; at most 80 characters."""
    hlo = re.match(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[^\]]*\])", name)
    if hlo:
        name = f"{hlo.group(1)} {hlo.group(2)}"
    return re.sub(r"\s+", " ", name).strip().lstrip("$")[:80]


def newest_xplane(trace_dir: str):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def reduce_dir(trace_dir: str):
    path = newest_xplane(trace_dir)
    return reduce_file(path) if path else None


def reduce_file(path: str):
    """The reduced trace, or None when it holds no device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_lines = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = {
                    name: _events(lines[line]) if line in lines else []
                    for name, line in (("ops", OPS_LINE),
                                       ("modules", MODULES_LINE),
                                       ("async", ASYNC_LINE))}
        elif plane.name == HOST_PLANE:
            host_lines = [_events(ln) for ln in plane.lines]
    devices = {k: v for k, v in devices.items() if v["ops"]}
    if not devices:
        return None
    return reduce_events(devices, dispatch_thread(host_lines))


def dispatch_thread(host_lines: list) -> list:
    """The events of the host thread that launches the device's programs:
    the one with the most dispatch marks; [] when none has any."""
    def marks(events):
        return sum(name.startswith(DISPATCH_MARKS) for _, _, name in events)

    best = max(host_lines, key=marks, default=[])
    return best if marks(best) else []


def reduce_events(devices: dict, host_events: list) -> dict:
    """`devices`: plane name -> {"ops": [(start, end, name)...], "modules":
    [...], "async": [...]}, times in ns; `host_events`: the dispatching
    thread's. See the module's docstring for what comes out; seconds
    throughout."""
    t0 = min(s for d in devices.values() for s, _, _ in d["ops"])
    t1 = max(e for d in devices.values() for _, e, _ in d["ops"])
    per_device, op_time, modules = {}, {}, {}
    for name, d in sorted(devices.items()):
        busy = union([[s, e] for s, e, _ in d["ops"]])
        collective, other = [], []
        for op, own in self_intervals(d["ops"]):
            op_time[op] = op_time.get(op, 0.0) + length(own)
            (collective if COLLECTIVE.search(op) else other).extend(own)
        collective += [[s, e] for s, e, op in d.get("async", [])
                       if COLLECTIVE.search(op)]
        collective = union(collective)
        per_device[name] = {
            "busy": busy, "busy_s": length(busy) / 1e9,
            "collective_s": length(collective) / 1e9,
            "collective_exposed_s":
                length(subtract(collective, union(other))) / 1e9}
        for s, e, module in d["modules"]:
            key = re.sub(r"\(.*\)$", "", module)
            modules.setdefault(key, []).append((e - s) / 1e6)
    window_s = (t1 - t0) / 1e9
    worst = min(per_device, key=lambda k: per_device[k]["busy_s"])
    n = len(per_device)
    gaps = subtract([[t0, t1]], per_device[worst]["busy"])
    by_label: dict = {}
    gap_starts = [s for s, _ in gaps]         # once, not once a host event
    for name, own in self_intervals(host_events):
        inside = overlap(own, gaps, gap_starts)
        if inside:
            label = _clean(name)
            by_label[label] = by_label.get(label, 0.0) + inside / 1e9
    left = length(gaps) / 1e9 - sum(by_label.values())
    if left > 1e-9:
        by_label["unattributed"] = left
    longest = max((e - s for s, e in gaps), default=0.0)
    return {
        "window_s": window_s, "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "idle_worst_s": window_s - per_device[worst]["busy_s"],
        "collective_s": max(d["collective_s"] for d in per_device.values()),
        "collective_exposed_s": max(d["collective_exposed_s"]
                                    for d in per_device.values()),
        "modules": {k: {"count": len(v), "median_ms": median(v),
                        "total_s": sum(v) / 1e3}
                    for k, v in modules.items()},
        "top_ops": [[_clean(k), v / 1e9 / n] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:10]],
        "top_gaps": [[k, v] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:10]],
        "gap_count": len(gaps), "longest_gap_s": longest / 1e9}


if __name__ == "__main__":
    import json
    import sys

    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
