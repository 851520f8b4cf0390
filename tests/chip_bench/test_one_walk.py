"""One walk of a traced run's events for all of its readers (PR 45):
`metrics/_events.walk` makes each device's own intervals once, and the scope
readers, `step_hbm_gb`, the phases and `trace_reduce.reduce_events` stopped
making them again. Nothing a reader returns may have moved: the constants
are what the parent's code (commit 8db49c4) gave on the two recorded traces
of `testdata/`, written down before the readers were changed. The recorded
programs (PR 22) have none of the named scopes, so a reader's scope set is
set to words their operations' paths do have."""

import importlib
import os
import shutil

import pytest

import trace_reduce as tr
from harness.spec import CHIP_DIR
from metrics import _events

WORDS = ("checkpoint", "closed_call", "body")
FAMILY_SETS = {"_moe_scopes": "MOE_SCOPES", "_mla_scopes": "MLA_SCOPES",
               "_kda_scopes": "KDA_SCOPES", "_ssm_scopes": "SSM_SCOPES",
               "_retention_scopes": "RETENTION_SCOPES"}
# [share in per cent, seconds inside one execution of the step's module]:
# the five families' files did one arithmetic, and gave one answer
FAMILY = {
    "one_chip": {"checkpoint": [37.24910788057995, 0.00016647976599999518],
                 "closed_call": [13.43506233516923, 6.004828199999035e-05],
                 "body": [0.6378157798190973, 2.8503120000064374e-06],
                 "absent": [0.0, None]},
    "four_chips": {"checkpoint": [35.97094134932119, 0.00015750503900000454],
                   "closed_call": [15.399733840225622, 6.7499375e-05],
                   "body": [0.5315566476543795, 2.32703100001812e-06],
                   "absent": [0.0, None]}}
# per cent of the window's device self time by scope
SCOPES = {'one_chip': {'checkpoint': 37.24910788057995,
                       'closed_call': 13.43506233516923,
                       'body': 0.6378157798190973,
                       'jvp': 24.656155594020067,
                       'unscoped': 24.021858410411653,
                       'attn': 0.0},
          'four_chips': {'checkpoint': 35.97094134932119,
                         'closed_call': 15.399733840225622,
                         'body': 0.5315566476543795,
                         'jvp': 37.98974927375439,
                         'unscoped': 10.108018889044423,
                         'attn': 0.0}}
# GB one execution of `jit__step` accesses
STEP_HBM = {'one_chip': {'read': 0.846487},
            'four_chips': {'read': 0.259579352}}
# the idlest device's gaps under the dispatching thread's `PjitFunction(...)`
# events, taken as phases
PHASES = {'one_chip': {'all': 24.3712556951451,
                       'step': 24.3712556951451,
                       'but': 0},
          'four_chips': {'all': 42.2738361238286,
                         'step': 42.2738361238286,
                         'but': 0}}
# the reduction itself: its first three operations, every gap
REDUCED = {'one_chip': {'window_s': 0.005410309,
                        'busy_s': 0.001340381,
                        'idle_worst_s': 0.0040699280000000004,
                        'collective_s': 0.0,
                        'collective_exposed_s': 0.0,
                        'gap_count': 745,
                        'longest_gap_s': 0.002372795,
                        'top_ops': [['%fusion.1 f32[2048]', 7.8e-05],
                                    ['%add_add_fusion.2 bf16[8,256,256]',
                                     7.7269e-05],
                                    ['%fusion.2 f32[2048,256]', 5.7489e-05]],
                        'top_gaps': [['np.asarray(jax.Array)', 0.002244885],
                                     ['PjitFunction(jit(_step))',
                                      0.0011936450000000001],
                                     ['bench.dispatch', 0.000300404],
                                     ['unattributed', 0.00020605299999999944],
                                     ['ParseArguments',
                                      0.00012183099999999999],
                                     ['PJRT_LoadedExecutable_Execute linkage',
                                      1.8699999999999999e-06],
                                     ['PythonRefManager::CollectGarbage',
                                      1.24e-06]]},
           'four_chips': {'window_s': 0.006488481,
                          'busy_s': 0.00131318075,
                          'idle_worst_s': 0.005177546,
                          'collective_s': 0.000750486,
                          'collective_exposed_s': 0.000750486,
                          'gap_count': 761,
                          'longest_gap_s': 0.00316065,
                          'top_ops': [['%fusion.428 bf16[256,64]',
                                       0.0001258185],
                                      ['%fusion.253 f32[256,64]',
                                       8.738125e-05],
                                      ['%all-gather.177 f32[2048,256]',
                                       6.60835e-05]],
                          'top_gaps': [['PjitFunction(jit(_step))',
                                        0.002720644],
                                       ['np.asarray(jax.Array)', 0.001383504],
                                       ['bench.dispatch', 0.001004939],
                                       ['unattributed',
                                        4.5779999999999606e-05],
                                       ['ParseArguments', 2.1009e-05],
                                       ['PythonRefManager::CollectGarbage',
                                        1.67e-06]]}}
PARENT = {"_scopes": SCOPES, **{reader: FAMILY for reader in FAMILY_SETS},
          "step_hbm_gb": STEP_HBM, "_phases": PHASES, "reduce_file": REDUCED}


def readings(reader: str, trace_dir: str, monkeypatch) -> dict:
    """What `reader` gives on the trace in `trace_dir`, its scope set (or
    the phases' prefix) set to words the recorded programs have."""
    record = {"trace_dir": trace_dir}
    if reader == "reduce_file":
        got = tr.reduce_dir(trace_dir)
        return {k: got[k] for k in (
            "window_s", "busy_s", "idle_worst_s", "collective_s",
            "collective_exposed_s", "gap_count", "longest_gap_s",
            "top_ops", "top_gaps")} | {"top_ops": got["top_ops"][:3]}
    module = importlib.import_module(f"metrics.{reader}")
    if reader == "step_hbm_gb":
        return {"read": module.read(record)}
    if reader == "_phases":
        monkeypatch.setattr(module, "PREFIX", "PjitFunction(")
        return {"all": module.idle_pct(record),
                "step": module.idle_pct(record, phases=("jit(_step))",)),
                "but": module.idle_pct(record, but=("jit(_step))",))}
    if reader == "_scopes":
        monkeypatch.setattr(module, "SCOPES", frozenset(WORDS + ("jvp",)))
        return {w: module.share(record, w)
                for w in WORDS + ("jvp", "unscoped", "attn")}
    monkeypatch.setattr(module, FAMILY_SETS[reader], WORDS)
    return {w: [module.share(record, w), module.step_seconds(record, w)]
            for w in WORDS + ("absent",)}


@pytest.fixture
def trace_dir(request, tmp_path):
    """The recorded trace under a path no other test has read: the walk and
    what is made of it are cached by the file's path."""
    name = request.param
    shutil.copy(os.path.join(CHIP_DIR, "testdata", name + ".xplane.pb"),
                tmp_path)
    return name, str(tmp_path)


@pytest.mark.parametrize("trace_dir", ["one_chip", "four_chips"],
                         indirect=True)
@pytest.mark.parametrize("reader", list(PARENT))
def test_a_reader_gives_what_the_parents_code_gave(reader, trace_dir,
                                                   monkeypatch):
    name, directory = trace_dir
    assert readings(reader, directory, monkeypatch) == PARENT[reader][name]


@pytest.mark.parametrize("trace_dir", ["one_chip", "four_chips"],
                         indirect=True)
def test_the_walk_is_the_own_intervals_and_leaves_made_once(trace_dir,
                                                            monkeypatch):
    _, directory = trace_dir
    path = tr.newest_xplane(directory)
    devices, _ = _events.load(path)
    walked = _events.walk(path)
    assert set(walked) == set(devices)
    for name, d in devices.items():
        own = tr.self_intervals(d["ops"])
        assert [(i, iv) for i, iv, _ in walked[name]["own"]] == own
        assert [ns for _, _, ns in walked[name]["own"]] == [
            tr.length(iv) for _, iv in own]
        ordered = sorted(d["ops"], key=lambda ev: (ev[0], -ev[1]))
        assert walked[name]["leaves"] == [
            ev for ev, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]]
    # made once: every reader after the first finds it there
    calls = []
    monkeypatch.setattr(tr, "self_intervals",
                        lambda *a, **k: calls.append(a) or [])
    for reader in ("_scopes", *FAMILY_SETS, "step_hbm_gb", "_phases"):
        readings(reader, directory, monkeypatch)
    assert calls == []


def test_self_intervals_as_the_parent_made_them():
    """The parent's function, kept here: a list of children for every
    event and a subtraction for every event."""
    def parents(events):
        order = sorted(range(len(events)),
                       key=lambda i: (events[i][0], -events[i][1]))
        children = {i: [] for i in order}
        stack = []
        for i in order:
            s, e, _ = events[i]
            while stack and events[stack[-1]][1] <= s:
                stack.pop()
            if stack and e <= events[stack[-1]][1]:
                children[stack[-1]].append([s, e])
            stack.append(i)
        return [(events[i][2], tr.subtract(
            [[events[i][0], events[i][1]]], tr.union(children[i])))
            for i in order]

    events = [(0, 100, "loop"), (10, 40, "a"), (40, 70, "b"), (70, 70, "nil"),
              (100, 120, "c"), (100, 110, "d"), (120, 120, "nil"),
              (5, 5, "nil"), (130, 125, "backwards")]
    assert tr.self_intervals(events) == parents(events)
    for name in ("one_chip", "four_chips"):
        devices, host = _events.load(os.path.join(
            CHIP_DIR, "testdata", name + ".xplane.pb"))
        for d in devices.values():
            assert tr.self_intervals(d["ops"]) == parents(d["ops"])
        for line in host:
            assert tr.self_intervals(line) == parents(line)


def test_the_gaps_starts_are_built_once_and_overlap_is_what_it_was():
    gaps = [[10, 20], [30, 40], [50, 60]]
    starts = [10, 30, 50]
    for own in ([[0, 100]], [[15, 35]], [[20, 30]], [[0, 5], [55, 70]], []):
        assert tr.overlap(own, gaps, starts) == tr.overlap(own, gaps)
    assert tr.overlap([[15, 35]], gaps) == 10
