"""The readers of the engine thread's accounting (PR 51): each of the eight
on a record made by hand, on a record of a program without the new keys, and
the eight entries as BENCHMARK.json holds them. And what two tests of
earlier cells held beside the line an appended entry fails
(`tests/conftest.py`): the count read from the file, an entry found by its
name."""

import os

import pytest

import test_keye_family as keye
import test_solar_family as solar
import trace_reduce as tr
from harness import spec
from test_hot_path_metrics import (  # noqa: F401
    DEVICE, _msg, _plane, engine_counters)

DECODE_CELLS = ["serve-xl-decode", "serve-kanana-docqa",
                "serve-brumby-fewshot", "serve-granite-docgen",
                "serve-kimi-longgen", "serve-keye-longdoc",
                "serve-solar-longctx"]
LOOP, DEVICE_LAYER = ("program_counter", "engine loop"), ("program_span",
                                                          "device")
# name -> unit, (source, layer): the issue's table, in its order
ENTRIES = {"engine_offcpu_ms.decode": ("ms", LOOP),
           "engine_release_ms.decode": ("ms", LOOP),
           "engine_put_ms.decode": ("ms", LOOP),
           "engine_dispatch_ms.decode": ("ms", LOOP),
           "engine_admit_ms.decode": ("ms", LOOP),
           "engine_slow_pass_pct.decode": ("%", LOOP),
           "idle_in_admit_pct.decode": ("%", DEVICE_LAYER),
           "idle_in_dispatch_pct.decode": ("%", DEVICE_LAYER)}
PHASES = ("calls", "admit", "plan", "put", "dispatch", "fetch", "sample",
          "publish", "notify", "empty", "release")


def _stats(steps, wall, cpu, slow_s):
    return {"engine_steps": steps, "phase_s": dict(zip(PHASES, wall)),
            "phase_cpu_s": dict(zip(PHASES, cpu)),
            "slow_passes": {"count": int(slow_s > 0), "seconds": slow_s,
                            "newest": []}}


# 100 steps in a window of 50 s; by phase, seconds of wall and of CPU
WALL = (0.1, 0.3, 0.05, 0.4, 0.25, 40.0, 0.2, 0.0, 0.1, 8.0, 0.6)
CPU = (0.1, 0.2, 0.05, 0.15, 0.2, 0.5, 0.2, 0.0, 0.1, 0.1, 0.1)
RECORD = {"counters": {
    "before": _stats(1000, [1.0] * 11, [0.5] * 11, 2.0),
    "after": _stats(1100, [1.0 + w for w in WALL], [0.5 + c for c in CPU],
                    4.5),
    "before_at": 100.0, "after_at": 150.0}}
# off the CPU outside `fetch` and `empty`: admit 0.1, put 0.25, dispatch
# 0.05, release 0.5 s over 100 steps
BY_HAND = {"engine_offcpu_ms": 9.0, "engine_release_ms": 6.0,
           "engine_put_ms": 4.0, "engine_dispatch_ms": 2.5,
           "engine_admit_ms": 3.0, "engine_slow_pass_pct": 5.0}


def _without(record, *keys):
    c = record["counters"]
    return {"counters": {**c, **{
        edge: {k: v for k, v in c[edge].items() if k not in keys}
        for edge in ("before", "after")}}}


@pytest.mark.parametrize("reading,want", BY_HAND.items())
def test_a_counter_reader_on_a_record_made_by_hand(reading, want):
    reader = spec.metric_reader(reading + ".decode")
    assert reader.read(RECORD) == pytest.approx(want)
    # a parent's record: wall seconds of nine phases and nothing beside
    # them, where `dispatch` still held the transfers
    parents = _without(RECORD, "phase_cpu_s", "slow_passes")
    assert reader.read(parents) is None
    assert reader.read({"counters": None}) is None and reader.read({}) is None
    if reading != "engine_slow_pass_pct":
        still = {"counters": {**RECORD["counters"],
                              "after": RECORD["counters"]["before"]}}
        assert reader.read(still) is None        # no step in the window


def _traced(directory, engine_thread: list) -> str:
    """A trace as the chip's profiler lays it out: one device busy 0-100
    and 900-1000 ns and idle between, and the engine's thread."""
    op = "%fusion.1 = f32[8]{0} fusion()"
    device = _plane(DEVICE, {
        tr.OPS_LINE: [(0, 100, op), (900, 1000, op)],
        tr.MODULES_LINE: [(0, 100, "jit__step(7)"),
                          (900, 1000, "jit__step(7)")]}, {})
    host = _plane(tr.HOST_PLANE, {"engine": [
        (0, 5, "PjitFunction(_step)"), *engine_thread]}, {})
    os.makedirs(directory / "plugins" / "profile" / "t")
    (directory / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(
        _msg((1, device), (1, host)))
    return str(directory)


@pytest.fixture(scope="module")
def traced_dir(tmp_path_factory):
    """The thread in `admit` 100-300, `put` 400-500, `dispatch` 500-650 and
    `fetch` 650-900, with a call beneath `put` as the Python tracer has
    it."""
    return _traced(tmp_path_factory.mktemp("trace"), [
        (100, 300, "engine.admit"), (400, 500, "engine.put"),
        (410, 490, "DevicePutWithSharding"), (500, 650, "engine.dispatch"),
        (650, 900, "engine.fetch")])


@pytest.mark.parametrize("reading,want", [("idle_in_admit_pct", 20.0),
                                          ("idle_in_dispatch_pct", 25.0)])
def test_a_span_reader_on_a_trace_made_by_hand(reading, want, traced_dir):
    reader = spec.metric_reader(reading + ".decode")
    assert reader.read({"trace_dir": traced_dir}) == pytest.approx(want)
    assert reader.read({"trace_dir": None}) is None      # an untraced run
    assert reader.read({}) is None


def test_a_trace_without_the_new_span_reads_what_the_old_one_covered(
        tmp_path):
    """A parent's trace has no `engine.put`: its `engine.dispatch` covers
    the transfers too, and the reader reads the same stretch."""
    record = {"trace_dir": _traced(tmp_path, [
        (400, 650, "engine.dispatch"), (650, 900, "engine.fetch")])}
    assert spec.metric_reader("idle_in_dispatch_pct").read(
        record) == pytest.approx(25.0)
    assert spec.metric_reader("idle_in_admit_pct").read(record) == 0.0


def test_the_readers_read_a_real_engines_counters(engine_counters):
    """`stats()` of a `gpt2-tiny` server at two moments, as
    `harness/serve_cell.py` records them."""
    record = {"counters": engine_counters}
    read = {name: spec.metric_reader(name).read(record) for name in BY_HAND}
    assert all(isinstance(v, float) for v in read.values()), read
    for name in ("engine_release_ms", "engine_put_ms", "engine_dispatch_ms",
                 "engine_admit_ms"):
        assert read[name] > 0
    # what the thread spent off the CPU outside `fetch` and `empty` is at
    # most what `engine_host_ms` reads of the same phases' wall seconds
    host = spec.metric_reader("engine_host_ms").read(record)
    assert -0.05 * host <= read["engine_offcpu_ms"] <= host
    assert 0.0 <= read["engine_slow_pass_pct"] <= 100.0


# ------------------------------------------------------------- the entries

def test_the_eight_entries_are_the_issues_table_appended():
    bench = spec.benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    theirs = names.index("gqa_rows_read_pct")
    at = [names.index(name) for name in ENTRIES]
    # appended, in the table's order, behind what the file had
    assert at == sorted(at) and at[0] > theirs
    assert len(names) <= 128
    for name, (unit, (source, layer)) in ENTRIES.items():
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": layer,
                         "moves": "serve_tokens_per_s",
                         "workloads": DECODE_CELLS}
        assert spec.metric_reader(name) is not None
        # one reading, one entry: no copy for the chat cell
        assert names.count(name) == 1
        assert name.split(".")[0] + ".chat" not in names
    serve = {m["name"]: m for m in bench["end_to_end"]}["serve_tokens_per_s"]
    assert serve["workloads"] == DECODE_CELLS
    for cell in DECODE_CELLS:
        assert set(ENTRIES) <= {m["name"] for m in
                                spec.cell(bench, cell)["per_layer"]}
    assert set(ENTRIES).isdisjoint(
        m["name"] for m in spec.cell(bench, "serve-xl-chat")["per_layer"])


# ----------------- what the two tests marked in tests/conftest.py held

def test_keyes_cell_reads_what_it_read_wherever_later_entries_stand():
    """`test_keye_family.py`'s
    `test_the_cell_reads_the_decode_metrics_that_exist_for_it_and_its_own`
    but its `len(per_layer) <= 115`: the count is the file's."""
    bench = spec.benchmark()
    keye.the_cell_reads_what_it_reads(bench)
    assert [w["name"] for w in bench["workloads"]][9] == keye.CELL
    assert len(bench["per_layer"]) <= 128
    own = [m for m in bench["per_layer"]
           if m.get("workloads") == [keye.CELL]]
    assert {m["name"] for m in own} == keye.DSA
    # the seven of its own stand together where ISSUE 46 put them
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(own[0]["name"])
    assert set(names[first:first + 7]) == keye.DSA and first + 7 <= 115


def test_solars_cell_reads_what_it_read_with_its_entry_found_by_name():
    """`test_solar_family.py`'s `test_the_cell_reads_what_it_reads` but
    `per_layer[-1] == own`: the entry is found by its name, and stands
    behind Keye's as ISSUE 49 put it."""
    bench = spec.benchmark()
    cell = spec.cell(bench, solar.CELL)
    assert cell["chips"] == 1 and cell["traffic"] == solar.TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert solar.DECODE <= names and solar.OWN <= names
    assert names.isdisjoint({"mla_attend_time_pct",
                             "mla_attend_roofline_pct"})
    for m in bench["per_layer"]:
        if m["name"] in solar.OWN:
            assert solar.CELL in m["workloads"]
            assert spec.metric_reader(m["name"]) is not None
    (own,) = [m for m in bench["per_layer"]
              if m["name"] == "gqa_rows_read_pct"]
    assert own == {"name": "gqa_rows_read_pct", "unit": "%",
                   "better": "lower", "source": "program_counter",
                   "layer": "engine programs", "moves": "serve_tokens_per_s",
                   "workloads": [solar.CELL]}
    listed = [m["name"] for m in bench["per_layer"]]
    assert listed.index("gqa_rows_read_pct") == listed.index(
        "dsa_rows_read_pct") + 1
    assert len(bench["per_layer"]) <= 128
    assert bench["workloads"][-1]["name"] == solar.CELL
    assert bench["configs"][-1]["name"] == solar.CONFIG["name"]
    kimis = {m["name"] for m in bench["per_layer"]
             if "serve-kimi-longgen" in m.get("workloads", [])}
    assert kimis - names == {"mla_attend_time_pct", "mla_attend_roofline_pct"}
    assert "1 row a held expert" in bench["workloads"][-1]["why"]
