"""The reduction from a trace to busy, idle, collective and exposed time,
on hand-made events with hand-computed answers and on a trace recorded on
the chip."""

import os

import pytest

import trace_reduce as tr
from conftest import CHIP_DIR


@pytest.mark.parametrize("intervals,want", [
    ([[0, 2], [1, 3], [5, 6]], [[0, 3], [5, 6]]),
    ([[5, 6], [0, 1], [1, 2]], [[0, 2], [5, 6]]),
    ([[0, 10], [2, 3]], [[0, 10]]), ([[4, 4]], []), ([], [])])
def test_union(intervals, want):
    assert tr.union(intervals) == want


@pytest.mark.parametrize("a,b,want", [
    ([[0, 10]], [[2, 3], [5, 7]], [[0, 2], [3, 5], [7, 10]]),
    ([[0, 4], [6, 9]], [[3, 7]], [[0, 3], [7, 9]]),
    ([[0, 4]], [], [[0, 4]]), ([[0, 4]], [[0, 4]], []),
    ([[2, 3]], [[0, 10]], [])])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_overlap():
    gaps = [[10, 20], [30, 40]]
    assert tr.overlap([[0, 15], [18, 35]], gaps) == 5 + 2 + 5
    assert tr.overlap([[20, 30]], gaps) == 0 and tr.overlap([], gaps) == 0


def test_self_intervals_cut_nested_events_out_once():
    # a loop [0,100] holding a fusion [10,40] that holds a copy [20,30]
    events = [(0, 100, "while"), (10, 40, "fusion"), (20, 30, "copy"),
              (50, 60, "all-reduce"), (120, 130, "after")]
    own = dict(tr.self_intervals(events))
    assert own["while"] == [[0, 10], [40, 50], [60, 100]]
    assert own["fusion"] == [[10, 20], [30, 40]]
    assert own["copy"] == [[20, 30]] and own["after"] == [[120, 130]]


def test_reduce_events_by_hand():
    us = 1000
    devices = {
        "/device:TPU:0": {
            "ops": [(0, 40 * us, "fusion.1"),
                    (40 * us, 60 * us, "all-reduce.2"),
                    (70 * us, 100 * us, "fusion.1")],
            "modules": [(0, 60 * us, "jit_step(123)"),
                        (70 * us, 100 * us, "jit_step(123)")]},
        "/device:TPU:1": {
            # the collective overlaps another line's op here for 10 us
            "ops": [(0, 50 * us, "fusion.1"),
                    (40 * us, 60 * us, "all-reduce.2"),
                    (60 * us, 100 * us, "fusion.1")],
            "modules": []}}
    host = [(55 * us, 75 * us, "$llm.py:1 outer"),
            (61 * us, 69 * us, "sample")]
    out = tr.reduce_events(devices, host)
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["devices"] == 2
    # device 0 is busy 90 us, device 1 100 us
    assert out["busy_s"] == pytest.approx(95e-6)
    assert out["idle_worst_s"] == pytest.approx(10e-6)
    assert out["collective_s"] == pytest.approx(20e-6)
    # device 0: all 20 us exposed; device 1: 10 of 20 under fusion.1
    assert out["collective_exposed_s"] == pytest.approx(20e-6)
    assert out["modules"]["jit_step"] == {
        "count": 2, "median_ms": pytest.approx(0.045),
        "total_s": pytest.approx(90e-6)}
    assert out["top_ops"][0][0] == "fusion.1"
    assert out["top_ops"][0][1] == pytest.approx((70 + 90) / 2 * 1e-6)
    # the one gap [60,70] on device 0: `sample` ran for 8 us of it, the
    # rest is `outer`'s own time
    assert out["top_gaps"] == [["sample", pytest.approx(8e-6)],
                               ["llm.py:1 outer", pytest.approx(2e-6)]]
    assert tr.reduce_events(devices, [])["top_gaps"] == [
        ["unattributed", pytest.approx(10e-6)]]


def test_async_collectives_count_from_start_to_done():
    us = 1000
    devices = {"/device:TPU:0": {
        "ops": [(0, 30 * us, "%fusion.1 = f32[8]{0} fusion()"),
                (50 * us, 60 * us, "%fusion.2 = f32[8]{0} fusion()")],
        "modules": [],
        "async": [(10 * us, 55 * us, "%all-gather-start.3 = f32[8]"),
                  (0, 5 * us, "%copy-start.4 = f32[8]")]}}
    out = tr.reduce_events(devices, [])
    assert out["collective_s"] == pytest.approx(45e-6)
    # in flight alone from 30 to 50
    assert out["collective_exposed_s"] == pytest.approx(20e-6)
    assert out["top_ops"][0] == ["%fusion.1 f32[8]", pytest.approx(30e-6)]


def test_the_dispatching_thread_is_the_one_that_launches_programs():
    a = [(0, 5, "queue.get")] * 9
    b = [(0, 5, "PjitFunction(_step)"), (1, 2, "np.asarray")]
    assert tr.dispatch_thread([a, b]) == b
    assert tr.dispatch_thread([a]) == []


def test_a_trace_without_a_device_plane_reduces_to_nothing(tmp_path):
    assert tr.reduce_dir(str(tmp_path)) is None


RECORDED = os.path.join(CHIP_DIR, "testdata")


@pytest.mark.parametrize("name", ["one_chip", "four_chips"])
def test_recorded_trace(name):
    """Recorded on the v5e in PR 22 (`rehearse/record_trace.py`); the
    expected numbers were worked out once beside the recording
    (`testdata/<name>.expected.json`) with a brute-force count on a
    nanosecond grid, not with this code."""
    import json

    path = os.path.join(RECORDED, name + ".xplane.pb")
    with open(os.path.join(RECORDED, name + ".expected.json")) as f:
        want = json.load(f)
    got = tr.reduce_file(path)
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s", "idle_worst_s", "collective_s",
                "collective_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-6, abs=1e-9), key
    assert sorted(got["modules"]) == sorted(want["modules"])
    if want["devices"] > 1:
        assert got["collective_s"] > 0
