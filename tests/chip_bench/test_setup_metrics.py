"""The eight readers of where `setup_s` goes (`metrics/_startup.py`), on the
start-up records two traced runs left on the chip
(`testdata/startup/<cell>/`: the run's `cluster_sessions.txt`, its record's
marks and window, and every process's `startup-*.jsonl`), on a run whose
program kept no record, and through the CPU rehearsal of a cell."""

import json
import os
import subprocess
import sys

import pytest

from conftest import CHIP_DIR, REPO
from harness import spec
from metrics import _startup

DATA = os.path.join(CHIP_DIR, "testdata", "startup")
NEW = ["setup_cluster_s", "setup_sched_s", "setup_worker_boot_s",
       "setup_compile_s", "setup_compile_missed", "setup_engine_build_s",
       "setup_train_build_s", "setup_unowned_pct"]
CELLS = {"train-small-1k": "setup_engine_build_s",
         "serve-xl-decode": "setup_train_build_s"}    # cell -> not its own


def recorded(cell: str) -> dict:
    """The run's record as the harness hands it to a reader, its trace
    directory and the machine's state directory where the test data is."""
    record = spec.load_json(os.path.join(DATA, cell, "record.json"))
    record["trace_dir"] = os.path.join(DATA, cell, "trace")
    record["state_dir"] = os.path.join(DATA, cell, "state")
    return record


def read(name: str, record: dict):
    return spec.metric_reader(name).read(record)


def span(record: dict, name: str, **attributes) -> dict:
    (found,) = [s for s in _startup.spans(record) if s["name"] == name
                and all(s["attributes"].get(k) == v
                        for k, v in attributes.items())]
    return found


# ------------------------------------------------- on the recorded files

@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_the_recorded_run(cell, name):
    record = recorded(cell)
    value = read(name, record)
    expected = spec.load_json(os.path.join(DATA, cell, "expected.json"))
    if name == CELLS[cell]:
        assert value is None and name not in expected
        return
    assert isinstance(value, float) and value >= 0
    assert value == pytest.approx(expected[name], rel=1e-9, abs=1e-9)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_readings_are_what_the_files_say(cell):
    record = recorded(cell)
    chip = _startup.chip_pid(record)
    boot = span(record, "worker.boot", pid=chip)
    init = span(record, "startup.init")
    assert init["attributes"]["role"] == "driver"
    assert read("setup_cluster_s", record) == init["end_ts"] - init["start_ts"]
    # the chip's worker: the one the head's spans name by its pid
    place = span(record, "sched.place", worker_pid=chip)
    spawn = span(record, "sched.spawn", worker_pid=chip)
    assert place["attributes"]["chips"] == 1
    assert read("setup_sched_s", record) == pytest.approx(
        place["end_ts"] - place["start_ts"]
        + spawn["end_ts"] - spawn["start_ts"])
    assert read("setup_worker_boot_s", record) == pytest.approx(
        boot["end_ts"] - boot["attributes"]["proc_start_ts"])
    assert boot["attributes"]["proc_start_ts"] < boot["start_ts"]
    # compiles: the chip's process's, before the window, none missed warm
    found = _startup.compiles(record)
    assert found and all(_startup.pid_of(s) == chip for s in found)
    assert all(s["end_ts"] <= record["window"]["t0"] for s in found)
    longest = sum(_startup.seconds(s) for s in found)
    assert 0 < read("setup_compile_s", record) <= longest + sum(
        (s["attributes"].get("trace_s") or 0)
        + (s["attributes"].get("lower_s") or 0) for s in found)
    assert read("setup_compile_missed", record) == len(
        [s for s in found if s["attributes"]["cache"] != "hit"])
    # the stretch the tracing is judged on has an owner
    assert 0 <= read("setup_unowned_pct", record) < 10


def test_the_train_build_is_the_three_spans_of_the_chips_worker():
    record = recorded("train-small-1k")
    chip = _startup.chip_pid(record)
    parts = [span(record, n, pid=chip) for n in (
        "train.worker_setup", "train.compile", "train.init_state")]
    assert read("setup_train_build_s", record) == pytest.approx(
        sum(_startup.seconds(s) for s in parts))
    # the chip opens in the loop's own first lines, which have their span
    prelude = span(record, "train.loop_prelude", pid=chip)
    assert _startup.seconds(prelude) > 5


def test_the_engine_build_is_engine_init_and_holds_its_children():
    record = recorded("serve-xl-decode")
    init = span(record, "engine.init")
    assert read("setup_engine_build_s", record) == _startup.seconds(init)
    children = [s for s in _startup.spans(record)
                if s["parent_id"] == init["span_id"]
                and s["name"].startswith("engine.")]
    assert sorted(s["name"] for s in children) == [
        "engine.cache", "engine.place", "engine.resident", "engine.weights"]
    assert sum(_startup.seconds(s) for s in children) <= _startup.seconds(init)
    assert span(record, "engine.weights")["attributes"]["source"] == "caller"


def test_missed_programs_are_named_in_the_runs_log(capsys):
    record = recorded("train-small-1k")
    for s in _startup.spans(record):            # as a cell's first run
        if s["name"] == "compile._step":
            s["attributes"]["cache"] = "miss"
    try:
        assert read("setup_compile_missed", record) == 1.0
        assert "not from the cache: _step" in capsys.readouterr().err
        read("setup_compile_s", record)
        assert "longest compiles: " in capsys.readouterr().err
    finally:
        _startup._load.cache_clear()


# --------------------------------------------------- where nothing is there

@pytest.mark.parametrize("name", NEW)
def test_a_program_without_a_record_reads_as_nothing(name, tmp_path):
    # a parent commit: the sessions file is there, no process wrote a span
    record = recorded("train-small-1k")
    record["trace_dir"] = str(tmp_path / "trace")
    record["state_dir"] = str(tmp_path / "state")
    (tmp_path / "cluster_sessions.txt").write_text("s0123456789ab\n")
    assert read(name, record) is None
    # an untraced run, and a run directory without a sessions file
    assert read(name, {**record, "trace_dir": None}) is None
    assert read(name, {**record, "trace_dir": str(tmp_path / "x" / "t")}) \
        is None


@pytest.mark.parametrize("name,gone", [
    ("setup_cluster_s", "startup.init"), ("setup_sched_s", "sched.place"),
    ("setup_worker_boot_s", "worker.boot"),
    ("setup_train_build_s", "train.init_state"),
    ("setup_compile_s", "train.compile"),
    ("setup_compile_missed", "train.compile")])
def test_a_reader_whose_span_is_absent_returns_none(name, gone, tmp_path):
    src = os.path.join(DATA, "train-small-1k")
    record = recorded("train-small-1k")
    record["trace_dir"] = str(tmp_path / "trace")
    record["state_dir"] = str(tmp_path / "state")
    with open(os.path.join(src, "cluster_sessions.txt")) as f:
        (session,) = f.read().split()
    (tmp_path / "cluster_sessions.txt").write_text(session + "\n")
    logs = tmp_path / "state" / session / "logs"
    logs.mkdir(parents=True)
    for name_ in os.listdir(os.path.join(src, "state", session, "logs")):
        with open(os.path.join(src, "state", session, "logs", name_)) as f:
            kept = [line for line in f if json.loads(line)["name"] != gone]
        (logs / name_).write_text("".join(kept) + '{"name": "cut sh')
    assert read(name, record) is None


def test_union_seconds_clips_and_merges():
    u = _startup.union_seconds
    assert u([(0, 4), (2, 6), (8, 9)], 1, 10) == 5 + 1
    assert u([(0, 4)], 5, 10) == 0 and u([], 0, 1) == 0
    assert u([(3, 30)], 0, 10) == 7


# ----------------------------------------------- the CPU run of a cell

def test_the_cpu_run_of_a_cell_reports_the_new_readings():
    """`rehearse/cpu_cell.py`: the command's phases, readers and output on
    the CPU at a tiny size. The rehearsal asks for no chip, so the
    scheduler leaves no `sched.place`; every other reading is there."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse", "cpu_cell.py"),
         "--workload", "train-small-1k", "--seconds", "3", "--trace", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        # one device, as the cell has (the tests' own processes have 8)
        env={**{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
             "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    cell = spec.cell(spec.benchmark(), "train-small-1k")
    mine = {m["name"] for m in cell["per_layer"]} & set(NEW)
    assert mine == set(NEW) - {"setup_engine_build_s"}
    got = {k: v["value"] for k, v in line["metrics"].items() if k in NEW}
    assert set(got) == mine - {"setup_sched_s"}
    assert got["setup_unowned_pct"] < 10
    assert got["setup_compile_s"] > 0 and got["setup_train_build_s"] > 0
    assert "setup_sched_s: nothing to read" in out.stderr
