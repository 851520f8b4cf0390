"""Pieces of the harness that need no cluster: whose `/dev/shm` segments a
run removes, the knee's rule, where a stalled train loop says it stalled,
and the training cells' reference check."""

import importlib.util
import os

import numpy as np
import pytest

from conftest import CHIP_DIR
from harness import procs, train_cell


def test_only_the_runs_own_clusters_segments_are_removed(tmp_path, monkeypatch):
    shm, workdir = tmp_path / "shm", tmp_path / "run"
    shm.mkdir()
    workdir.mkdir()
    monkeypatch.setattr(procs, "SHM_DIR", str(shm))
    # the program's names, as this sandbox's /dev/shm shows them
    mine = ["rtpu_arena_s0123456789ab", "rtpu_s0123456_2693adb72949_p1202fc",
            "rtpu_arena_1bbb38da_sfedcba987654",
            "rtpu_1bbb38da_sfedcba9_070275ba4bfd_p0dc8c3"]
    others = ["rtpu_arena_s0123450000ab", "rtpu_s7777777_2693adb72949_p12",
              "rtpu_arena_1bbb38da_sbba8beba797d", "rtpu_chan_0123456789ab",
              "rtpu_7e34be70_joined_90800244b63b_p283b22",
              "psm_another_tenant"]
    for name in mine + others:
        (shm / name).write_bytes(b"x")
    (workdir / procs.SESSIONS_FILE).write_text(
        "s0123456789ab\nsfedcba987654\n")
    assert procs.remove_cluster_shm(str(workdir)) == len(mine)
    assert sorted(os.listdir(shm)) == sorted(others)


def test_a_run_that_started_no_cluster_removes_nothing(tmp_path, monkeypatch):
    shm = tmp_path / "shm"
    shm.mkdir()
    (shm / "rtpu_arena_s0123456789ab").write_bytes(b"x")
    monkeypatch.setattr(procs, "SHM_DIR", str(shm))
    assert procs.remove_cluster_shm(str(tmp_path)) == 0
    assert os.listdir(shm) == ["rtpu_arena_s0123456789ab"]


def _knee_sweep():
    spec = importlib.util.spec_from_file_location(
        "knee_sweep", os.path.join(CHIP_DIR, "rehearse", "knee_sweep.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# a request inside both limits takes at most 2 + 127 x 0.15 = 21.05 s
LIMITS = {"limits": {"ttft_ms": 2000, "tpot_ms": 150},
          "output": {"clip": [16, 128]}}


def _row(rate, attain, close=3):
    return {"rate_per_s": rate, "attain_pct": attain,
            "in_flight_close": close}


@pytest.mark.parametrize("rows,want", [
    ([_row(0.4, 95), _row(0.6, 91.7), _row(0.8, 46.9)], 0.6),
    # a pass above a failure is noise, not capacity
    ([_row(0.4, 95), _row(0.5, 80), _row(0.6, 92)], 0.4),
    # the limits are met but more are in flight than 0.6 x 21.05 = 12.6
    ([_row(0.4, 95, close=8), _row(0.6, 95, close=13)], 0.4),
    ([_row(0.6, 95), _row(0.4, 100)], 0.6),          # any order
    ([_row(0.4, 50)], None)])
def test_the_knee_is_the_highest_rate_that_passes_with_all_below(rows, want):
    assert _knee_sweep().knee(rows, LIMITS) == want


def test_a_stalled_loop_says_where_it_stalled():
    window = {"t0": 100.0, "report_s": [0.002, 0.004],
              "iterations": [[100.3, 0.001, 0.002, 0.29],
                             [107.9, 0.001, 0.002, 0.31],   # 7.6 s, 0.3 known
                             [108.2, 0.001, 0.002, 0.29]]}
    said = train_cell._longest_iteration(window)
    assert said["iteration"] == 2
    assert said["seconds"] == pytest.approx(7.6)
    assert said["dispatch_s"] + said["next_batch_s"] + said["landing_s"] \
        == pytest.approx(0.313)
    assert said["longest_report_s"] == 0.004


MODEL = {"vocab_size": 500, "padded_vocab_size": 512, "n_positions": 64,
         "n_embd": 64, "n_layer": 2, "n_head": 4, "n_inner": None}
JOB = {"seq_len": 32, "global_batch": 6, "remat": "dots", "total_steps": 10}


def test_the_reference_is_held_against_the_steps_own_first_loss():
    import jax

    from families import gpt2 as family

    prog = family.build_train(MODEL, JOB, jax.devices()[:1], seed=5)
    tokens = np.random.default_rng(5).integers(
        0, 500, (JOB["global_batch"], JOB["seq_len"] + 1)).astype(np.int32)
    state = prog.init_state()
    _, metrics = prog.compile_step(state)(state, prog.put_batch(tokens))
    step_loss = float(metrics["loss"])
    # slices of 4 leave a ragged last one: still the whole batch's mean
    check = prog.check_against_reference(tokens, step_loss, 4)
    assert check["ok"] and check["sequences"] == 6
    whole = float(family.reference_loss(
        family.seeded_params(prog.cfg, 5), tokens, MODEL["n_head"]))
    assert check["reference_loss"] == pytest.approx(whole, abs=1e-5)
    # what the window ran is what is compared: a step whose loss is off by
    # a hundredth (a softmax or a matmul below bf16) is refused
    assert not prog.check_against_reference(
        tokens, step_loss + 1e-2, 4)["ok"]
