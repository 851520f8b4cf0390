"""North-star end-to-end slice (SURVEY §7.3): JaxTrainer runs a real SPMD
GPT-2 train loop in a worker actor — mesh over the 8 virtual CPU devices,
pjit data plane, report(metrics, checkpoint), restart on induced failure.
"""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer, RunConfig,
                           ScalingConfig)


@pytest.fixture(scope="module")
def cluster():
    info = ray_tpu.init(num_cpus=8, max_workers=8)
    yield info
    ray_tpu.shutdown()


def _gpt2_loop(config):
    import jax
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer

    ctx = train.get_context()
    devices = jax.devices()
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2), devices=devices[:8])
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", vocab_size=256, max_seq_len=32)
    prog = compile_gpt2_train(cfg, mesh,
                              optimizer=default_optimizer(total_steps=10))
    state = prog.init_fn(jax.random.key(0))
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32),
        prog.batch_sharding)

    losses = []
    for step in range(config["steps"]):
        state, metrics = prog.step_fn(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        ckpt = None
        if step == config["steps"] - 1 and ctx.get_world_rank() == 0:
            import tempfile

            d = tempfile.mkdtemp()
            # checkpoint the params the TPU-native way: host-fetched numpy
            np.save(os.path.join(d, "wte.npy"),
                    np.asarray(state.params["wte"]))
            ckpt = Checkpoint(d)
        train.report({"loss": losses[-1], "step": step,
                      "first_loss": losses[0]}, checkpoint=ckpt)


def test_jax_trainer_e2e(cluster, tmp_path):
    trainer = JaxTrainer(
        _gpt2_loop,
        train_loop_config={"steps": 4},
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker={"CPU": 4}),
        run_config=RunConfig(name="gpt2-e2e", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    assert result.error is None
    # training makes progress: final loss below the first
    assert result.metrics["loss"] < result.metrics["first_loss"]
    assert result.checkpoint is not None
    wte = np.load(os.path.join(result.checkpoint.path, "wte.npy"))
    assert wte.ndim == 2 and np.isfinite(wte).all()


def test_jax_trainer_restart_after_worker_kill(cluster, tmp_path):
    marker = str(tmp_path / "killed_once")

    def loop(config):
        if not os.path.exists(config["marker"]):
            open(config["marker"], "w").close()
            os.kill(os.getpid(), 9)  # induced host failure
        train.report({"recovered": True})

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker},
        scaling_config=ScalingConfig(num_workers=2,
                                     resources_per_worker={"CPU": 1}),
        run_config=RunConfig(name="gpt2-ft", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2)),
    )
    result = trainer.fit()
    assert result.metrics["recovered"] is True
    assert result.restarts >= 1


# ---------------------------------------------------------------------------
# Elastic fault tolerance (ROADMAP item 5): daemon kills mid-run, shrink to
# surviving capacity, resume from a world-size-agnostic checkpoint, grow
# back when the node rejoins.
# ---------------------------------------------------------------------------

def test_sharded_checkpoint_world_size_roundtrip(tmp_path):
    """A checkpoint saved at world size 4 restores at 2, 1, and back at
    4 — params bitwise-equal after gather (world-size-agnostic manifest
    + gather-on-restore)."""
    import jax
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import (compile_gpt2_train, default_optimizer,
                                    restore_state_sharded,
                                    save_state_sharded)

    devices = jax.devices()
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", vocab_size=256, max_seq_len=32)
    mesh4 = build_mesh(MeshConfig(dp=2, fsdp=2), devices=devices[:4])
    prog4 = compile_gpt2_train(cfg, mesh4,
                               optimizer=default_optimizer(total_steps=10))
    state = prog4.init_fn(jax.random.key(0))
    # one real step so opt-state moments are non-trivial
    rng = np.random.default_rng(0)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32),
        prog4.batch_sharding)
    state, _ = prog4.step_fn(state, {"tokens": tokens})
    d = str(tmp_path / "ckpt")
    save_state_sharded(state, d, world_size=4)
    from ray_tpu.train.checkpoint import (is_sharded_checkpoint,
                                          read_sharded_manifest)

    assert is_sharded_checkpoint(d)
    assert read_sharded_manifest(d)["world_size"] == 4

    from ray_tpu.train.checkpoint import _leaf_key

    def leaves(tree):
        return [(_leaf_key(kp), np.asarray(leaf)) for
                kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]]

    want = {k: v for k, v in leaves(state.params)}
    for world in (2, 1, 4):
        mesh = build_mesh(MeshConfig(dp=world), devices=devices[:world])
        prog = compile_gpt2_train(
            cfg, mesh, optimizer=default_optimizer(total_steps=10))
        got = restore_state_sharded(d, prog)
        assert int(got.step) == int(state.step)
        for k, arr in leaves(got.params):
            assert (arr == want[k]).all(), f"{k} diverged at world {world}"
        # opt-state rides too (resharded mu/nu, replicated counts)
        for (k, a), (_, b) in zip(leaves(got.opt_state),
                                  leaves(state.opt_state)):
            assert (np.asarray(a) == np.asarray(b)).all(), k


def test_sharded_checkpoint_multiprocess_chunks(tmp_path):
    """Multi-process saves reuse blob names ("<leaf>::0") across shard
    files; the loader must scope each process's chunk list to ITS npz —
    matching the merged list against every file would silently duplicate
    one process's data into the others' windows."""
    import json

    import numpy as np

    from ray_tpu.train.checkpoint import load_sharded

    d = tmp_path / "ckpt"
    d.mkdir()
    top = np.arange(8, dtype=np.float32).reshape(2, 4)
    bottom = np.arange(8, 16, dtype=np.float32).reshape(2, 4)
    for pidx, (win, data) in enumerate((([[0, 2], [0, 4]], top),
                                        ([[2, 4], [0, 4]], bottom))):
        np.savez(str(d / f"shards_p{pidx:05d}.npz"), **{"w::0": data})
        with open(d / f"manifest_p{pidx:05d}.json", "w") as f:
            json.dump({"format": "ray_tpu.sharded_ckpt.v1", "step": 3,
                       "world_size": 2, "process_index": pidx,
                       "params": {"w": {"shape": [4, 4],
                                        "dtype": "float32"}},
                       "chunks": [{"leaf": "w", "blob": "w::0",
                                   "index": win}]}, f)
    flat, manifest = load_sharded(str(d))
    assert manifest["num_save_processes"] == 2
    want = np.concatenate([top, bottom])
    assert (flat["w"] == want).all(), flat["w"]


def _elastic_ddp_loop(config):
    """GPT-2 DDP across the worker gang: per-worker SPMD mesh over local
    devices, gradients averaged across workers via the kv collective
    (generation-scoped group), sharded checkpoint every step, restore
    resharded to whatever world size the controller scheduled."""
    import json
    import os
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import (compile_gpt2_train,
                                    cross_worker_grad_sync,
                                    default_optimizer, restore_state_sharded,
                                    save_state_sharded)
    from ray_tpu.util import collective

    ctx = train.get_context()
    world, rank = ctx.get_world_size(), ctx.get_world_rank()
    gen = ctx.get_generation()
    mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    cfg = gpt2.GPT2Config.preset(
        "gpt2-tiny", vocab_size=128, max_seq_len=16,
        n_layer=1, n_head=2, d_model=32, d_ff=64)
    prog = compile_gpt2_train(
        cfg, mesh, optimizer=default_optimizer(lr=1e-2, warmup=1,
                                               total_steps=config["steps"]))
    ck = ctx.get_checkpoint()
    if ck is not None:
        state = restore_state_sharded(ck.as_directory(), prog)
        start = int(state.step)
    else:
        state = prog.init_fn(jax.random.key(0))
        start = 0
    group = None
    if world > 1:
        # membership-scoped rendezvous: a fenced gang's stale keys can
        # never collide with this generation's
        group = f"ddp:{config['run']}:g{gen}"
        collective.rebuild_collective_group(world, rank, backend="kv",
                                            group_name=group)
    # fixed per-rank batch (memorization task): the loss descends
    # monotonically, so "the curve continues after restore" is a real
    # assertion, not a coin flip on fresh random batches
    rng = np.random.default_rng(rank)
    tokens = jax.device_put(
        rng.integers(0, cfg.vocab_size, (4, 17), dtype=np.int32),
        prog.batch_sharding)
    for step in range(start, config["steps"]):
        loss, grads = prog.grad_fn(state, {"tokens": tokens})
        if world > 1:
            grads = cross_worker_grad_sync(grads, group, world)
        state = prog.apply_fn(state, grads)
        ckpt = None
        if rank == 0:
            d = tempfile.mkdtemp(prefix="elastic_ckpt_")
            save_state_sharded(state, d, world_size=world)
            ckpt = Checkpoint(d)
            with open(config["history"], "a") as f:
                f.write(json.dumps({
                    "gen": gen, "step": step, "world": world,
                    "loss": float(loss), "ts": _time.time()}) + "\n")
        train.report({"loss": float(loss), "step": step, "world": world,
                      "gen": gen}, checkpoint=ckpt)
        # pacing: give the capacity watcher a realistic window between
        # checkpoint boundaries (real steps aren't sub-millisecond)
        _time.sleep(config.get("step_s", 0.0))


def _read_history(path):
    import json

    if not os.path.exists(path):
        return []
    out = []
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                pass   # torn trailing line mid-append from the worker
    return out


def _start_elastic_cluster():
    from ray_tpu.cluster_utils import Cluster

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = Cluster(num_cpus=0)   # head schedules no train workers
    nids = [cluster.add_node(num_cpus=1), cluster.add_node(num_cpus=1)]
    cluster.connect()
    cluster.wait_for_nodes(3)
    return cluster, nids


def _run_controller_bg(tmp_path, run_name, steps, history, regrow,
                       step_s=0.0):
    import threading

    from ray_tpu.train import ElasticConfig
    from ray_tpu.train.controller import TrainControllerLogic

    logic = TrainControllerLogic(
        _elastic_ddp_loop,
        {"steps": steps, "run": run_name, "history": history,
         "step_s": step_s},
        ScalingConfig(
            num_workers=2, min_workers=1,
            resources_per_worker={"CPU": 1},
            elastic=ElasticConfig(scale_up_check_interval_s=0.4,
                                  schedule_wait_s=30.0,
                                  regrow=regrow)),
        RunConfig(name=run_name, storage_path=str(tmp_path),
                  failure_config=FailureConfig(max_failures=3)))
    box = {}

    def _run():
        try:
            box["result"] = logic.run()
        except BaseException as e:   # surfaced by the test's join
            box["error"] = e

    t = threading.Thread(target=_run, daemon=True, name="train-controller")
    t.start()
    return logic, t, box


def _wait_history(history, pred, timeout, what):
    import time as _time

    deadline = _time.time() + timeout
    while _time.time() < deadline:
        entries = _read_history(history)
        if pred(entries):
            return entries
        _time.sleep(0.2)
    raise AssertionError(f"timed out waiting for {what}: "
                         f"{_read_history(history)[-5:]}")


@pytest.mark.chaos
def test_elastic_shrink_on_daemon_kill(tmp_path):
    """Acceptance drill 1: SIGKILL a node daemon mid-GPT-2-DDP run. The
    controller hears the death event, fences the gang, reshapes to the
    surviving capacity (2 -> 1), restores the latest checkpoint resharded
    to world size 1, and the run FINISHES at reduced size with the loss
    curve continuing within tolerance."""
    history = str(tmp_path / "history.jsonl")
    cluster, nids = _start_elastic_cluster()
    try:
        # paced above the controller's poll interval (0.2 s): it registers a
        # checkpoint when it drains a report, so unpaced steps leave the
        # restore point several steps behind the kill on an idle host
        logic, t, box = _run_controller_bg(tmp_path, "shrink", 12, history,
                                           regrow=False, step_s=0.3)
        _wait_history(history, lambda es: any(
            e["world"] == 2 and e["step"] >= 3 for e in es),
            timeout=180, what="2-worker progress")
        pre = _read_history(history)
        cluster.kill_node(nids[1])
        t.join(timeout=240)
        assert not t.is_alive(), "controller never finished after kill"
        assert "error" not in box, box.get("error")
        result = box["result"]
        assert result["state"] == "FINISHED", result["error"]
        assert result["restarts"] >= 1
        assert result["final_world_size"] == 1
        entries = _read_history(history)
        post = [e for e in entries if e["gen"] >= 1]
        assert post, "no post-restore steps recorded"
        assert all(e["world"] == 1 for e in post)
        # resumed from a checkpoint, not from scratch, and the restored
        # stream advances monotonically. (The old assertion demanded the
        # restore point trail the last pre-kill step by at most one — a
        # fixed lag bound that flakes on slow hosts whenever the kill
        # lands a couple of steps past the last checkpoint; monotonic
        # coverage is the actual contract.)
        post_steps = [e["step"] for e in post]
        assert post_steps == sorted(post_steps), post_steps
        assert post[0]["step"] >= 1, "restore rewound to step 0"
        # every step of the run is covered exactly once per final owner
        assert {e["step"] for e in entries} == set(range(12))
        # loss curve continues within tolerance: the first post-restore
        # loss stays in family with the last pre-kill loss and below the
        # run's initial loss (no re-warmup from scratch)
        pre_last = [e for e in pre if e["gen"] == 0][-1]["loss"]
        first0 = entries[0]["loss"]
        assert post[0]["loss"] < first0, (post[0]["loss"], first0)
        assert post[0]["loss"] <= pre_last * 1.15 + 0.05, \
            (post[0]["loss"], pre_last)
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


@pytest.mark.chaos
def test_elastic_regrow_on_rejoin(tmp_path):
    """Acceptance drill 2: after the shrink-on-kill recovery, a fresh node
    joins; the capacity watcher stops the 1-worker gang at the next
    checkpoint boundary and restarts it at the full 2-worker size."""
    history = str(tmp_path / "history.jsonl")
    cluster, nids = _start_elastic_cluster()
    try:
        logic, t, box = _run_controller_bg(tmp_path, "regrow", 24, history,
                                           regrow=True, step_s=0.3)
        _wait_history(history, lambda es: any(
            e["world"] == 2 and e["step"] >= 2 for e in es),
            timeout=180, what="2-worker progress")
        cluster.kill_node(nids[1])
        # shrunken generation makes progress at world size 1
        _wait_history(history, lambda es: any(
            e["world"] == 1 for e in es), timeout=240,
            what="post-kill 1-worker progress")
        cluster.add_node(num_cpus=1)   # capacity returns
        t.join(timeout=420)
        assert not t.is_alive(), "controller never finished after rejoin"
        assert "error" not in box, box.get("error")
        result = box["result"]
        assert result["state"] == "FINISHED", result["error"]
        assert result["restarts"] >= 1, "kill never registered as failure"
        assert result["resizes"] >= 1, "capacity watcher never regrew"
        assert result["final_world_size"] == 2
        entries = _read_history(history)
        worlds = [e["world"] for e in entries]
        assert 1 in worlds and worlds[-1] == 2, worlds
        assert {e["step"] for e in entries} == set(range(24))
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
