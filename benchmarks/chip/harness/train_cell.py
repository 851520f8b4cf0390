"""A cell of kind `train`: one `JaxTrainer.fit()` on a cluster this phase
starts, whose one worker holds the cell's chips and runs `train_loop`.

The loop is the benchmark's own function, so it can do inside the worker
what only the process that holds the chips can: open and close the window
on `block_until_ready`, trace a few steps, ask the compiler for the step's
size, and compare the program with the family's reference.
"""

from __future__ import annotations

import json
import os
import threading
import time

from . import device, procs, spec

PHASES = (("measure", 1100),)


def _write(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def train_loop(config: dict) -> None:
    """Runs inside the JaxTrainer worker."""
    t_loop = time.time()
    import jax
    import numpy as np

    from harness import replica_probe, spec as _spec
    from harness.device import worker_devices
    from ray_tpu import train

    workdir = config["workdir"]
    _write(os.path.join(workdir, "worker_ready.json"),
           {"time": t_loop, "devices": worker_devices()})
    compiles = replica_probe.CompileCounter()
    family = _spec.family(config["family"])
    job, data = config["job"], config["traffic"]
    marks = {"loop_start": t_loop}
    prog = family.build_train(config["model"], job, jax.devices(),
                              config["seed"])
    state = prog.init_state()
    jax.block_until_ready(state)
    marks["weights"] = time.time()
    step = prog.compile_step(state)
    step_bytes = replica_probe.memory_bytes(step)
    marks["compiled"] = time.time()

    shard = train.get_dataset_shard("train")
    waits = {"next": 0.0, "put": 0.0}

    def batches():
        while True:                      # a new epoch when one runs out
            yield from shard.iter_batches(batch_size=prog.global_batch)

    stream = batches()

    def next_batch():
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.next_batch"):
            host = next(stream)["tokens"]
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.device_put"):
            dev = prog.put_batch(host)
        waits["next"] += t1 - t0
        waits["put"] += time.perf_counter() - t1
        return host, dev

    first_host, batch = next_batch()
    losses, in_flight, steps_done, landed_at, report_s = [], [], 0, [], []
    report_every, run_ahead = data["report_every"], data["run_ahead"]

    def land(limit: int) -> None:
        """Waits until at most `limit` steps are in flight; a landed step
        is counted, and every `report_every`-th reports its loss as a
        real loop does (it has finished, so nothing waits for it)."""
        nonlocal steps_done
        while len(in_flight) > limit:
            with jax.profiler.TraceAnnotation("bench.wait_step"):
                loss = float(in_flight.pop(0))
            steps_done += 1
            landed_at.append(time.time())
            if steps_done % report_every == 0 or steps_done == 1:
                losses.append(loss)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.report"):
                    train.report({"step": steps_done, "loss": loss})
                report_s.append(time.perf_counter() - t0)

    iterations = []     # [ended at, dispatch s, next batch s, landing s]

    def take_step():
        nonlocal state, batch
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = step(state, batch)
        in_flight.append(metrics["loss"])
        t1 = time.perf_counter()
        _, batch = next_batch()
        t2 = time.perf_counter()
        land(run_ahead)
        iterations.append([time.time(), t1 - t0, t2 - t1,
                           time.perf_counter() - t2])

    for _ in range(data["warmup_steps"]):
        take_step()
    land(0)
    marks["warm"] = time.time()
    compiles_at_open, steps_at_open = compiles.count, steps_done
    landed_at.clear()
    report_s.clear()
    iterations.clear()
    for k in waits:
        waits[k] = 0.0
    trace_dir = os.path.join(workdir, "trace")
    trace = {"on": bool(config["trace"]), "t0": None, "t1": None}
    seconds = config["seconds"]
    t_open = time.time()
    while (now := time.time()) < t_open + seconds:
        if trace["on"] and trace["t0"] is None \
                and now >= t_open + seconds * data["trace_at"]:
            replica_probe.start_trace(trace_dir)
            trace["t0"] = time.time()
        if trace["t0"] and trace["t1"] is None \
                and now >= trace["t0"] + data["trace_seconds"]:
            replica_probe.stop_trace()
            trace["t1"] = time.time()
        take_step()
    land(0)
    jax.block_until_ready(state)
    t_close = time.time()
    if trace["t0"] and trace["t1"] is None:
        replica_probe.stop_trace()
        trace["t1"] = time.time()
    window = {"t0": t_open, "t1": t_close,
              "steps": steps_done - steps_at_open, "landed_at": landed_at,
              "report_s": report_s, "iterations": iterations,
              "programs_prepared": compiles.count - compiles_at_open,
              "wait_s": dict(waits)}
    devices = worker_devices()
    del state, batch
    # the step's own first loss is of the whole first batch at the seed's
    # weights; the reference walks that batch a few sequences at a time
    check = prog.check_against_reference(
        first_host, losses[0], max(2, len(jax.devices())))
    _write(os.path.join(workdir, "loop.json"), {
        "marks": marks, "window": window, "losses": losses,
        "step_bytes": step_bytes, "devices": devices, "trace": trace,
        "step_tokens": prog.global_batch * prog.seq_len,
        "flops_per_token": family.train_flops_per_token(
            config["model"], prog.seq_len),
        "reference_check": check})


def _longest_iteration(window: dict) -> dict:
    """The window's longest pass through the loop and where it went, so
    that a stalled run says what stalled: the dispatch, the wait for a
    batch, the wait for a step to land (with its report), or none of them
    (the loop's thread was off the CPU between two of its statements)."""
    ends = [window["t0"]] + [it[0] for it in window["iterations"]]
    at = max(range(1, len(ends)), key=lambda i: ends[i] - ends[i - 1])
    _, dispatch, batch, landing = window["iterations"][at - 1]
    return {"iteration": at, "seconds": ends[at] - ends[at - 1],
            "dispatch_s": dispatch, "next_batch_s": batch,
            "landing_s": landing,
            "longest_report_s": max(window["report_s"], default=0.0)}


def run_phase(phase: str, cell: dict, args, result: dict) -> None:
    if phase != "measure":
        raise ValueError(f"a train cell has no phase {phase!r}")
    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.train import JaxTrainer, ScalingConfig

    config, traffic = cell["config"], cell["traffic"]
    tokens = spec.generator(traffic["generator"]).generate(
        traffic, config, args.seed)
    cache_before = spec.compile_cache_entries()
    t_init = time.time()
    procs.start_cluster(args.workdir)
    t_cluster = time.time()
    chips = device.chip_request(cell["chips"])
    scaling = (ScalingConfig(num_workers=1, use_tpu=True,
                             chips_per_worker=chips) if chips
               else ScalingConfig(num_workers=1))
    loop_config = {
        "workdir": args.workdir, "family": config["family"],
        "model": config["model"], "job": config["job"], "traffic": traffic,
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace}
    failure: list = []

    def fit():
        try:
            JaxTrainer(train_loop, train_loop_config=loop_config,
                       scaling_config=scaling,
                       datasets={"train": rdata.from_numpy(
                           {"tokens": tokens})}).fit()
        except BaseException as e:  # noqa: BLE001 - re-raised below
            failure.append(e)

    try:
        t_fit = time.time()
        thread = threading.Thread(target=fit, daemon=True)
        thread.start()
        ready_path = os.path.join(args.workdir, "worker_ready.json")
        while thread.is_alive() and not os.path.exists(ready_path):
            time.sleep(0.05)
        if os.path.exists(ready_path):
            ready = spec.load_json(ready_path)
            device.require_chip(ready["devices"], cell["chips"])
        thread.join()
        if failure:
            raise failure[0]
    finally:
        ray_tpu.shutdown()
    loop = spec.load_json(os.path.join(args.workdir, "loop.json"))
    losses = loop["losses"]
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    checks = {
        "longest_iteration": _longest_iteration(loop["window"]),
        "reference": loop["reference_check"],
        "losses_finite": finite,
        "loss_fell": bool(losses) and losses[-1] < losses[0],
        "no_program_prepared_in_window":
            loop["window"]["programs_prepared"] == 0}
    result["record"] = {
        "kind": "train", "t_start": args.t_start, "seconds": args.seconds,
        "chips": cell["chips"], "marks": {
            "init": t_init, "cluster": t_cluster, "fit": t_fit,
            "worker_ready": ready["time"], **loop["marks"]},
        "window": loop["window"], "loop": loop,
        "cache_new": spec.compile_cache_entries() - cache_before,
        "devices": loop["devices"],
        "program_bytes": {"train_step": loop["step_bytes"]},
        "trace_dir": (os.path.join(args.workdir, "trace")
                      if loop["trace"]["t0"] else None)}
    result["attempted"] = loop["window"]["steps"]
    result["failed"] = 0 if finite else sum(
        1 for x in losses if not (x == x and abs(x) != float("inf")))
    result["checks"] = checks
    result["correct"] = (checks["reference"]["ok"] and finite
                         and checks["loss_fell"]
                         and checks["no_program_prepared_in_window"])
