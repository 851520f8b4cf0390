#!/usr/bin/env python3
"""The quickest proof that ray_tpu's two main paths still start on the chip.

    python chip_smoke.py            # one chip: device, kernels, train, serve
    python chip_smoke.py --chips 4  # four chips: only what exists across chips

GPT-2 training goes through `JaxTrainer` + `train/spmd.compile_gpt2_train`
and GPT-2 serving through `serve.run` + the HTTP proxy + `LLMEngine`, at
published widths with seeded random weights, each inside a worker that the
scheduler granted its chips. Nothing here may carry on without the TPU: a
phase whose JAX platform is not `tpu` fails, and any failed phase fails the
run.

This process never imports JAX (a process that has touched JAX holds the
chip). Every phase is a child process — for train and serve the driver of
a `ray_tpu` cluster whose worker owns the chip — and the child and all it
started are gone before the next phase begins.

The last line of standard output is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`, with
the device as JAX reported it inside a child; everything else is on
earlier lines. Exit code 0 only with `"ok": true`.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# (phase, seconds it may take); the whole run stays inside RUN_LIMIT_S
ONE_CHIP_PHASES = (("device", 180), ("kernels", 240), ("train", 420),
                   ("serve", 600), ("serve_reference", 300))
FOUR_CHIP_PHASES = (("build", 120), ("train_mesh", 600),
                    ("serve_replicas", 600))
RUN_LIMIT_S = 1150
SEED = 0    # weights, batches and prompts are all made from it

# train: the model of the benchmark's `train-small-1k`. The per-chip batch
# is the first of these whose compiled step fits the device (decided by
# memory_analysis, printed).
TRAIN_PRESET, TRAIN_SEQ = "gpt2-125m", 1024
TRAIN_PER_CHIP_BATCHES = (24, 20, 16, 12, 8, 4)
TRAIN_STEPS = 10
# the four-chip comparison: dp2·tp2 against one chip at the same global batch
MESH_BATCH, MESH_STEPS = 16, 5
# bf16 activations: the same step summed in another order across chips
MESH_LOSS_TOLERANCE = 2e-2

SERVE_PRESET, SERVE_SEQ, SERVE_BATCH = "gpt2-1.5b", 1024, 8
SERVE_NEW_TOKENS = 32
# prompt lengths in byte-tokens: the first is sent twice (cold, then as a
# shared-prefix hit that must give the same tokens); the rest arrive
# together, so they join and leave the running batch at different steps and
# the long ones prefill in chunks
SERVE_FIRST_LEN = 64
SERVE_BURST_LENS = (16, 130, 260, 400, 550, 700)
# teacher-forced check: the served token's logit under plain gpt2.forward
# must be within this of the row's maximum. 0.125 is eight bf16 steps at the
# top logit's magnitude (2..4 for these seeded weights); a wrong token sits
# whole units below.
SERVE_LOGIT_TOLERANCE = 0.125
REPLICA_PRESET, REPLICA_REQUESTS = "gpt2-125m", 16

# flash kernel check: max |kernel - reference| over max |reference|; both
# round to bf16 (2^-8 relative), the reference also rounds its probabilities
KERNEL_SHAPE = (4, 12, 2048, 64)
KERNEL_TOLERANCE = 4e-2


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


class PhaseFailed(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# --------------------------------------------------------------- the parent

def _session_pids(sid: int) -> list:
    pids = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        # after the "(comm)" field: state ppid pgrp session ...
        if int(fields[3]) == sid and fields[0] != "Z":
            pids.append(int(name))
    return pids


def _wait_session_empty(sid: int, seconds: float) -> list:
    deadline = time.monotonic() + seconds
    while (left := _session_pids(sid)) and time.monotonic() < deadline:
        time.sleep(0.2)
    return left


def _end_session(sid: int) -> int:
    """Everything the phase started must be gone before the next phase may
    open the chip. A clean phase leaves nothing; what is left is killed and
    counted."""
    left = _wait_session_empty(sid, 10)
    for pid in left:
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    _wait_session_empty(sid, 10)
    return len(left)


def run_phase(name: str, limit_s: float, args, workdir: str) -> dict:
    result_path = os.path.join(workdir, f"{name}.json")
    shm_before = set(os.listdir("/dev/shm"))
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name,
         "--workdir", workdir],
        start_new_session=True)
    try:
        rc = proc.wait(timeout=limit_s)
    except subprocess.TimeoutExpired:
        rc = None
    left_procs = _end_session(proc.pid)
    if rc is None:
        proc.wait()
    left_shm = sorted(set(os.listdir("/dev/shm")) - shm_before)
    for seg in left_shm:
        try:
            os.unlink(os.path.join("/dev/shm", seg))
        except OSError:
            pass
    try:
        with open(result_path) as f:
            result = json.load(f)
    except (OSError, ValueError):
        result = {"ok": False, "error": "phase wrote no result"}
    if rc is None:
        result.update(ok=False, error=f"phase exceeded its {limit_s:.0f}s")
    elif rc != 0:
        result["ok"] = False
        result.setdefault("error", f"phase exited with code {rc}")
    still = _session_pids(proc.pid)
    if still:
        result.update(ok=False, error=f"processes {still} outlived the phase")
    say(name, f"{'ok' if result['ok'] else 'FAILED: ' + result['error']} "
              f"[{time.monotonic() - t0:.1f}s; left behind and removed: "
              f"{left_procs} processes, {len(left_shm)} /dev/shm segments]")
    return result


def parent(args) -> int:
    phases = ONE_CHIP_PHASES if args.chips == 1 else FOUR_CHIP_PHASES
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    deadline = time.monotonic() + RUN_LIMIT_S
    ok, device = True, None
    try:
        for name, limit_s in phases:
            left = deadline - time.monotonic()
            result = run_phase(name, max(min(limit_s, left), 1), args,
                               workdir)
            device = result.get("device") or device
            if not result["ok"]:
                ok = False
                break
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if ok and (device is None or device["platform"] != "tpu"
               or device["count"] != args.chips):
        say("result", f"expected {args.chips} tpu device(s), found {device}")
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


# ------------------------------------------------------- what children share

def jax_device_block() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def require_tpu(device: dict) -> None:
    check(device["platform"] == "tpu",
          f"JAX found platform {device['platform']!r} "
          f"({device['count']} x {device['kind']}), not 'tpu': nothing is "
          f"run on it")


def build_native_store(phase: str) -> None:
    """The shm object store from source, as a fresh checkout has to: the
    library would degrade to per-object segments without it, which a chip
    run must not do unnoticed."""
    native = os.path.join(REPO, "ray_tpu", "_native")
    lib = os.path.join(native, "libraytpu_store.so")
    if os.path.exists(lib):
        os.unlink(lib)
    t0 = time.perf_counter()
    made = subprocess.run(["make", "-C", native], capture_output=True,
                          text=True, timeout=120)
    check(made.returncode == 0,
          f"make -C ray_tpu/_native failed: {made.stderr[-800:]}")
    from ray_tpu.core.native_store import native_available

    check(native_available(), "libraytpu_store.so was built but not loaded")
    say(phase, f"native store built from source and loaded in "
               f"{time.perf_counter() - t0:.1f}s")


def cache_entries() -> int:
    from ray_tpu.utils.platform import compile_cache_dir

    try:
        return len(os.listdir(compile_cache_dir()))
    except OSError:
        return 0


def worker_devices_are_tpu(phase: str, who: str, devices: list,
                           count: int) -> None:
    say(phase, f"{who} jax.devices(): {json.dumps(devices)}")
    check(len(devices) == count and all(d["platform"] == "tpu"
                                        for d in devices),
          f"{who} does not hold {count} tpu device(s): {devices}")


# ------------------------------------------------------------ phase: device

def phase_device(args, result: dict) -> None:
    from ray_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    import importlib.metadata as md

    import jax

    result["device"] = device = jax_device_block()
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu",
                                           "flax", "optax")}
    say("device", f"versions {json.dumps(versions)}")
    say("device", f"device {json.dumps(device)}")
    require_tpu(device)
    stats = jax.devices()[0].memory_stats()
    say("device", f"hbm bytes_limit {stats['bytes_limit']} "
                  f"({stats['bytes_limit'] / 2 ** 30:.2f} GiB) per device")
    say("device", f"compile cache at {cache} "
                  f"({cache_entries()} entries before this run)")
    build_native_store("device")


def phase_build(args, result: dict) -> None:
    build_native_store("build")


# ----------------------------------------------------------- phase: kernels

def phase_kernels(args, result: dict, shape=KERNEL_SHAPE) -> None:
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention, mha_reference

    result["device"] = device = jax_device_block()
    require_tpu(device)
    keys = jax.random.split(jax.random.key(SEED), 4)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys)

    def weighted(fn):
        # a fixed random cotangent keeps every gradient entry O(1)
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    programs = {
        "fwd": (jax.jit(lambda q, k, v: flash_attention(q, k, v, True)),
                jax.jit(lambda q, k, v: mha_reference(q, k, v, True))),
        "grad": (jax.jit(jax.grad(weighted(
                     lambda q, k, v: flash_attention(q, k, v, True)),
                     argnums=(0, 1, 2))),
                 jax.jit(jax.grad(weighted(
                     lambda q, k, v: mha_reference(q, k, v, True)),
                     argnums=(0, 1, 2)))),
    }
    for name, (kernel, reference) in programs.items():
        t0 = time.perf_counter()
        compiled = kernel.lower(q, k, v).compile()
        compile_s = time.perf_counter() - t0
        # the program that runs below is the one inspected here
        check("tpu_custom_call" in compiled.as_text(),
              f"flash {name}: no Mosaic custom call in the compiled program")
        got = jax.block_until_ready(compiled(q, k, v))
        want = jax.block_until_ready(reference(q, k, v))
        errs = []
        for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            g, r = g.astype(jnp.float32), r.astype(jnp.float32)
            check(bool(jnp.all(jnp.isfinite(g))), f"flash {name}: non-finite")
            errs.append(float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r))))
        say("kernels", f"flash {name} bf16 {list(shape)} causal: Mosaic "
                       f"custom call present, compile {compile_s:.2f}s, "
                       f"max|kernel-ref|/max|ref| = "
                       f"{[round(e, 5) for e in errs]} "
                       f"(tolerance {KERNEL_TOLERANCE})")
        check(max(errs) <= KERNEL_TOLERANCE,
              f"flash {name} differs from mha_reference by {max(errs):.4f}")


# ------------------------------------------------------------- phase: train

def train_loop(config: dict) -> None:
    """Runs inside the JaxTrainer worker — the process that was granted the
    chips. Builds the mesh from `jax.devices()`, compiles the GPT-2 step
    through `compile_gpt2_train`, takes the steps on one seeded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.spmd import compile_gpt2_train, default_optimizer
    from ray_tpu.utils.platform import device_report

    devices = jax.devices()
    axes = config["mesh"] or {"dp": len(devices)}
    mesh = build_mesh(MeshConfig(**axes), devices=devices)
    seq = config["seq"]
    cfg = gpt2.GPT2Config.preset(config["preset"], max_seq_len=seq,
                                 remat=True, remat_policy="dots")
    prog = compile_gpt2_train(cfg, mesh,
                              optimizer=default_optimizer(total_steps=100))
    state = prog.init_fn(jax.random.key(config["seed"]))
    hbm = (devices[0].memory_stats() or {}).get("bytes_limit")
    attempts, compiled = [], None
    for batch in config["global_batches"]:
        data = {"tokens": jax.ShapeDtypeStruct(
            (batch, seq + 1), jnp.int32, sharding=prog.batch_sharding)}
        t0 = time.perf_counter()
        try:
            candidate = prog.step_fn.lower(state, data).compile()
        except Exception as e:  # noqa: BLE001 - only an OOM refusal steps down
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            attempts.append({"global_batch": batch, "fits": False,
                             "refused": str(e)[:300]})
            continue
        m = candidate.memory_analysis()
        need = (m.temp_size_in_bytes + m.argument_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes)
        fits = hbm is None or need <= hbm
        attempts.append({
            "global_batch": batch, "fits": fits,
            "compile_s": round(time.perf_counter() - t0, 2),
            "temp_bytes": m.temp_size_in_bytes,
            "argument_bytes": m.argument_size_in_bytes,
            "per_device_bytes": need, "bytes_limit": hbm})
        if fits:
            compiled = candidate
            break
    if compiled is None:
        train.report({"attempts": attempts, "devices": device_report()})
        return
    tokens = jax.device_put(
        np.random.default_rng(config["seed"]).integers(
            0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32),
        prog.batch_sharding)
    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, {"tokens": tokens})
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    train.report({"attempts": attempts, "global_batch": batch,
                  "mesh": {k: int(v) for k, v in mesh.shape.items()},
                  "losses": losses, "step_s": step_s,
                  "devices": device_report()})


def run_trainer(phase: str, args, *, mesh, chips_per_worker, global_batches,
                steps, preset=TRAIN_PRESET, seq=TRAIN_SEQ) -> dict:
    """One `JaxTrainer.fit()` on the running cluster; returns what the
    worker reported, after the checks every training run must pass."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    entries = cache_entries()
    t0 = time.perf_counter()
    out = JaxTrainer(
        train_loop,
        train_loop_config={"preset": preset, "seq": seq, "mesh": mesh,
                           "global_batches": list(global_batches),
                           "steps": steps, "seed": SEED},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=chips_per_worker),
    ).fit().metrics
    fit_s = time.perf_counter() - t0
    for a in out["attempts"]:
        say(phase, f"memory_analysis: {json.dumps(a)}")
    check("losses" in out, "no candidate batch fits the device")
    losses, step_s = out["losses"], out["step_s"]
    warm = sorted(step_s[2:])
    say(phase, f"{preset} T={seq} mesh {out['mesh']} global batch "
               f"{out['global_batch']}: {steps} steps, losses "
               f"{[round(x, 4) for x in losses]}")
    say(phase, f"compile {out['attempts'][-1]['compile_s']}s "
               f"(+{cache_entries() - entries} compile-cache entries), first "
               f"step {step_s[0]:.3f}s, warm step median "
               f"{warm[len(warm) // 2]:.4f}s to block_until_ready, peak HBM "
               f"{[d['peak_bytes_in_use'] for d in out['devices']]} bytes, "
               f"fit() {fit_s:.1f}s")
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]} -> {losses[-1]}")
    return out


def phase_train(args, result: dict) -> None:
    import ray_tpu

    info = ray_tpu.init()
    try:
        chips = int(info["resources"].get("TPU", 0))
        say("train", f"ray_tpu.init() advertises {info['resources']}")
        check(chips >= 1, "the node advertises no TPU chip")
        out = run_trainer(
            "train", args, mesh=None, chips_per_worker=None,
            global_batches=[b * chips for b in TRAIN_PER_CHIP_BATCHES],
            steps=TRAIN_STEPS)
        worker_devices_are_tpu("train", "JaxTrainer worker", out["devices"],
                               chips)
    finally:
        ray_tpu.shutdown()


def phase_train_mesh(args, result: dict) -> None:
    """Four chips: the train step on a dp2·tp2 mesh against the same global
    batch on one chip, in one cluster."""
    import ray_tpu

    info = ray_tpu.init()
    try:
        say("train_mesh", f"ray_tpu.init() advertises {info['resources']}")
        check(info["resources"].get("TPU") == 4.0,
              "this phase needs a host that advertises four chips")
        one = run_trainer("train_mesh", args, mesh=None, chips_per_worker=1,
                          global_batches=[MESH_BATCH], steps=MESH_STEPS)
        worker_devices_are_tpu("train_mesh", "one-chip worker",
                               one["devices"], 1)
        four = run_trainer("train_mesh", args, mesh={"dp": 2, "tp": 2},
                           chips_per_worker=None,
                           global_batches=[MESH_BATCH], steps=MESH_STEPS)
        worker_devices_are_tpu("train_mesh", "dp2.tp2 worker",
                               four["devices"], 4)
        check(all((d["peak_bytes_in_use"] or 0) > 0
                  for d in four["devices"]),
              f"a device of the mesh held nothing: {four['devices']}")
        gaps = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
        say("train_mesh", f"|loss(1 chip) - loss(dp2.tp2)| per step "
                          f"{[round(g, 5) for g in gaps]} "
                          f"(tolerance {MESH_LOSS_TOLERANCE})")
        check(max(gaps) <= MESH_LOSS_TOLERANCE,
              f"dp2.tp2 losses leave the one-chip run by {max(gaps):.4f}")
        d = four["devices"][0]
        result["device"] = {"platform": d["platform"], "kind": d["kind"],
                            "count": len(four["devices"])}
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------------- phase: serve

def prompt_text(seed: int, index: int, length: int) -> str:
    """`length` printable ASCII bytes (one byte-token each), from the seed."""
    import random

    rng = random.Random(seed * 1000 + index)
    return "".join(chr(rng.randrange(32, 127)) for _ in range(length))


def post_completion(port: int, prompt: str, timeout: float = 900) -> dict:
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": SERVE_NEW_TOKENS,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        status, body = resp.status, json.loads(resp.read())
    return {"status": status, "wall_s": time.perf_counter() - t0,
            "prompt": prompt, "token_ids": body["choices"][0]["token_ids"],
            "finish_reason": body["choices"][0]["finish_reason"]}


def post_all(port: int, prompts: list) -> list:
    """The prompts at once, one connection each; every reply is read."""
    replies: list = [None] * len(prompts)

    def one(i):
        try:
            replies[i] = post_completion(port, prompts[i])
        except Exception as e:  # noqa: BLE001 - reported through the check
            replies[i] = {"status": repr(e), "prompt": prompts[i]}

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return replies


def check_replies(replies: list) -> None:
    for r in replies:
        check(r["status"] == 200,
              f"completion of a {len(r['prompt'])}-token prompt: {r['status']}")
        check(len(r["token_ids"]) == SERVE_NEW_TOKENS
              and r["finish_reason"] == "length",
              f"{len(r['token_ids'])} tokens ({r['finish_reason']}) for a "
              f"{len(r['prompt'])}-token prompt, wanted {SERVE_NEW_TOKENS}")


def deploy(phase: str, args, *, preset, max_batch, num_replicas):
    """`serve.run` of the OpenAI app with one chip per replica; returns
    (proxy port, a call for per-replica stats that waits until every
    replica has loaded, the deployment's name)."""
    from ray_tpu import serve
    from ray_tpu.serve.api import _get_or_create_controller
    from ray_tpu.serve.llm import build_openai_app

    t0 = time.perf_counter()
    app = build_openai_app(preset=preset, max_seq_len=SERVE_SEQ,
                           max_batch=max_batch, num_tpu_chips=1,
                           num_replicas=num_replicas, seed=SEED,
                           model_id=f"smoke-{num_replicas}")
    serve.run(app, route_prefix="/v1")
    port = serve.start()

    def stats():
        return replica_stats(_get_or_create_controller(), app.name,
                             num_replicas)

    stats()
    say(phase, f"{num_replicas} x {preset} max_batch={max_batch} "
               f"max_seq_len={SERVE_SEQ}: every replica loaded (weights "
               f"initialised in the replica from seed {SEED}) and proxy "
               f"on :{port} after {time.perf_counter() - t0:.1f}s")
    return port, stats, app.name


def replica_stats(controller, name: str, num_replicas: int) -> dict:
    import ray_tpu

    deadline = time.monotonic() + 120
    while True:
        table = ray_tpu.get(controller.get_routing_table.remote(name),
                            timeout=60)
        if table and len(table["replicas"]) == num_replicas:
            break
        check(time.monotonic() < deadline,
              f"{name}: {num_replicas} replicas never appeared")
        time.sleep(0.5)
    # a stats call queues behind the replica's __init__ (weights, cache)
    return {tag: ray_tpu.get(h.handle_request.remote("stats", (), {}),
                             timeout=600)
            for tag, h in sorted(table["replicas"].items())}


def phase_serve(args, result: dict) -> None:
    import ray_tpu
    from ray_tpu import serve

    info = ray_tpu.init()
    try:
        say("serve", f"ray_tpu.init() advertises {info['resources']}")
        port, stats_fn, _ = deploy("serve", args, preset=SERVE_PRESET,
                                   max_batch=SERVE_BATCH, num_replicas=1)

        def replica():
            (stats,) = stats_fn().values()
            return stats

        worker_devices_are_tpu("serve", "replica", replica()["devices"], 1)
        entries = cache_entries()

        def ttft_sum():
            return replica()["ttft_s"]["sum"]

        first = prompt_text(SEED, 0, SERVE_FIRST_LEN)
        cold = post_completion(port, first)
        cold_ttft = ttft_sum()
        again = post_completion(port, first)
        warm_ttft = ttft_sum() - cold_ttft
        burst = post_all(port, [prompt_text(SEED, 1 + i, n)
                                for i, n in enumerate(SERVE_BURST_LENS)])
        replies = [cold, again] + burst
        check_replies(replies)
        stats = replica()

        def gap(r, ttft):
            return (r["wall_s"] - ttft) / (SERVE_NEW_TOKENS - 1)

        say("serve", f"8 completions over HTTP, {SERVE_NEW_TOKENS} greedy "
                     f"tokens each, prompt lengths "
                     f"{[len(r['prompt']) for r in replies]}")
        say("serve", f"cold  ({SERVE_FIRST_LEN}-token prompt, compiles): "
                     f"wall {cold['wall_s']:.2f}s, time to first token "
                     f"{cold_ttft:.2f}s, gap between tokens "
                     f"{gap(cold, cold_ttft) * 1e3:.1f} ms "
                     f"(+{cache_entries() - entries} compile-cache entries)")
        say("serve", f"warm  (same prompt, prefix hit): wall "
                     f"{again['wall_s']:.2f}s, time to first token "
                     f"{warm_ttft:.3f}s, gap between tokens "
                     f"{gap(again, warm_ttft) * 1e3:.1f} ms")
        say("serve", f"burst ({len(burst)} prompts at once): wall "
                     f"{max(r['wall_s'] for r in burst):.2f}s for the "
                     f"slowest, engine mean time to first token "
                     f"{stats['ttft_s']['sum'] / 8:.3f}s over all 8")
        say("serve", f"engine: {stats['engine_steps']} steps, "
                     f"{stats['chunk_steps']} chunked, "
                     f"{stats['tokens_prefilled']} prompt tokens prefilled, "
                     f"kv {json.dumps(stats['kv_cache'])}, peak HBM "
                     f"{stats['devices'][0]['peak_bytes_in_use']} of "
                     f"{stats['devices'][0]['bytes_limit']} bytes")
        check(stats["engine_steps"] > 0 and stats["chunk_steps"] > 0,
              f"the engine did not step: {stats}")
        check(stats["kv_cache"]["prefix_hits"] >= 1,
              f"no shared-prefix hit: {stats['kv_cache']}")
        check(again["token_ids"] == cold["token_ids"],
              f"the same prompt gave {cold['token_ids']} then "
              f"{again['token_ids']}")
        with open(os.path.join(args.workdir, "served.json"), "w") as f:
            json.dump([{"prompt": r["prompt"], "token_ids": r["token_ids"]}
                       for r in replies], f)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def phase_serve_reference(args, result: dict, preset=SERVE_PRESET,
                          seq=SERVE_SEQ) -> None:
    """With the cluster down and the chip free: the same seeded weights,
    each served sequence once through plain `gpt2.forward`, and at every
    generated position the served token's logit against the row's
    maximum (random weights give near-ties, so token equality would be a
    coin toss; this is not)."""
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import ByteTokenizer

    result["device"] = device = jax_device_block()
    require_tpu(device)
    with open(os.path.join(args.workdir, "served.json")) as f:
        served = json.load(f)
    cfg = gpt2.GPT2Config.preset(preset, max_seq_len=seq)
    params = gpt2.init_params(jax.random.key(SEED), cfg)
    tok = ByteTokenizer()
    rows = [tok.encode(s["prompt"]) + s["token_ids"] for s in served]
    width = -(-max(len(r) for r in rows) // 128) * 128
    tokens = np.zeros((len(rows), width), np.int32)   # causal: padding after
    for i, r in enumerate(rows):                      # a row cannot reach it
        tokens[i, :len(r)] = r
    forward = jax.jit(lambda p, t: gpt2.forward(p, t, cfg))
    worst = 0.0
    for i, s in enumerate(served):        # one row at a time: [1, T, vocab]
        logits = np.asarray(forward(params, jnp.asarray(tokens[i:i + 1]))
                            .astype(jnp.float32))[0]
        n_prompt = len(rows[i]) - len(s["token_ids"])
        for j, token in enumerate(s["token_ids"]):
            row = logits[n_prompt - 1 + j]
            check(bool(np.all(np.isfinite(row))), "non-finite logits")
            worst = max(worst, float(row.max() - row[token]))
    say("serve_reference", f"{sum(len(s['token_ids']) for s in served)} "
                           f"served tokens teacher-forced through "
                           f"gpt2.forward ({preset}): largest (row max - "
                           f"served token's logit) = {worst:.4f} "
                           f"(tolerance {SERVE_LOGIT_TOLERANCE})")
    check(worst <= SERVE_LOGIT_TOLERANCE,
          f"a served token is {worst:.3f} below the reference's best")


def phase_serve_replicas(args, result: dict) -> None:
    """Four chips: four one-chip replicas behind the proxy, against one
    replica's answers to the same prompts."""
    import ray_tpu
    from ray_tpu import serve

    prompts = [prompt_text(SEED, i, 24 + 8 * i)
               for i in range(REPLICA_REQUESTS)]
    info = ray_tpu.init()
    try:
        answers = {}
        for n in (1, 4):
            port, stats_fn, name = deploy("serve_replicas", args,
                                          preset=REPLICA_PRESET,
                                          max_batch=SERVE_BATCH,
                                          num_replicas=n)
            replies = post_all(port, prompts)
            check_replies(replies)
            answers[n] = [r["token_ids"] for r in replies]
            stats = stats_fn()
            for tag, s in stats.items():
                worker_devices_are_tpu("serve_replicas", f"replica {tag}",
                                       s["devices"], 1)
                say("serve_replicas", f"replica {tag} generated "
                                      f"{s['total_generated']} tokens")
            check(all(s["total_generated"] > 0 for s in stats.values()),
                  "a replica answered nothing")
            chips = sorted(s["devices"][0]["process_chips"]
                           for s in stats.values())
            say("serve_replicas", f"{n} replica(s) on chips {chips}")
            check(len(set(chips)) == n, f"replicas share a chip: {chips}")
            serve.delete(name)   # its chips return as the replicas exit
        check(answers[4] == answers[1],
              "four replicas and one replica gave different tokens")
        say("serve_replicas", f"{REPLICA_REQUESTS} requests: tokens from "
                              f"four replicas equal the one-replica answers")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


PHASES = {"device": phase_device, "build": phase_build,
          "kernels": phase_kernels, "train": phase_train,
          "train_mesh": phase_train_mesh, "serve": phase_serve,
          "serve_reference": phase_serve_reference,
          "serve_replicas": phase_serve_replicas}


def child(args) -> int:
    result: dict = {"ok": False}
    try:
        PHASES[args.phase](args, result)
        result["ok"] = True
    except PhaseFailed as e:
        result["error"] = str(e)
    except BaseException as e:  # noqa: BLE001 - the phase's failure, reported
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:2000]
    with open(os.path.join(args.workdir, f"{args.phase}.json"), "w") as f:
        json.dump(result, f)
    return 0 if result["ok"] else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the paths that exist across four chips")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args()
    return child(args) if args.phase else parent(args)


if __name__ == "__main__":
    sys.exit(main())
