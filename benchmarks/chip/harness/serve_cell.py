"""A cell of kind `serve`: the family's app behind the HTTP proxy on a
cluster this phase starts, one replica holding one chip, the load
generator in this process, and then, with the cluster down and the chip
free, the family's reference check of what was served.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import threading
import time

from . import client, client_log, device, procs, spec

PHASES = (("measure", 900), ("reference", 400))


def run_phase(phase: str, cell: dict, args, result: dict) -> None:
    if phase == "measure":
        measure(cell, args, result)
    else:
        reference(cell, args, result)


class Replica:
    """Calls into the one replica's server object, as the program's own
    `stats` is reached (`chip_smoke.replica_stats`, PR 21)."""

    def __init__(self, name: str):
        import ray_tpu
        from ray_tpu.serve.api import _get_or_create_controller

        self._get = ray_tpu.get
        controller = _get_or_create_controller()
        deadline = time.monotonic() + 120
        while True:
            table = ray_tpu.get(controller.get_routing_table.remote(name),
                                timeout=60)
            if table and len(table["replicas"]) == 1:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(f"{name}: the replica never appeared")
            time.sleep(0.2)
        (self._handle,) = table["replicas"].values()

    def call(self, method: str, *args):
        # a call queues behind the replica's __init__ (weights, cache)
        return self._get(self._handle.handle_request.remote(
            method, args, {}), timeout=600)

    def counters(self) -> dict:
        return {"stats": self.call("stats"), "probe": self.call("probe")}


def measure(cell: dict, args, result: dict) -> None:
    import ray_tpu
    from ray_tpu import serve

    config, traffic = cell["config"], cell["traffic"]
    family = spec.family(config["family"])
    load_plan = spec.generator(traffic["generator"]).generate(
        traffic, config, args.seed, args.seconds)
    cache_before = spec.compile_cache_entries()
    marks = {"init": time.time()}
    procs.start_cluster(args.workdir)
    marks["cluster"] = time.time()
    record: dict = {"kind": "serve", "t_start": args.t_start,
                    "seconds": args.seconds, "chips": cell["chips"],
                    "limits": traffic.get("limits")}
    try:
        app = family.build_app(config, args.seed,
                               device.chip_request(cell["chips"]))
        marks["run"] = time.time()
        serve.run(app, route_prefix="/v1")
        port = serve.start()
        replica = Replica(app.name)
        first = replica.call("probe")
        device.require_chip(first["devices"], cell["chips"])
        marks["replica_up"] = time.time()
        marks.update({f"replica_{k}": v for k, v in first["marks"].items()})

        load = client.Load(f"http://127.0.0.1:{port}{family.REQUEST_PATH}",
                           family.request_body)
        warm = asyncio.run(load.one_by_one(load_plan["warmup"]))
        bad = [e for e in warm if client_log.failed(e)]
        if bad:
            raise RuntimeError(f"a warm-up request failed: {bad[0]}")
        record["program_bytes"] = replica.call("probe_programs")
        marks["warm"] = time.time()

        window: dict = {}
        t_open = time.time() + load_plan["ramp_s"]
        t_close = t_open + args.seconds
        trace_dir = os.path.join(args.workdir, "trace")

        def side() -> None:
            """What is read from the replica at the window's edges, and
            the trace in its middle, off the load generator's thread."""
            _sleep_until(t_open)
            window["before"] = replica.counters()
            if args.trace:
                _sleep_until(t_open + args.seconds * traffic["trace_at"])
                window["trace_t0"] = replica.call("profile_start", trace_dir)
                _sleep_until(window["trace_t0"] + traffic["trace_seconds"])
                window["trace_t1"] = replica.call("profile_stop")
            _sleep_until(t_close)
            window["after"] = replica.counters()

        side_thread = threading.Thread(target=side, daemon=True)
        side_thread.start()
        if load_plan["loop"] == "open":
            asyncio.run(load.open_loop(
                load_plan["requests"], t_open,
                wait_for=lambda e: t_open <= e["due"] < t_close,
                drain_until=t_close + load_plan["drain_s"]))
        else:
            t_start_load = t_open - load_plan["ramp_s"]
            _sleep_until(t_start_load)
            asyncio.run(load.closed_loop(
                load_plan["requests"], load_plan["clients"], t_close))
        side_thread.join()
        marks["load_end"] = time.time()
        last = replica.call("probe")
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()

    log = load.log
    counted = (client_log.due_in(log, t_open, t_close)
               if load_plan["loop"] == "open"
               else client_log.ended_in(log, t_open, t_close))
    # the reference checks a seeded sample of the greedy replies
    tokenizer = family.CharTokenizer()
    by_id = {r["id"]: r for r in load_plan["requests"]}
    greedy = [e for e in counted
              if e["greedy"] and not client_log.failed(e)]
    sample = random.Random(args.seed).sample(
        greedy, min(traffic["reference_sample"], len(greedy)))
    with open(os.path.join(args.workdir, "served.json"), "w") as f:
        json.dump([{"id": e["id"],
                    "prompt_ids": by_id[e["id"]]["prompt_ids"],
                    "token_ids": tokenizer.encode(e["text"])}
                   for e in sample], f)
    incomplete = [e["id"] for e in counted if not client_log.failed(e)
                  and not (client_log.n_tokens(e) == e["max_tokens"]
                           or e["finish_reason"] == "stop")]
    for e in log:
        del e["text"]
    prepared = (window["after"]["probe"]["compiles"]
                - window["before"]["probe"]["compiles"])
    record.update({
        "marks": marks, "loop": load_plan["loop"],
        "window": {"t0": t_open, "t1": t_close,
                   "programs_prepared": prepared},
        # read when the replica answered, which a slow profile_stop delays
        "counters": {"before": window["before"]["stats"],
                     "after": window["after"]["stats"],
                     "before_at": window["before"]["probe"]["time"],
                     "after_at": window["after"]["probe"]["time"]},
        "client": log, "counted_ids": [e["id"] for e in counted],
        "prompt_tokens_counted": sum(e["prompt_tokens"] for e in counted),
        "cache_new": spec.compile_cache_entries() - cache_before,
        "devices": last["devices"],
        "trace_dir": trace_dir if args.trace else None})
    result["record"] = record
    result["attempted"] = len(counted)
    result["failed"] = sum(client_log.failed(e) for e in counted)
    # the latency medians are over the requests that were answered, so a
    # server that refuses or drops some would read faster: not correct
    result["checks"] = {
        "no_program_prepared_in_window": prepared == 0,
        "every_reply_whole": not incomplete,
        "no_request_failed": result["failed"] == 0,
        "greedy_replies_sampled": len(sample)}
    result["correct"] = (prepared == 0 and not incomplete
                         and result["failed"] == 0 and len(sample) > 0)


def reference(cell: dict, args, result: dict) -> None:
    """The chip is free now: this process holds it for the check."""
    from ray_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    config = cell["config"]
    family = spec.family(config["family"])
    served = spec.load_json(os.path.join(args.workdir, "served.json"))
    check = family.check_served(config, args.seed, served)
    result["checks"] = {"reference": check}
    result["correct"] = bool(check["ok"])


def _sleep_until(t: float) -> None:
    while (left := t - time.time()) > 0:
        time.sleep(min(left, 0.5))
