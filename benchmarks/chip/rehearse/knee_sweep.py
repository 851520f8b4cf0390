#!/usr/bin/env python3
"""The knee of an open-loop mix, found once on the chip: one deployment,
one set-up, then the same mix at each of a few rates in rising order, a
window each, and a table. The mix's own generator draws each rate's
schedule, so the rate the traffic file ends up with is swept on the very
schedule the cell then runs. A rate passes if at least 90% of its
requests met the mix's limits and completions keep up with arrivals: the
requests in flight at the window's close are no more than arrive in the
longest time a request inside both limits can take (a server that keeps
up holds no more, by Little's law; comparing the close with the middle of
the window, as the first sweep did, is noise at two or three requests in
flight). The knee is the highest swept rate that passes with every lower
swept rate passing too; the traffic file gets 0.8 x the knee.

    python benchmarks/chip/rehearse/knee_sweep.py --workload serve-xl-chat \
        --rates 0.4,0.5,0.6,0.7,0.8 [--seconds 51] [--seed 1] [--out DIR]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP_DIR))
sys.path[:0] = [REPO, CHIP_DIR]


def in_flight(log: list, t: float) -> int:
    return sum(1 for e in log if e["due"] <= t
               and not ((e.get("done") or e.get("failed_at") or 1e18) <= t))


def longest_inside_limits_s(traffic: dict) -> float:
    """The TTFT limit, and the TPOT limit for every further token of the
    longest output the mix asks for."""
    limits = traffic["limits"]
    return (limits["ttft_ms"] + (traffic["output"]["clip"][1] - 1)
            * limits["tpot_ms"]) / 1e3


def passes(row: dict, traffic: dict) -> bool:
    return (row["attain_pct"] >= 90.0 and row["in_flight_close"]
            <= row["rate_per_s"] * longest_inside_limits_s(traffic))


def knee(rows: list, traffic: dict):
    """The highest rate that passes with every lower one; None if the
    lowest fails."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_per_s"]):
        if not passes(row, traffic):
            break
        best = row["rate_per_s"]
    return best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out"))
    args = ap.parse_args()
    import run as command

    command.environment()               # as every run of the benchmark
    import ray_tpu
    from harness import (client, client_log as cl, device, procs, serve_cell,
                         spec)
    from ray_tpu import serve

    cell = spec.cell(spec.benchmark(), args.workload)
    config, traffic = cell["config"], cell["traffic"]
    family = spec.family(config["family"])
    gen = spec.generator(traffic["generator"])
    rows = []
    workdir = os.path.join(command.RUNS_DIR, "knee_sweep")
    os.makedirs(workdir, exist_ok=True)
    procs.start_cluster(workdir)
    try:
        app = family.build_app(config, args.seed,
                               device.chip_request(cell["chips"]))
        serve.run(app, route_prefix="/v1")
        port = serve.start()
        replica = serve_cell.Replica(app.name)
        device.require_chip(replica.call("probe")["devices"], cell["chips"])
        url = f"http://127.0.0.1:{port}{family.REQUEST_PATH}"
        warm = gen.generate(traffic, config, args.seed, args.seconds)
        asyncio.run(client.Load(url, family.request_body).one_by_one(
            warm["warmup"]))
        for rate in sorted(float(r) for r in args.rates.split(",")):
            plan = gen.generate({**traffic, "rate_per_s": rate}, config,
                                args.seed, args.seconds)
            load = client.Load(url, family.request_body)
            t_open = time.time() + plan["ramp_s"]
            t_close = t_open + args.seconds
            before = replica.call("stats")
            asyncio.run(load.open_loop(
                plan["requests"], t_open,
                wait_for=lambda e: True,
                drain_until=t_close + 60.0))
            after = replica.call("stats")
            counted = cl.due_in(load.log, t_open, t_close)
            ok = [e for e in counted if not cl.failed(e)]
            ttft = [cl.ttft_ms(e) for e in ok]
            tpot = [v for e in ok if (v := cl.tpot_ms(e)) is not None]
            row = {
                "rate_per_s": rate, "due": len(counted),
                "failed": len(counted) - len(ok),
                "attain_pct": 100.0 * sum(cl.met(e, traffic["limits"])
                                          for e in counted) / len(counted),
                "ttft_p50_ms": cl.percentile(ttft, 50),
                "ttft_p90_ms": cl.percentile(ttft, 90),
                "tpot_p50_ms": cl.percentile(tpot, 50),
                "tpot_p90_ms": cl.percentile(tpot, 90),
                "in_flight_mid": in_flight(load.log,
                                           (t_open + t_close) / 2),
                "in_flight_close": in_flight(load.log, t_close),
                "done_by_close_pct": 100.0 * sum(
                    1 for e in counted if (e.get("done") or 1e18)
                    <= t_close) / len(counted),
                "engine_steps": after["engine_steps"]
                - before["engine_steps"],
                "tokens": cl.tokens_between(load.log, t_open, t_close)}
            row["passes"] = passes(row, traffic)
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
            procs.remove_cluster_shm(workdir)
    found = knee(rows, traffic)
    print(json.dumps({"knee_per_s": found, "rate_per_s":
                      None if found is None else round(0.8 * found, 3)}),
          flush=True)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "knee_sweep.json"), "w") as f:
        json.dump({"seconds": args.seconds, "seed": args.seed,
                   "knee_per_s": found, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
