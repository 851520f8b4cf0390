"""Collective-layer bus-bandwidth benchmark (allreduce / reducescatter /
allgather / broadcast).

Measures the second BASELINE.json metric ("ICI allreduce bus-bw, GB/s")
at the collective API layer — the analog of the reference's
`util/collective/examples/` throughput scripts driving
`collective.py:311` allreduce.

Modes:
- **processes** (default): N member processes form an `xla-multihost`
  group exactly as user actors do (gloo on CPU hosts, ICI on multi-chip
  TPU hosts) and time whole-group collectives.
- **mesh**: times raw XLA collectives (`psum`/`psum_scatter`/
  `all_gather`) inside one jitted shard_map over the local device mesh —
  the in-program path the parallel layer (FSDP/TP) actually exercises on
  TPU; on a single host this is the honest ICI/HBM-bound number.

Bus bandwidth follows the NCCL-tests convention so numbers compare to
the reference's NCCL baselines: allreduce 2(w-1)/w · S/t,
reducescatter/allgather (w-1)/w · S/t, broadcast S/t.

Run: `python benchmarks/collective_benchmark.py [--mode mesh|processes]
[--world 4] [--sizes-mb 1,8,64] [--op allreduce,...]`
Emits one JSON line per (op, size) plus a summary line.

`--mode suite` runs the hierarchical/quantized gate rows instead
(`collective_suite`, also reachable as
`microbenchmark.collective_plane`) and writes the
`collective_microbench.json` artifact consumed by
`check_regression.py --suite collective`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax import shard_map  # noqa: E402

MEMBER_ENV = {"JAX_PLATFORMS": "cpu",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def _bus_factor(op: str, world: int) -> float:
    return {"allreduce": 2.0 * (world - 1) / world,
            "reducescatter": (world - 1) / world,
            "allgather": (world - 1) / world,
            "broadcast": 1.0}[op]


# ---------------------------------------------------------------- processes
def bench_processes(world: int, sizes: list, ops: list, iters: int) -> list:
    import ray_tpu

    ray_tpu.init(num_cpus=world + 2, num_tpu_chips=0, max_workers=world + 2)

    @ray_tpu.remote
    class Member:
        def __init__(self, world, rank, name):
            import ray_tpu.util.collective as col

            self.world, self.rank, self.name = world, rank, name
            col.init_collective_group(world, rank, backend="xla-multihost",
                                      group_name=name)

        def run(self, op, nbytes, iters):
            import ray_tpu.util.collective as col

            n = max(nbytes // 4, self.world)
            n -= n % self.world  # reducescatter needs world-divisible
            x = np.ones(n, dtype=np.float32)
            if op == "reducescatter":
                x = x.reshape(self.world, -1)
            col.barrier(group_name=self.name)
            fn = {"allreduce": lambda: col.allreduce(x, group_name=self.name),
                  "reducescatter": lambda: col.reducescatter(
                      x, group_name=self.name),
                  "allgather": lambda: col.allgather(
                      None, x, group_name=self.name),
                  "broadcast": lambda: col.broadcast(
                      x, src_rank=0, group_name=self.name)}[op]
            fn()  # warm (compile + rendezvous)
            col.barrier(group_name=self.name)
            t0 = time.perf_counter()
            for _ in range(iters):
                fn()
            dt = (time.perf_counter() - t0) / iters
            return dt

        def destroy(self):
            import ray_tpu.util.collective as col

            col.destroy_collective_group(self.name)

    name = f"bench{os.getpid() % 10000}"
    members = [Member.options(runtime_env={"env_vars": MEMBER_ENV}).remote(
        world, r, name) for r in range(world)]
    rows = []
    for op in ops:
        for nbytes in sizes:
            dts = ray_tpu.get([m.run.remote(op, nbytes, iters)
                               for m in members], timeout=600)
            dt = max(dts)  # group op finishes when the slowest rank does
            rows.append(_row(op, world, nbytes, dt, mode="processes"))
    for m in members:
        try:
            ray_tpu.get(m.destroy.remote(), timeout=30)
        except Exception:
            pass
    ray_tpu.shutdown()
    return rows


# --------------------------------------------------------------------- mesh
def bench_mesh(world: int, sizes: list, ops: list, iters: int) -> list:
    import jax
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if len(devs) < world:
        from ray_tpu.utils.platform import ensure_virtual_cpu

        ensure_virtual_cpu(world)
        import jax

        devs = jax.devices()
    mesh = Mesh(np.array(devs[:world]), ("p",))

    progs = {
        "allreduce": lambda a: lax.psum(a, "p"),
        "reducescatter": lambda a: lax.psum_scatter(a, "p", tiled=True),
        "allgather": lambda a: lax.all_gather(a, "p", tiled=True),
        "broadcast": lambda a: lax.all_gather(  # one src's data everywhere
            a, "p", tiled=True)[: a.shape[0]],
    }
    rows = []
    for op in ops:
        for nbytes in sizes:
            n = max(nbytes // 4, world * world)
            n -= n % (world * world)
            per = n // world
            x = jax.device_put(
                np.ones(n, dtype=np.float32),
                NamedSharding(mesh, P("p")))
            f = jax.jit(shard_map(progs[op], mesh=mesh, in_specs=P("p"),
                                  out_specs=P("p")))
            jax.block_until_ready(f(x))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = f(x)
            jax.block_until_ready(out)
            dt = (time.perf_counter() - t0) / iters
            rows.append(_row(op, world, per * world * 4, dt, mode="mesh"))
            del x
    return rows


def _row(op: str, world: int, nbytes: int, dt: float, mode: str) -> dict:
    alg_bw = nbytes / dt / 1e9
    return {"op": op, "world": world, "bytes": nbytes, "mode": mode,
            "time_s": round(dt, 6),
            "alg_bw_gb_s": round(alg_bw, 3),
            "bus_bw_gb_s": round(alg_bw * _bus_factor(op, world), 3)}


# ------------------------------------------------------- hierarchical suite
HIER_MEMBER_ENV = {"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}


def collective_suite(out_path: str | None = None, payload_mb: int = 8,
                     iters: int = 5) -> dict:
    """Gate rows for `check_regression.py --suite collective`, measured on
    the emulated 2-host x 2-device topology (2 member processes, each
    with 2 virtual CPU devices; the cross-process gloo edge is the slow
    "DCN" fabric, the in-process devices the fast one):

      allreduce_mb_s       — the flat pre-hierarchy path at the
                             collective API layer (host-staged numpy in,
                             one world-flat device allreduce, numpy out);
      hier_allreduce_mb_s  — the staged two-level device path
                             (`allreduce_device`): payload split over the
                             local devices, each column allreducing its
                             S/2 shard across the slow edge concurrently;
      quant_allreduce_mb_s — same with the int8 inter hop (per-chunk
                             scales; error feedback off — the wire-rate
                             row; grad sync below exercises EF);
      grad_sync_steps_per_s — cross_worker_grad_sync steps/s on the
                             device hierarchical path with the
                             error-feedback int8 inter hop (fused ~8 MB
                             gradient pytree per step, residual carried
                             across iterations);
      reshard_mb_s         — reshard() of a 32 MB array from a 4-device
                             sharding onto a different 2-device mesh
                             (the restore-under-new-mesh window path).
    """
    import ray_tpu

    nbytes = payload_mb * (1 << 20)
    results: dict = {}

    ray_tpu.init(num_cpus=4, num_tpu_chips=0, max_workers=6)

    @ray_tpu.remote
    class HierMember:
        def __init__(self, world, rank, name):
            import ray_tpu.util.collective as col

            self.world, self.rank, self.name = world, rank, name
            col.init_collective_group(world, rank, backend="xla-multihost",
                                      group_name=name)

        def run(self, mode, nbytes, iters):
            import time as _t

            import numpy as _np

            import ray_tpu.util.collective as col
            from ray_tpu.train.spmd import cross_worker_grad_sync

            n = nbytes // 4
            g = col.get_group(self.name)
            rng = _np.random.default_rng(17 + self.rank)
            x = rng.standard_normal(n).astype(_np.float32)
            quant = col.QuantizedAllreduce(dtype="int8", chunk=4096,
                                           error_feedback=False)
            quant_ef = col.QuantizedAllreduce(dtype="int8", chunk=4096,
                                              error_feedback=True)
            tree = {"w": x.reshape(-1, 1024), "b": x[:4096].copy()}
            fns = {
                "flat": lambda: col.allreduce(x.copy(),
                                              group_name=self.name),
                "hier": lambda: g.allreduce_device(x),
                "quant": lambda: g.allreduce_device(x, quantize=quant),
                "grad_sync": lambda: cross_worker_grad_sync(
                    tree, self.name, self.world, quantize=quant_ef),
            }
            fn = fns[mode]
            col.barrier(group_name=self.name)
            fn()  # warm: compile + transport setup
            col.barrier(group_name=self.name)
            t0 = _t.perf_counter()
            for _ in range(iters):
                out = fn()
            if mode != "flat":  # device results: force completion
                import jax

                jax.block_until_ready(
                    out["b"] if mode == "grad_sync" else out)
            return (_t.perf_counter() - t0) / iters

    name = f"hier{os.getpid() % 10000}"
    members = [HierMember.options(
        runtime_env={"env_vars": HIER_MEMBER_ENV}).remote(2, r, name)
        for r in range(2)]
    for mode, row in (("flat", "allreduce_mb_s"),
                      ("hier", "hier_allreduce_mb_s"),
                      ("quant", "quant_allreduce_mb_s"),
                      ("grad_sync", "grad_sync_steps_per_s")):
        dts = ray_tpu.get([m.run.remote(mode, nbytes, iters)
                           for m in members], timeout=600)
        dt = max(dts)  # a group op finishes when the slowest member does
        if row.endswith("_mb_s"):
            results[row] = nbytes / dt / 1e6
        else:
            results[row] = 1.0 / dt
        print(json.dumps({"row": row, "value": round(results[row], 2),
                          "dt_s": round(dt, 4)}))
    ray_tpu.shutdown()

    # reshard row: in-process, 4-device source -> different 2-device mesh
    from ray_tpu.utils.platform import ensure_virtual_cpu

    ensure_virtual_cpu(6)
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.util.collective import reshard

    rbytes = 32 * (1 << 20)
    arr = np.arange(rbytes // 4, dtype=np.float32).reshape(-1, 1024)
    src = reshard(arr, NamedSharding(
        Mesh(np.array(jax.devices()[:4]), ("p",)), P("p")))
    dst_sh = NamedSharding(Mesh(np.array(jax.devices()[4:6]), ("p",)),
                           P("p"))
    jax.block_until_ready(reshard(src, dst_sh))  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = reshard(src, dst_sh)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    results["reshard_mb_s"] = rbytes / dt / 1e6
    print(json.dumps({"row": "reshard_mb_s",
                      "value": round(results["reshard_mb_s"], 2)}))

    # streaming reshard row: a 64 MB host leaf redistributed through an
    # 8 MB chunk budget (peak host bytes <= in_flight * chunk, asserted
    # by tests; here we gate the pipelined throughput)
    from ray_tpu.util.collective import reshard_streaming

    sbytes = 64 * (1 << 20)
    big = np.arange(sbytes // 4, dtype=np.float32).reshape(-1, 1024)
    s_chunk = 8 * (1 << 20)
    jax.block_until_ready(reshard_streaming(
        big, dst_sh, chunk_bytes=s_chunk, max_in_flight=2))  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = reshard_streaming(big, dst_sh, chunk_bytes=s_chunk,
                                max_in_flight=2)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    results["reshard_large_mb_s"] = sbytes / dt / 1e6
    print(json.dumps({"row": "reshard_large_mb_s",
                      "value": round(results["reshard_large_mb_s"], 2)}))

    # fused in-program grad sync: whole train step (fwd+bwd+two-level
    # int8-EF sync+apply) as ONE compiled XLA program on the emulated
    # 2x2 hierarchical mesh — no Python between collectives. A second
    # row gates the acceptance claim head-on: the same fwd+bwd+EF-sync
    # as one fused program vs as the staged dispatch chain (grad program,
    # then sync program — PR-12 shape) at matched in-process topology.
    import jax.numpy as jnp
    import optax
    from ray_tpu.parallel import mesh as mesh_lib
    from ray_tpu.train import spmd
    from ray_tpu.util.collective import QuantizedAllreduce
    from ray_tpu.util.collective.hierarchy import (Topology,
                                                   hier_allreduce_ef_program)

    mesh = mesh_lib.build_hierarchical_mesh(
        {"dp": 4}, devices=jax.devices()[:4],
        topology=Topology(inter=2, intra=2))
    gbytes = payload_mb * (1 << 20)
    cols = 1024
    rows_n = gbytes // 4 // cols
    quant_ef2 = QuantizedAllreduce(dtype="int8", chunk=4096,
                                   error_feedback=True)

    def _loss(p, b):
        return jnp.mean((b @ p["w"]) ** 2)

    ct = spmd.compile_train(
        _loss, lambda k: {"w": jnp.zeros((rows_n, cols), jnp.float32)},
        {"w": P()}, mesh, optimizer=optax.sgd(1e-3),
        grad_quantize=quant_ef2)
    state = ct.init_fn(jax.random.key(0))
    ef = ct.init_ef_fn()
    batch = jax.device_put(
        np.random.default_rng(11).standard_normal(
            (4, rows_n), dtype=np.float32),
        NamedSharding(mesh, P((*mesh_lib.DP_SUB_AXES, "fsdp"))))
    state, m, ef = ct.step_fn(state, batch, ef)  # warm: compile
    jax.block_until_ready(m["loss"])
    best_dt = float("inf")  # best-of-trials: CPU-steal noise rejection
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            state, m, ef = ct.step_fn(state, batch, ef)
        jax.block_until_ready(m["loss"])
        best_dt = min(best_dt, (time.perf_counter() - t0) / iters)
    results["fused_grad_sync_steps_per_s"] = 1.0 / best_dt
    print(json.dumps({"row": "fused_grad_sync_steps_per_s",
                      "value": round(results["fused_grad_sync_steps_per_s"],
                                     2), "dt_s": round(best_dt, 4)}))

    # staged chain at matched topology: grad program -> EF sync program
    topo = mesh_lib.hier_topology(mesh)
    dp_spec = P(mesh_lib.DP_SUB_AXES)
    n_el = rows_n * cols
    w_rep = jax.device_put(jnp.zeros((rows_n, cols), jnp.float32),
                           NamedSharding(mesh, P()))

    def _local_grad(w, b):
        l, g = jax.value_and_grad(_loss)({"w": w}, b)
        return g["w"].reshape(1, -1), l[None]

    grad_fn = jax.jit(shard_map(_local_grad, mesh=mesh,
                                in_specs=(P(), dp_spec),
                                out_specs=(dp_spec, dp_spec),
                                check_vma=False))
    stage_sync = jax.jit(shard_map(
        hier_allreduce_ef_program(topo, quant_ef2), mesh=mesh,
        in_specs=(dp_spec, dp_spec), out_specs=(dp_spec, dp_spec),
        check_vma=False))
    s_res = jax.device_put(jnp.zeros((4, n_el // 2), jnp.float32),
                           NamedSharding(mesh, dp_spec))

    def staged_once():
        g, _l = grad_fn(w_rep, batch)
        s, _r = stage_sync(g, s_res)
        return s

    jax.block_until_ready(staged_once())  # warm
    st2 = ct.init_fn(jax.random.key(1))
    jax.block_until_ready(ct.sync_fn(st2, batch)[0])  # warm fused sync
    fused_dt = staged_dt = float("inf")
    for _ in range(3):  # interleaved: both sides see the same CPU steal
        t0 = time.perf_counter()
        for _ in range(iters):
            out = ct.sync_fn(st2, batch)
        jax.block_until_ready(out[0])
        fused_dt = min(fused_dt, (time.perf_counter() - t0) / iters)
        t0 = time.perf_counter()
        for _ in range(iters):
            s = staged_once()
        jax.block_until_ready(s)
        staged_dt = min(staged_dt, (time.perf_counter() - t0) / iters)
    results["fused_vs_staged_sync_x"] = staged_dt / fused_dt
    print(json.dumps({"row": "fused_vs_staged_sync_x",
                      "value": round(results["fused_vs_staged_sync_x"], 3),
                      "fused_dt_s": round(fused_dt, 4),
                      "staged_dt_s": round(staged_dt, 4)}))

    report = {
        "metrics": {k: round(v, 2) for k, v in results.items()},
        "unit": "*_mb_s: MB/s, *_per_s: steps/s (all higher is better)",
        "host": {"cpus": os.cpu_count(), "payload_mb": payload_mb},
        "reference": {
            "topology": "emulated 2 hosts x 2 local devices: member "
                        "processes are hosts (slow gloo edge = DCN), "
                        "their virtual CPU devices the fast local fabric",
            "acceptance": "hier_allreduce_mb_s > allreduce_mb_s and "
                          "quant_allreduce_mb_s >= 1.5x allreduce_mb_s "
                          "at matched payload; fused_grad_sync_steps_per_s "
                          ">= grad_sync_steps_per_s (the in-program "
                          "schedule must not lose to the staged one)",
            "fused_grad_sync_steps_per_s":
                "train.spmd.compile_train fused step on the in-process "
                "(dp_inter, dp_intra) hierarchical mesh: fwd+bwd, "
                "RS(intra)/int8-EF-AR(inter)/AG(intra), optimizer apply "
                "— one XLA program per step, zero host round trips",
            "reshard_large_mb_s":
                "collective.reshard_streaming of a 64 MB host leaf "
                "through an 8 MB chunk budget (max_in_flight=2): the "
                "bounded-host-memory restore path at full pipeline rate",
            "fused_vs_staged_sync_x":
                "dt(staged grad+EF-sync dispatch chain) / dt(fused "
                "one-program grad+EF-sync), interleaved best-of-trials "
                "at matched in-process topology — >= 1.0 is the "
                "'fusion never loses to staging' acceptance gate",
        },
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1)
    return report


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["processes", "mesh", "suite"],
                   default="processes")
    p.add_argument("--world", type=int, default=4)
    p.add_argument("--sizes-mb", type=str, default="1,8,64")
    p.add_argument("--op", type=str,
                   default="allreduce,reducescatter,allgather,broadcast")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--out", type=str, default=None)
    args = p.parse_args()

    if args.mode == "suite":
        collective_suite(args.out)
        return
    sizes = [int(float(s) * (1 << 20)) for s in args.sizes_mb.split(",")]
    ops = args.op.split(",")
    if args.mode == "mesh":
        rows = bench_mesh(args.world, sizes, ops, args.iters)
    else:
        rows = bench_processes(args.world, sizes, ops, args.iters)
    for r in rows:
        print(json.dumps(r))
    big_ar = [r for r in rows if r["op"] == "allreduce"]
    summary = {
        "metric": "allreduce_bus_bw_gb_s",
        "value": max((r["bus_bw_gb_s"] for r in big_ar), default=0.0),
        "unit": "GB/s",
        "world": args.world,
        "mode": args.mode,
        "host_cpus": os.cpu_count(),
        "rows": rows,
    }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
