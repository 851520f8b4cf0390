"""Platform helpers: where compiled programs are cached, what the devices
report, and the virtual multi-device CPU backend the tests run on.

The reference tests distributed logic on one machine with fake resources
(SURVEY.md §4.2); our analog is an N-device virtual CPU mesh.
"""

from __future__ import annotations

import os
import sys
from typing import List


def ensure_virtual_cpu(n_devices: int) -> None:
    """Make `jax.devices()` return >= n_devices CPU devices, resetting the
    already-initialized backend if necessary. Call before creating any arrays
    (live buffers on a cleared backend become invalid)."""
    import jax
    import jax.extend.backend
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        if jax.devices()[0].platform == "cpu" and len(jax.devices()) >= n_devices:
            return
        jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", max(n_devices, 1))
    got = len(jax.devices())
    if got < n_devices:
        raise RuntimeError(
            f"could not create {n_devices} virtual CPU devices (got {got})")


def compile_cache_dir() -> str:
    """Where this installation keeps JAX's persistent compilation cache:
    `JAX_COMPILATION_CACHE_DIR` verbatim when the environment sets it, else
    `.jax_cache` beside the package (the checkout's root). The path is part
    of the cache key, so it is never derived from a temp name, pid, session
    or time: every process of every run agrees on it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point this process — and, through the environment, every child it
    starts — at `compile_cache_dir()`. Called at start-up by every process
    that compiles (workers before user code, chip_smoke.py's phases);
    imports no JAX itself, since JAX reads the variables when it is
    imported.

    The cache's key takes in each program's metadata (operation names with
    their `jax.named_scope`s, source lines). JAX leaves it out by default,
    and an executable found in the cache then carries the names of whichever
    version of the code compiled it first: a device trace of this version
    would show another's scopes, or none (PR 23: a parent commit that ran
    first on a shared cache left the serving programs unnamed). The price is
    a compile after an edit that moves the model's lines."""
    path = os.environ["JAX_COMPILATION_CACHE_DIR"] = compile_cache_dir()
    os.environ["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] = "true"
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
    return path


def device_report() -> List[dict]:
    """What `jax.devices()` is in this process, for results that must name
    the device they ran on: id, platform, kind, the memory counters the
    backend keeps (peak and limit in bytes; absent on the CPU), and the chip
    ids libtpu was narrowed to when the scheduler granted this process part
    of a host (`process_chips`; None for a whole host)."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "platform": d.platform,
                    "kind": d.device_kind,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                    "process_chips": os.environ.get("TPU_VISIBLE_CHIPS")})
    return out


# Root for all on-disk runtime state (job logs, runtime_env extractions,
# spill files, CLI address file). Deliberately NOT "/tmp/ray_tpu": a dir
# named like the package becomes an importable namespace package that
# shadows the real library for any script run from /tmp.
STATE_DIR = "/tmp/ray_tpu_state"
