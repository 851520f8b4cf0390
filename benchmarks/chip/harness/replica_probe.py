"""What the benchmark adds to a serving replica, and only that.

Only the process that holds the chip can trace it, count its compilations
or ask its compiler how large a program is. A family's server class mixes
this in; the methods are reached through the replica's `handle_request`,
as the program's own `stats` is. The same class is deployed with
`--trace 0` and `--trace 1`, so both runs execute the same code.
"""

from __future__ import annotations

import time


class CompileCounter:
    """Counts the programs this process prepares to run, through JAX's own
    monitoring event around `compile_or_get_cached`: it fires once for
    every new shape of every jitted function, whether the executable was
    compiled or read from the persistent cache. A count that rises inside
    the measured window means a shape was not warmed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


def start_trace(directory: str) -> None:
    """A device trace with the host's own events beside it, so that an
    idle gap can be laid to what the host was doing."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 2
    options.python_tracer_level = 1
    jax.profiler.start_trace(directory, profiler_options=options)


def stop_trace() -> None:
    import jax

    jax.profiler.stop_trace()


def memory_bytes(compiled) -> dict:
    """`memory_analysis()` of a compiled program, per device; `total` is
    what PR 21 sized a step by: temporaries + arguments + outputs less
    what the outputs alias."""
    m = compiled.memory_analysis()
    out = {"temp": m.temp_size_in_bytes,
           "arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "aliased": m.alias_size_in_bytes}
    out["total"] = (out["temp"] + out["arguments"] + out["outputs"]
                    - out["aliased"])
    return out


def program_bytes(jitted, example_args) -> dict:
    """`memory_bytes` of a jitted program for arguments shaped like these.
    The compile hits JAX's caches when the program has already run."""
    import jax
    import numpy as np

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), example_args)
    return memory_bytes(jitted.lower(*shapes).compile())


class ReplicaProbe:
    def probe_init(self) -> None:
        from harness.device import worker_devices

        self._probe_marks = {"start": time.time()}
        self._probe_compiles = CompileCounter()
        worker_devices()                  # the first touch of the chip
        self.probe_mark("chip")

    def probe_mark(self, name: str) -> None:
        self._probe_marks[name] = time.time()

    def probe(self) -> dict:
        from harness.device import worker_devices

        return {"marks": dict(self._probe_marks),
                "compiles": self._probe_compiles.count,
                "devices": worker_devices(), "time": time.time()}

    def probe_programs(self) -> dict:
        """Compiled sizes of the programs the family's server names in
        `engine_programs()`; a program that cannot be sized is left out."""
        out = {}
        for name, (jitted, args) in self.engine_programs().items():
            try:
                out[name] = program_bytes(jitted, args)
            except Exception as e:  # noqa: BLE001 - a reader finds nothing
                out[name] = {"error": repr(e)[:300]}
        return out

    def profile_start(self, directory: str) -> float:
        start_trace(directory)
        return time.time()

    def profile_stop(self) -> float:
        stop_trace()
        return time.time()
