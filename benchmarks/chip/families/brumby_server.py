"""The replica class the Brumby serving cell deploys (`families/brumby.py`'s
`build_app`). A module of its own so that it is pickled by name and
imported inside the replica's worker."""

from __future__ import annotations

from harness.replica_probe import ReplicaProbe
from ray_tpu.serve.llm import OpenAIServer


class BenchServer(ReplicaProbe, OpenAIServer):
    """The program's `OpenAIServer` with the harness's probe (profiler
    start/stop, a count of compilations, the engine programs' compiled
    sizes) and the family's unit costs beside the engine's counters in
    `stats()`; neither touches a request's path. The weights are the
    engine's own default: `brumby.init_params` from `seed`, on the
    device, a layer at a time, in the dtype they are held in."""

    def __init__(self, *, roofline_costs, **kwargs):
        import jax

        self.probe_init()
        self._roofline_costs = roofline_costs
        super().__init__(**kwargs)
        jax.block_until_ready(self.engine.params)
        self.probe_mark("weights")
        self.probe_mark("engine")

    def stats(self) -> dict:
        return {**super().stats(), "roofline_costs": self._roofline_costs}

    def engine_programs(self) -> dict:
        """name -> (jitted program, example arguments) of the two programs
        the engine loop runs, for the probe to size."""
        import numpy as np

        eng = self.engine
        b, c = eng.max_batch, eng.prefill_chunk_size
        ints = np.zeros((b,), np.int32)
        on = np.zeros((b,), bool)
        return {
            "decode": (eng._step, (eng.params, eng.cache, ints, ints, on)),
            "prefill": (eng._chunk_step,
                        (eng.params, eng.cache, np.zeros((b, c), np.int32),
                         ints, ints, on)),
        }
