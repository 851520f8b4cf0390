"""The replica class the GPT-2 serving cells deploy (`families/gpt2.py`'s
`build_app`). A module of its own so that it is pickled by name and
imported inside the replica's worker."""

from __future__ import annotations

from families.gpt2 import seeded_params
from harness.replica_probe import ReplicaProbe
from ray_tpu.serve.llm import OpenAIServer


class BenchServer(ReplicaProbe, OpenAIServer):
    """The program's `OpenAIServer` with two additions and one
    substitution, none of which touches a request's path: the harness's
    probe (profiler start/stop, a count of compilations, the engine
    programs' compiled sizes), and the seed's weights made on the device
    in one jitted call and handed over through the engine's own
    `params_override` (the engine's default path makes them leaf by leaf,
    un-jitted; PR 21 measured 40-50 s for that at 1.5B). Same function,
    same key, same values."""

    def __init__(self, *, preset, model_overrides, max_seq_len, seed,
                 **kwargs):
        import jax

        from ray_tpu.models import gpt2

        self.probe_init()
        cfg = gpt2.GPT2Config.preset(
            preset, **{**model_overrides, "max_seq_len": max_seq_len})
        params = seeded_params(cfg, seed)
        jax.block_until_ready(params)
        self.probe_mark("weights")
        super().__init__(
            preset=preset, model_overrides=model_overrides,
            max_seq_len=max_seq_len, params_override=params,
            cfg_override=cfg, weights_id=f"{preset}@seed{seed}", **kwargs)
        self.probe_mark("engine")

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
