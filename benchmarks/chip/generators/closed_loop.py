"""Closed-loop clients: each sends its next request when the last one has
ended, with no think time. Every client has its own seeded list of
requests, long enough to outlast the window; prompts are all distinct."""

from __future__ import annotations

import numpy as np

from . import draws


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng([seed, 0xC105])
    vocab = config["model"]["vocab_size"]
    clients, each = traffic["clients"], traffic["requests_per_client"]
    n = clients * each
    prompt_lens = draws.uniform_lengths(rng, n, *traffic["prompt_uniform"])
    out_lens = draws.uniform_lengths(rng, n, *traffic["output_uniform"])
    requests = [{"id": i, "client": i % clients,
                 "prompt_ids": draws.tokens(rng, prompt_lens[i], vocab),
                 "max_tokens": out_lens[i], "temperature": 0.0,
                 "top_p": 1.0} for i in range(n)]
    warmup = [{"id": "warm0", "max_tokens": 2, "temperature": 0.0,
               "top_p": 1.0, "prompt_ids": draws.tokens(
                   rng, traffic["prompt_uniform"][1], vocab)}]
    return {"loop": "closed", "warmup": warmup, "requests": requests,
            "clients": clients, "ramp_s": traffic["ramp_s"],
            "tail_s": 0.0, "drain_s": 0.0}
