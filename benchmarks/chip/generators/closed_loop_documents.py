"""Closed-loop clients asking short, distinct questions about a handful of
long documents: each request is one of `documents` seeded documents (whole
blocks of the prefix pool, so a pooled document is found again to its last
token) followed by a question of its own, and each client sends its next
request when its last one has ended, with no think time. Every client has
its own list of requests, long enough to outlast the window.

Two seeds, as in `open_loop_sessions`. The schedule's shape (how long each
document, question and answer is, and which document a request asks about)
is the mix's own and comes from the traffic file's `schedule_seed`: every
request costs one step of the chunk program, eight decode steps' time, so
how many requests begin inside a window decides its tokens per second, and
from one drawn schedule to the next that number moves them by 1.7% (PERF.md,
PR 29), more than the bound allows and more than anything in the program
does. Every token of every document and question comes from `--seed`.

The warm-up sends each document once, one request at a time, so that all of
them are pooled before the ramp, and the first once more, so that the copy
out of the pool is prepared too."""

from __future__ import annotations

import numpy as np

from . import draws


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    shape = np.random.default_rng([traffic["schedule_seed"], 0xD0C5])
    rng = np.random.default_rng([seed, 0xD0C5])
    vocab = config["model"]["vocab_size"]
    clients, each = traffic["clients"], traffic["requests_per_client"]
    n = clients * each
    block = traffic["document_block"]
    lo, hi = traffic["document_uniform"]
    document_blocks = draws.uniform_lengths(
        shape, traffic["documents"], lo // block, hi // block)
    of = [int(x * len(document_blocks))
          for x in draws.stratified_uniform(shape, n)]
    question_lens = draws.uniform_lengths(shape, n,
                                          *traffic["question_uniform"])
    out_lens = draws.uniform_lengths(shape, n, *traffic["output_uniform"])
    documents = [draws.tokens(rng, blocks * block, vocab)
                 for blocks in document_blocks]

    def ask(document: int, question_len: int) -> list:
        return documents[document] + draws.tokens(rng, question_len, vocab)

    requests = [{"id": i, "client": i % clients, "document": of[i],
                 "prompt_ids": ask(of[i], question_lens[i]),
                 "max_tokens": out_lens[i], "temperature": 0.0,
                 "top_p": 1.0} for i in range(n)]
    warmup = [{"id": f"warm{k}", "max_tokens": 2, "temperature": 0.0,
               "top_p": 1.0,
               "prompt_ids": ask(d, traffic["question_uniform"][1])}
              for k, d in enumerate(list(range(len(documents))) + [0])]
    return {"loop": "closed", "warmup": warmup, "requests": requests,
            "clients": clients, "ramp_s": traffic["ramp_s"],
            "tail_s": 0.0, "drain_s": 0.0}
