"""Open-loop chat sessions: requests arrive on a schedule fixed by the
traffic file's rate, whatever the server does.

Arrivals: `round(rate * length)` requests over each of the ramp, the
window and the tail, at sorted uniform times (a Poisson process given its
count). A request's prompt is one of a few system prompts plus a user
part; a follow-up resends an earlier request's whole prompt, tokens
standing for the answer it got, and a new user part. `due_s` is relative
to the window's opening; only requests with 0 <= due_s < seconds are
measured.

Two seeds. The schedule's shape (when each request is due, every length,
which system prompt, who follows whom, which requests are greedy) is the
mix's own and comes from the traffic file's `schedule_seed`: a window
below the knee holds two dozen requests, and medians over two dozen differ
by 12-14% from one drawn schedule to the next (PERF.md, PR 22), more than
any bound may be. Every token of every prompt comes from `--seed`, as the
weights do. Another schedule is another mix: a data file.
"""

from __future__ import annotations

import numpy as np

from . import draws


def generate(traffic: dict, config: dict, seed: int, seconds: float) -> dict:
    rng = np.random.default_rng([traffic["schedule_seed"], 0xC4A7])
    words = np.random.default_rng([seed, 0xC4A7])      # the tokens
    vocab = config["model"]["vocab_size"]
    window = config["deployment"]["max_seq_len"]
    p, out = traffic["prompt"], traffic["output"]
    systems = [draws.tokens(words, p["system_tokens"], vocab)
               for _ in range(p["system_prompts"])]

    spans = [(-traffic["ramp_s"], 0.0), (0.0, seconds),
             (seconds, seconds + traffic["tail_s"])]
    due = []
    for lo, hi in spans:
        n = int(round(traffic["rate_per_s"] * (hi - lo)))
        due += sorted((lo + rng.random(n) * (hi - lo)).tolist())
    n = len(due)
    user_lens = draws.lognormal_lengths(rng, n, *p["user_lognormal"],
                                        *p["user_clip"])
    out_lens = draws.lognormal_lengths(rng, n, *out["lognormal"],
                                       *out["clip"])
    which_system = draws.zipf_choices(rng, n, len(systems),
                                      p["system_zipf_s"])
    wants_follow = draws.stratified_uniform(rng, n) < p["followup_share"]

    requests = []
    for i in range(n):
        user = draws.tokens(words, user_lens[i], vocab)
        parent = None
        if wants_follow[i]:
            earlier = [r for r in requests
                       if r["due_s"] <= due[i] - p["followup_min_gap_s"]
                       and len(r["prompt_ids"]) + p["answer_tokens"]
                       + len(user) <= p["max_prompt_tokens"]]
            if earlier:
                parent = earlier[int(rng.integers(len(earlier)))]
        if parent is not None:
            prompt = (parent["prompt_ids"]
                      + draws.tokens(words, p["answer_tokens"], vocab) + user)
        else:
            prompt = (systems[which_system[i]] + user)[:p["max_prompt_tokens"]]
        greedy = i % traffic["greedy_every"] == traffic["greedy_every"] - 1
        requests.append({
            "id": i, "due_s": due[i], "prompt_ids": prompt,
            "follows": parent["id"] if parent is not None else None,
            "max_tokens": min(out_lens[i], window - 1 - len(prompt)),
            "temperature": 0.0 if greedy else traffic["temperature"],
            "top_p": 1.0 if greedy else traffic["top_p"]})
    warmup = [{"id": f"warm{k}", "prompt_ids": s, "max_tokens": 2,
               "temperature": 0.0, "top_p": 1.0}
              for k, s in enumerate(systems + systems[:1])]
    return {"loop": "open", "warmup": warmup, "requests": requests,
            "ramp_s": traffic["ramp_s"], "tail_s": traffic["tail_s"],
            "drain_s": traffic["drain_s"]}
