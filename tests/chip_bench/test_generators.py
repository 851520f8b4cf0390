"""The traffic generators: everything from the seed, the stated clips and
shares hold."""

import json
import os

import numpy as np
import pytest

from conftest import CHIP_DIR
from generators import closed_loop, draws, open_loop_sessions, train_job

CONFIG = {"model": {"vocab_size": 50257},
          "deployment": {"max_seq_len": 1024},
          "job": {"global_batch": 4, "seq_len": 64}}
GENERATORS = {"chat-sessions": open_loop_sessions, "batch-decode": closed_loop}


def traffic(name):
    with open(os.path.join(CHIP_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", sorted(GENERATORS))
def test_same_seed_same_requests(mix):
    gen = GENERATORS[mix]
    a = gen.generate(traffic(mix), CONFIG, 7, 51)
    b = gen.generate(traffic(mix), CONFIG, 7, 51)
    assert a == b


@pytest.mark.parametrize("mix", sorted(GENERATORS))
def test_other_seed_other_requests(mix):
    gen = GENERATORS[mix]
    a = gen.generate(traffic(mix), CONFIG, 7, 51)
    b = gen.generate(traffic(mix), CONFIG, 8, 51)
    assert [r["prompt_ids"] for r in a["requests"]] != \
        [r["prompt_ids"] for r in b["requests"]]


def test_chat_schedule_is_the_mixes_and_the_tokens_are_the_seeds():
    t = traffic("chat-sessions")
    a = open_loop_sessions.generate(t, CONFIG, 1, 51)["requests"]
    b = open_loop_sessions.generate(t, CONFIG, 2, 51)["requests"]
    shape = lambda reqs: [(r["due_s"], len(r["prompt_ids"]), r["follows"],
                           r["max_tokens"], r["temperature"]) for r in reqs]
    assert shape(a) == shape(b)
    assert all(x["prompt_ids"] != y["prompt_ids"] for x, y in zip(a, b))
    c = open_loop_sessions.generate({**t, "schedule_seed": 23}, CONFIG, 1,
                                    51)["requests"]
    assert shape(c) != shape(a)


@pytest.mark.parametrize("seed,schedule_seed", [(1, 22), (2, 22), (1, 5)])
def test_chat_sessions_keep_their_clips_and_shares(seed, schedule_seed):
    t = {**traffic("chat-sessions"), "schedule_seed": schedule_seed}
    plan = open_loop_sessions.generate(t, CONFIG, seed, 51)
    reqs = plan["requests"]
    p = t["prompt"]
    in_window = [r for r in reqs if 0 <= r["due_s"] < 51]
    # a fixed amount of work: the same count whatever the seed
    assert len(in_window) == round(t["rate_per_s"] * 51)
    assert len([r for r in reqs if r["due_s"] < 0]) == round(
        t["rate_per_s"] * t["ramp_s"])
    assert [r["due_s"] for r in reqs] == sorted(r["due_s"] for r in reqs)
    by_id = {r["id"]: r for r in reqs}
    systems = {tuple(r["prompt_ids"][:p["system_tokens"]]) for r in reqs}
    assert len(systems) <= p["system_prompts"]
    for r in reqs:
        n = len(r["prompt_ids"])
        assert p["system_tokens"] + p["user_clip"][0] <= n \
            <= p["max_prompt_tokens"]
        assert 1 <= r["max_tokens"] <= t["output"]["clip"][1]
        assert n + r["max_tokens"] <= 1023
        assert all(0 <= tok < 50257 for tok in r["prompt_ids"])
        if r["follows"] is not None:
            parent = by_id[r["follows"]]
            assert parent["due_s"] <= r["due_s"] - p["followup_min_gap_s"]
            assert r["prompt_ids"][:len(parent["prompt_ids"])] == \
                parent["prompt_ids"]
            assert n >= len(parent["prompt_ids"]) + p["answer_tokens"] \
                + p["user_clip"][0]
    follow = sum(r["follows"] is not None for r in reqs) / len(reqs)
    assert 0.15 <= follow <= p["followup_share"] + 0.02
    greedy = [r for r in reqs if r["temperature"] == 0.0]
    assert len(greedy) == len(reqs) // t["greedy_every"]
    assert all(r["top_p"] == t["top_p"] and r["temperature"]
               == t["temperature"] for r in reqs if r not in greedy)
    # the warm-up sends each system prompt once, and one again (a hit)
    assert len(plan["warmup"]) == p["system_prompts"] + 1


@pytest.mark.parametrize("seed", [1, 2])
def test_batch_decode_keeps_its_clips(seed):
    t = traffic("batch-decode")
    plan = closed_loop.generate(t, CONFIG, seed, 51)
    reqs = plan["requests"]
    assert plan["clients"] == 16 and plan["loop"] == "closed"
    assert len(reqs) == 16 * t["requests_per_client"]
    assert {r["client"] for r in reqs} == set(range(16))
    lo, hi = t["prompt_uniform"]
    assert all(lo <= len(r["prompt_ids"]) <= hi for r in reqs)
    lo, hi = t["output_uniform"]
    assert all(lo <= r["max_tokens"] <= hi for r in reqs)
    assert all(r["temperature"] == 0.0 for r in reqs)
    assert len({tuple(r["prompt_ids"]) for r in reqs}) == len(reqs)


def test_train_job_is_seeded_zipf():
    t = traffic("pretrain-1k")
    a = train_job.generate(t, CONFIG, 5)
    b = train_job.generate(t, CONFIG, 5)
    c = train_job.generate(t, CONFIG, 6)
    assert a.dtype == np.int32
    assert a.shape == (t["dataset_batches"] * 4, 65)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 50257
    # Zipf: the commonest id takes about 1/H(V) ~ 8.7% of the tokens
    top = np.bincount(a.ravel()).max() / a.size
    assert 0.07 < top < 0.11


@pytest.mark.parametrize("n", [1, 7, 40])
def test_stratified_draws_cover_every_slice(n):
    rng = np.random.default_rng(0)
    u = draws.stratified_uniform(rng, n)
    assert sorted(int(x * n) for x in u) == list(range(n))
    lens = draws.lognormal_lengths(rng, n, 96, 0.9, 16, 640)
    assert all(16 <= x <= 640 for x in lens)
    picks = draws.zipf_choices(rng, n, 4, 1.0)
    assert all(0 <= k < 4 for k in picks)
