"""A pretraining job's token stream: Zipf unigrams over the published
vocabulary (so the loss has somewhere to fall: from ln V towards the
unigram entropy), `dataset_batches` global batches of `seq_len + 1` tokens,
all from the seed. The loop reads them through the trainer's ingest path
and starts a new epoch if it outruns them."""

from __future__ import annotations

import numpy as np


def generate(traffic: dict, config: dict, seed: int,
             seconds: float = 0.0) -> np.ndarray:
    rng = np.random.default_rng([seed, 0x7EA1])
    vocab = config["model"]["vocab_size"]
    job = config["job"]
    rows = traffic["dataset_batches"] * job["global_batch"]
    weights = 1.0 / np.arange(1, vocab + 1) ** traffic["zipf_s"]
    edges = np.cumsum(weights / weights.sum())
    ranks = np.searchsorted(edges, rng.random((rows, job["seq_len"] + 1)))
    ids = rng.permutation(vocab)            # which id has which rank
    return ids[np.minimum(ranks, vocab - 1)].astype(np.int32)
