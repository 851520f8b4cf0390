"""The device's selection of the next token (`serve/sampling.py`) against
the plain numpy definition the engine used to run on the host: the same
kept set, the same distribution, the first index of the maximum for a
greedy row. CPU, a small vocabulary."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.sampling import kept_tokens, select_tokens

V = 32
DRAWS = 20_000


def reference_probs(logit_row, temperature, top_k, top_p):
    """What `LLMEngine._sample` drew from until PR 28, line for line up to
    the draw: the probabilities of one sampling row, 0 where a token is
    cut."""
    lg = logit_row / temperature
    if top_k and top_k < len(lg):
        kth = np.partition(lg, -top_k)[-top_k]
        lg = np.where(lg < kth, -np.inf, lg)
    p = np.exp(lg - lg.max())
    p /= p.sum()
    if top_p < 1.0:
        order = np.argsort(p)[::-1]
        # standard nucleus: smallest set whose mass reaches top_p — keep a
        # token if the mass BEFORE it is still short of the threshold
        # (inclusive of the one that crosses it)
        csum = np.cumsum(p[order])
        keep = (csum - p[order]) < top_p
        mask = np.zeros_like(p, bool)
        mask[order[keep]] = True
        p = np.where(mask, p, 0.0)
        p /= p.sum()
    return p


def _row(seed=0):
    return np.random.default_rng(seed).normal(0, 2, V).astype(np.float32)


def _tied_row():
    # the third, fourth and fifth largest are one value
    row = np.linspace(-3, 0, V).astype(np.float32)
    row[[4, 9, 20, 21, 30]] = [5.0, 4.0, 3.0, 3.0, 3.0]
    return row


CASES = {
    "temperature-only": (_row(1), 1.0, 0, 1.0),
    "cold": (_row(2), 0.3, 0, 1.0),
    "top-k": (_row(3), 0.7, 5, 1.0),
    "top-k-ties-at-the-kth": (_tied_row(), 1.0, 3, 1.0),
    "top-k-wider-than-the-vocabulary": (_row(4), 1.0, V + 8, 1.0),
    "nucleus": (_row(5), 1.0, 0, 0.9),
    "nucleus-chat": (_row(6), 0.7, 0, 0.95),
    "nucleus-of-one": (_row(7), 1.0, 0, 0.01),
    "top-k-then-nucleus": (_row(8), 1.3, 8, 0.6),
}


@pytest.mark.parametrize("case", CASES)
def test_the_device_draws_from_the_references_distribution(case):
    row, temperature, top_k, top_p = CASES[case]
    p = reference_probs(row, temperature, top_k, top_p)
    kept = np.asarray(kept_tokens(
        jnp.asarray(row / np.float32(temperature))[None],
        jnp.asarray([top_k], jnp.int32), jnp.asarray([top_p], jnp.float32)))
    assert kept.shape == (1, V)
    assert set(np.flatnonzero(kept[0])) == set(np.flatnonzero(p > 0))
    if case == "top-k-ties-at-the-kth":
        assert set(np.flatnonzero(p > 0)) == {4, 9, 20, 21, 30}
    if case == "nucleus-of-one":
        assert np.flatnonzero(p > 0).tolist() == [int(np.argmax(row))]

    # one draw a slot: DRAWS slots of the same row
    ids = np.asarray(jax.jit(select_tokens)(
        jnp.tile(jnp.asarray(row)[None], (DRAWS, 1)),
        jnp.full((DRAWS,), -1, jnp.int32), jnp.ones((DRAWS,), bool),
        jnp.full((DRAWS,), temperature, jnp.float32),
        jnp.full((DRAWS,), top_k, jnp.int32),
        jnp.full((DRAWS,), top_p, jnp.float32), jax.random.key(11)))
    freq = np.bincount(ids, minlength=V) / DRAWS
    assert not freq[p == 0].any()            # nothing outside the kept set
    # four standard deviations of a binomial share, and a little for
    # float32's softmax: a correct sampler leaves it once in thousands
    band = 4 * np.sqrt(p * (1 - p) / DRAWS) + 1e-3
    assert (np.abs(freq - p) <= band).all(), (freq, p)


def _select(logits, produce, temperature, top_k=None, top_p=None, prev=None,
            key=0):
    B = len(logits)
    return np.asarray(jax.jit(select_tokens)(
        jnp.asarray(logits),
        jnp.asarray(np.full(B, -1) if prev is None else prev, jnp.int32),
        jnp.asarray(produce), jnp.asarray(temperature, jnp.float32),
        jnp.asarray(np.zeros(B) if top_k is None else top_k, jnp.int32),
        jnp.asarray(np.ones(B) if top_p is None else top_p, jnp.float32),
        jax.random.key(key)))


def test_a_greedy_row_beside_sampling_rows_is_its_argmax():
    logits = np.stack([_row(s) for s in range(4)])
    logits[0, [7, 19]] = logits[0].max() + 1      # a tie: the first wins
    logits[2, [25, 3]] = logits[2].max() + 1
    ids = _select(logits, np.ones(4, bool), [0.0, 0.7, 0.0, 1.0],
                  top_p=[1.0, 0.9, 1.0, 1.0])
    assert ids[0] == np.argmax(logits[0]) == 7
    assert ids[2] == np.argmax(logits[2]) == 3
    # the rows that sample do sample: over many keys, more than one token
    drawn = {int(_select(logits, np.ones(4, bool), [0.0, 0.7, 0.0, 1.0],
                         key=k)[3]) for k in range(40)}
    assert len(drawn) > 1


def test_a_greedy_batch_is_numpys_argmax_and_idle_lanes_keep_their_token():
    logits = np.stack([_row(s) for s in range(6)])
    logits[1, [2, 11, 30]] = 9.0
    produce = np.array([True, True, False, True, False, True])
    prev = np.arange(100, 106)
    # a lane that does not produce may hold a sampling request's leftovers
    ids = _select(logits, produce, [0.0, 0.0, 0.7, 0.0, 0.0, 0.0], prev=prev)
    want = np.where(produce, logits.argmax(-1), prev)
    assert ids.tolist() == want.tolist() and ids[1] == 2
    assert ids.dtype == np.int32


def test_the_sort_is_under_a_cond_a_greedy_batch_does_not_take():
    """One program whatever the batch holds: the sort sits in a branch of
    a conditional on what the program sees in its input."""
    B = 4
    text = jax.jit(select_tokens).lower(
        jax.ShapeDtypeStruct((B, V), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), bool),
        jax.ShapeDtypeStruct((B,), jnp.float32),
        jax.ShapeDtypeStruct((B,), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.float32),
        jax.random.key(0)).as_text()
    head, _, branches = text.partition("stablehlo.case")
    assert branches and "stablehlo.sort" in branches
    assert "stablehlo.sort" not in head


def test_the_same_seed_requests_and_order_give_the_same_sampled_replies():
    from ray_tpu.serve.llm import LLMEngine

    def serve(seed):
        eng = LLMEngine(preset="gpt2-tiny", max_batch=2, max_seq_len=96,
                        seed=seed, prefill_chunk_size=16, kv_block_size=8)
        try:
            return [eng.generate(prompt_ids=list(range(3 + i, 30 + 2 * i)),
                                 max_tokens=10, temperature=0.9, top_k=k,
                                 top_p=p)["token_ids"]
                    for i, (k, p) in enumerate([(0, 0.95), (20, 1.0),
                                                (0, 1.0)])]
        finally:
            eng.shutdown()

    first, again = serve(3), serve(3)
    assert first == again
    assert all(len(r) == 10 for r in first)
