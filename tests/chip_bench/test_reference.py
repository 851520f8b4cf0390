"""The family's plain reference against the program at a tiny size, and
the family's other pieces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from families import gpt2 as family
from ray_tpu.models import gpt2

MODEL = {"vocab_size": 500, "padded_vocab_size": 512, "n_positions": 128,
         "n_embd": 128, "n_layer": 2, "n_head": 4, "n_inner": None}


@pytest.fixture(scope="module")
def f32():
    cfg = family.program_config(MODEL, dtype=jnp.float32, remat=False,
                                attn_impl="dense")
    params = gpt2.init_params(jax.random.key(3), cfg)
    tokens = jax.random.randint(jax.random.key(4), (3, 65), 0, 500)
    return cfg, params, tokens


def test_reference_logits_agree_with_the_program_in_f32(f32):
    cfg, params, tokens = f32
    with jax.default_matmul_precision("highest"):
        got = gpt2.forward(params, tokens[:, :-1], cfg)
    want = family.reference_logits(params, tokens[:, :-1], MODEL["n_head"])
    # both float32: only the order of summation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_reference_loss_agrees_with_the_program_in_f32(f32):
    cfg, params, tokens = f32
    with jax.default_matmul_precision("highest"):
        got = gpt2.loss_fn(params, {"tokens": tokens}, cfg)
    want = family.reference_loss(params, tokens, MODEL["n_head"])
    assert float(got) == pytest.approx(float(want), abs=1e-5)


def test_bf16_program_is_inside_the_stated_tolerance_and_a_wrong_one_not(f32):
    _, params, tokens = f32
    cfg = family.program_config(MODEL, attn_impl="dense")       # bf16
    want = float(family.reference_loss(params, tokens, MODEL["n_head"]))
    got = float(gpt2.loss_fn(params, {"tokens": tokens}, cfg))
    assert abs(got - want) <= family.TRAIN_LOSS_TOLERANCE
    # a model that skips a layer is outside it
    fewer = jax.tree.map(lambda a: a[:1], params["blocks"])
    wrong = float(family.reference_loss({**params, "blocks": fewer}, tokens,
                                        MODEL["n_head"]))
    scaled = {**params, "wte": params["wte"] * 4.0}
    wrong2 = float(family.reference_loss(scaled, tokens, MODEL["n_head"]))
    assert max(abs(wrong - want), abs(wrong2 - want)) > \
        family.TRAIN_LOSS_TOLERANCE


def test_weights_under_one_jit_are_the_engines_weights():
    cfg = family.program_config(MODEL)
    eager = gpt2.init_params(jax.random.key(11), cfg)
    jitted = jax.jit(lambda k: gpt2.init_params(k, cfg))(jax.random.key(11))
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_char_tokenizer_is_one_character_a_token_both_ways():
    tok = family.CharTokenizer()
    ids = [0, 1, 255, 256, 50256, 50303]
    text = tok.decode(ids)
    assert len(text) == len(ids)
    assert tok.encode(text) == ids
    assert text.encode("utf-8").decode("utf-8") == text
    import json
    assert json.loads(json.dumps({"t": text}))["t"] == text


@pytest.mark.parametrize("model,want", [
    # 6 * (12 * 12 * 768^2 + 50257 * 768) + 12 * 12 * 768 * 1024
    ({"n_embd": 768, "n_layer": 12, "vocab_size": 50257},
     6 * (12 * 12 * 768 ** 2 + 50257 * 768) + 12 * 12 * 768 * 1024),
    ({"n_embd": 1600, "n_layer": 48, "vocab_size": 50257},
     6 * (12 * 48 * 1600 ** 2 + 50257 * 1600) + 12 * 48 * 1600 * 1024)])
def test_train_flops_per_token(model, want):
    assert family.train_flops_per_token(model, 1024) == want


def test_check_served_passes_the_argmax_and_fails_another_token():
    config = {"model": MODEL, "deployment": {"preset": "gpt2-tiny",
                                             "max_seq_len": 128}}
    cfg = gpt2.GPT2Config.preset(
        "gpt2-tiny", vocab_size=512, n_layer=2, n_head=4, d_model=128,
        d_ff=512, max_seq_len=128)
    params = gpt2.init_params(jax.random.key(9), cfg)
    prompt = [5, 6, 7, 8]
    row = list(prompt)
    for _ in range(3):                       # greedy under the reference
        logits = family.reference_logits(
            params, jnp.asarray([row]), MODEL["n_head"])
        row.append(int(jnp.argmax(logits[0, -1])))
    good = [{"prompt_ids": prompt, "token_ids": row[4:]}]
    assert family.check_served(config, 9, good)["ok"]
    bad = [{"prompt_ids": prompt, "token_ids": [row[4], (row[5] + 1) % 500,
                                                row[6]]}]
    assert not family.check_served(config, 9, bad)["ok"]
