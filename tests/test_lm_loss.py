"""`lm.chunked_cross_entropy`: the fused unembedding and loss every model
family's `loss_fn` calls, whose backward is made in its forward. Value and
gradients against autodiff of `cross_entropy(x @ head, targets)`, and the
rule that chooses the chunk from what the call can observe."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama, lm, moe
from ray_tpu.parallel.mesh import MeshConfig, build_mesh, use_mesh


def _hidden_and_head(family: str, seq_len: int):
    """(x [2,T,D] after the final norm, head [D,V], targets [2,T]) of a
    tiny model: GPT-2's head is its embedding table transposed (tied),
    llama's and the MoE's a matrix of its own (untied)."""
    rng = np.random.default_rng(0)
    if family == "gpt2":
        mod, cfg = gpt2, gpt2.GPT2Config.preset("gpt2-tiny",
                                                max_seq_len=seq_len)
    elif family == "llama":
        mod, cfg = llama, llama.LlamaConfig.preset(
            "llama-tiny", max_seq_len=seq_len, tie_embeddings=False)
    else:
        mod, cfg = moe, moe.MoEConfig.preset(
            "moe-tiny", max_seq_len=seq_len, tie_embeddings=False)
    params = mod.init_params(jax.random.key(0), cfg)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, seq_len + 1)),
                         jnp.int32)
    x = mod.hidden_states(params, tokens[:, :-1], cfg)
    x = x[0] if family == "moe" else x
    final = llama.final_hidden if family == "moe" else mod.final_hidden
    return (*final(params, x, cfg), tokens[:, 1:])


def _chunks_of(monkeypatch, targets, vocab: int, chunks: int):
    """Set the budget so that the float32 logits make `chunks` chunks."""
    monkeypatch.setattr(lm, "LOGITS_CHUNK_BYTES",
                        targets.size * vocab * 4 // chunks)


def _same_as_plain(x, head, targets, scale, want_chunks):
    """The old `test_chunked_ce_matches_plain`'s tolerances: the value
    within 1e-4, every gradient entry within 1e-3."""
    assert lm.loss_chunks(*targets.shape, head.shape[1])[0] == want_chunks

    def plain(x, head):
        return scale * lm.cross_entropy(x @ head, targets)

    def fused(x, head):
        return scale * lm.chunked_cross_entropy(x, head, targets)

    l0, g0 = jax.value_and_grad(plain, (0, 1))(x, head)
    l1, g1 = jax.jit(jax.value_and_grad(fused, (0, 1)))(x, head)
    assert abs(float(l0) - float(l1)) < 1e-4 * scale
    assert abs(float(fused(x, head)) - float(l0)) < 1e-4 * scale
    for a, b in zip(g0, g1):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)))) < 1e-3
        assert float(jnp.max(jnp.abs(a))) > 0


@pytest.mark.parametrize("family,seq_len,chunks,scale,want_chunks", [
    ("gpt2", 64, 1, 1.0, 1), ("gpt2", 64, 4, 1.0, 4),
    ("llama", 64, 1, 1.0, 1), ("llama", 64, 4, 1.0, 4),
    ("moe", 64, 2, 1.0, 2),
    # the incoming cotangent is honoured, not assumed to be 1
    ("gpt2", 64, 4, 3.0, 4), ("llama", 64, 1, 3.0, 1),
    # 62 = 2 x 31 has no divisor in [4, 8): the sequence stays whole
    ("gpt2", 62, 4, 1.0, 1),
    # 60 has none at 7 but 10 lies in [7, 14)
    ("llama", 60, 7, 1.0, 10),
], ids=["tied-1chunk", "tied-4chunks", "untied-1chunk", "untied-4chunks",
        "moe-untied-2chunks", "tied-4chunks-cotangent3",
        "untied-1chunk-cotangent3", "length-no-chunk-divides",
        "length-next-divisor"])
def test_fused_loss_matches_autodiff_of_plain(monkeypatch, family, seq_len,
                                              chunks, scale, want_chunks):
    x, head, targets = _hidden_and_head(family, seq_len)
    _chunks_of(monkeypatch, targets, head.shape[1], chunks)
    _same_as_plain(x, head, targets, scale, want_chunks)


@pytest.mark.parametrize("axes,chunks", [
    (dict(dp=2, sp=2, tp=2), 1), (dict(dp=2, sp=2, tp=2), 2),
    (dict(fsdp=4, tp=2), 4)], ids=["sp2-1chunk", "sp2-2chunks", "fsdp4-4"])
def test_fused_loss_under_a_mesh(devices8, monkeypatch, axes, chunks):
    """Sharded as a job shards it (the sequence over `sp`, the vocabulary
    over `tp`, the batch over `dp`/`fsdp`): a chunk is a piece of every
    device's part of the sequence, and the budget is a device's."""
    x, head, targets = _hidden_and_head("gpt2", 64)
    monkeypatch.setattr(lm, "LOGITS_CHUNK_BYTES",
                        targets.size * head.shape[1] * 4 // (8 * chunks))
    with use_mesh(build_mesh(MeshConfig(**axes), devices=devices8)):
        _same_as_plain(x, head, targets, 1.0, chunks)


@pytest.mark.parametrize("shape,axes,want", [
    ((20, 1024, 50304), {}, 2),            # train-small-1k: 10,240 tokens
    ((8, 4096, 50304), {}, 4),             # train-olmoe-4k: 8,192 tokens
    ((32, 1024, 50304), dict(fsdp=4), 1),  # train-xl-fsdp4-1k: 8,192 a chip
    ((32, 1024, 50304), {}, 4),            # the same batch on one device
    ((2, 64, 512), {}, 1)], ids=["small-1k", "olmoe-4k", "xl-fsdp4",
                                 "xl-one-device", "tiny"])
def test_chunk_is_read_from_the_device_s_logits(devices8, shape, axes, want):
    if axes:
        with use_mesh(build_mesh(MeshConfig(**axes), devices=devices8[:4])):
            assert lm.loss_chunks(*shape) == (want, 1)
    else:
        assert lm.loss_chunks(*shape) == (want, 1)
