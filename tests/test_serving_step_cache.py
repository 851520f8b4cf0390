"""What `gpt2.decode_step` and `gpt2.prefill_chunk` do to the KV cache they
carry through their loop over the layers: which entries change, what the
written rows hold, and that a prompt fed through both gives the logits of
`gpt2.forward`. CPU, `gpt2-tiny` in float32 (so a tolerance can be tight),
no cluster. The cache's entries outside the written rows are compared bit
for bit: nothing may round-trip through arithmetic on its way through."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2

# T above the 128 positions a write touches at the least, so that a window
# starts past 0 and is clamped near the end of the sequence
B, T, C = 4, 160, 8
CFG = gpt2.GPT2Config.preset("gpt2-tiny", dtype=jnp.float32, max_seq_len=T,
                             attn_impl="dense")


@pytest.fixture(scope="module")
def params():
    return gpt2.init_params(jax.random.key(3), CFG)


@pytest.fixture(scope="module")
def steps(params):
    """The two programs as serve/llm.LLMEngine jits them: cache donated."""
    step = jax.jit(lambda c, t, pos, a: gpt2.decode_step(
        params, c, t, pos, a, CFG), donate_argnums=(0,))
    chunk = jax.jit(lambda c, t, p0, n, a: gpt2.prefill_chunk(
        params, c, t, p0, n, a, CFG), donate_argnums=(0,))
    return step, chunk


def _random_cache(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    shape = (CFG.n_layer, B, CFG.n_head, T, CFG.head_dim)
    return {n: rng.standard_normal(shape).astype(np.float32) for n in "kv"}


def _reference(params, cache, tokens, pos0, length, active):
    """One step, written plainly: a Python loop over layers, slots and
    lanes, each valid lane's k/v row stored at its position before the
    layer's attention reads the slot's cache. tokens [B, n]. Returns
    (logits at each slot's last valid lane, cache)."""
    n = tokens.shape[1]
    k_all = np.array(cache["k"])
    v_all = np.array(cache["v"])
    H, Dh = CFG.n_head, CFG.head_dim
    logits = np.zeros((B, CFG.vocab_size), np.float32)
    for b in range(B):
        pos = pos0[b] + np.arange(n)
        x = params["wte"][tokens[b]] + params["wpe"][np.clip(pos, 0, T - 1)]
        rows = [i for i in range(n) if active[b] and i < length[b]]
        for l in range(CFG.n_layer):
            bp = jax.tree.map(lambda w: w[l], params["blocks"])
            h = gpt2._layer_norm(x, bp["ln1"])
            qkv = h @ bp["attn"]["wqkv"] + bp["attn"]["bqkv"]
            q, k, v = (a.reshape(n, H, Dh) for a in jnp.split(qkv, 3, -1))
            for i in rows:
                k_all[l, b, :, pos[i], :] = k[i]
                v_all[l, b, :, pos[i], :] = v[i]
            scores = jnp.einsum("chd,htd->hct", q, k_all[l, b]) / math.sqrt(Dh)
            seen = np.arange(T)[None, None, :] <= pos[None, :, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            attn = jnp.einsum("hct,htd->chd", probs, v_all[l, b])
            x = x + attn.reshape(n, H * Dh) @ bp["attn"]["wo"] + \
                bp["attn"]["bo"]
            x = x + gpt2._mlp(gpt2._layer_norm(x, bp["ln2"]), bp["mlp"], CFG)
        last = int(np.clip(length[b] - 1, 0, n - 1))
        logits[b] = gpt2._layer_norm(x[last], params["ln_f"]) @ \
            params["wte"].T
    return logits, {"k": k_all, "v": v_all}


def _written(pos0, length, active) -> np.ndarray:
    """[B, T] bool: the positions a step may write."""
    t = np.arange(T)[None, :]
    return (active[:, None] & (t >= pos0[:, None])
            & (t < (pos0 + length)[:, None]))


def _check(new_cache, logits, cache, ref_logits, ref_cache, written):
    counted = written.any(axis=1)
    for name in "kv":
        got = np.asarray(new_cache[name])
        # (a) outside the written rows of active slots: the input's bits
        keep = np.broadcast_to(~written[None, :, None, :, None], got.shape)
        assert np.array_equal(got[keep].view(np.uint32),
                              cache[name][keep].view(np.uint32))
        # (b) the written rows: the layer's own k/v
        np.testing.assert_allclose(got[~keep], ref_cache[name][~keep],
                                   rtol=2e-5, atol=2e-5)
        if written.any():
            assert not np.array_equal(got[~keep], cache[name][~keep])
    np.testing.assert_allclose(np.asarray(logits)[counted],
                               ref_logits[counted], rtol=2e-4, atol=2e-4)


DECODE_CASES = {
    "all-active": ([5, 9, 17, 30], [1, 1, 1, 1]),
    "some-inactive": ([5, 9, 17, 30], [1, 0, 1, 0]),
    "none-active": ([5, 9, 17, 30], [0, 0, 0, 0]),
    "first-and-last-position": ([0, T - 1, 0, T - 1], [1, 1, 0, 0]),
    "same-position": ([7, 7, 7, 7], [0, 1, 1, 1]),
    "around-the-windows-edges": ([127, 128, T - 129, T - 128], [1, 1, 1, 1]),
}


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_step_writes_one_row_per_active_slot(params, steps, case):
    pos, active = (np.asarray(a) for a in DECODE_CASES[case])
    active = active.astype(bool)
    rng = np.random.default_rng(len(case))
    tokens = rng.integers(0, CFG.vocab_size, B)
    cache = _random_cache(11)
    logits, new_cache = steps[0](
        jax.tree.map(jnp.asarray, cache), jnp.asarray(tokens, jnp.int32),
        jnp.asarray(pos, jnp.int32), jnp.asarray(active))
    ones = np.ones(B, np.int64)
    ref_logits, ref_cache = _reference(params, cache, tokens[:, None], pos,
                                       ones, active)
    _check(new_cache, logits, cache, ref_logits, ref_cache,
           _written(pos, ones, active))


def _write_then_attend(params, cache, tokens, pos, active, cfg):
    """`decode_step` as it was until PR 56, written plainly: every layer
    stores its new row at pos[b] of each active slot first, then attends
    over its whole slice of the cache to that position. Precision as the
    program's: rows and probabilities in cfg.dtype, scores in float32."""
    n = tokens.shape[0]
    H, Dh = cfg.n_head, cfg.head_dim
    ck, cv = cache["k"], cache["v"]
    t_len = ck.shape[3]
    slot = jnp.arange(n)
    x = (params["wte"][tokens] + params["wpe"][
        jnp.clip(pos, 0, cfg.max_seq_len - 1)]).astype(cfg.dtype)
    for l in range(cfg.n_layer):
        bp = jax.tree.map(lambda w: w[l], params["blocks"])
        h = gpt2._layer_norm(x, bp["ln1"])
        qkv = h @ bp["attn"]["wqkv"].astype(cfg.dtype) + \
            bp["attn"]["bqkv"].astype(cfg.dtype)
        q, k, v = (a.reshape(n, H, Dh) for a in jnp.split(qkv, 3, -1))
        keep = ~active[:, None, None]
        ck = ck.at[l, slot, :, pos].set(jnp.where(keep, ck[l, slot, :, pos], k))
        cv = cv.at[l, slot, :, pos].set(jnp.where(keep, cv[l, slot, :, pos], v))
        scores = jnp.einsum("bhd,bhtd->bht", q, ck[l],
                            preferred_element_type=jnp.float32)
        seen = jnp.arange(t_len)[None, None, :] <= pos[:, None, None]
        scores = jnp.where(seen, scores / math.sqrt(Dh), -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
        attn = jnp.einsum("bht,bhtd->bhd", probs, cv[l]).reshape(n, H * Dh)
        x = x + attn @ bp["attn"]["wo"].astype(cfg.dtype) + \
            bp["attn"]["bo"].astype(cfg.dtype)
        x = x + gpt2._mlp(gpt2._layer_norm(x, bp["ln2"]), bp["mlp"], cfg)
    x = gpt2._layer_norm(x, params["ln_f"])
    logits = (x @ params["wte"].T.astype(cfg.dtype)).astype(jnp.float32)
    return logits, {"k": ck, "v": cv}


# float32: the order of a sum (3e-7 read); bfloat16: the last bit of a
# value near 1, 0.0039, on its way through two layers (0.0036 read: both
# forms hold rows and probabilities in bf16 and round the weighted values
# once)
_OWN_ROW_TOL = {"float32": 1e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("at", [0, 1, 127, 128, T - 1],
                         ids=lambda p: f"pos{p}")
def test_decode_step_attends_to_its_own_row_and_writes_it_after(dtype, at):
    """The layer attends to the cache as it was before the step plus its
    own new row, and the rows of all layers are written after the loop:
    the logits and the cache of write-then-attend. Slot 1 is inactive at
    the same position and slot 2 stands elsewhere. What the cache holds at
    a slot's position and beyond is stale: finite junk there gives, bit for
    bit, the logits and the written rows that zeros give."""
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", dtype=_DTYPES[dtype],
                                 max_seq_len=T, attn_impl="dense")
    params = gpt2.init_params(jax.random.key(9), cfg)
    rng = np.random.default_rng(at)
    shape = (cfg.n_layer, B, cfg.n_head, T, cfg.head_dim)
    junk = {n: jnp.asarray(rng.standard_normal(shape), cfg.dtype)
            for n in "kv"}
    pos = np.array([at, at, (at + 64) % T, at])
    active = np.array([True, False, True, True])
    stale = (np.arange(T)[None, :] >= pos[:, None]) & active[:, None]
    clean = {n: jnp.where(stale[None, :, None, :, None], 0, a)
             for n, a in junk.items()}
    args = (jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32),
            jnp.asarray(pos, jnp.int32), jnp.asarray(active))
    step = jax.jit(lambda c: gpt2.decode_step(params, c, *args, cfg))
    logits, new = step(junk)
    logits_clean, new_clean = step(clean)
    ref_logits, ref = jax.jit(
        lambda c: _write_then_attend(params, c, *args, cfg))(junk)
    tol = _OWN_ROW_TOL[dtype]

    def f32(a):
        return np.asarray(a.astype(jnp.float32))

    assert np.abs(f32(logits)).max() > 0.1
    np.testing.assert_allclose(f32(logits)[active], f32(ref_logits)[active],
                               rtol=tol, atol=tol)
    assert _same_bits(logits[active], logits_clean[active])
    written = _written(pos, np.ones(B, np.int64), active)
    keep = np.broadcast_to(~written[None, :, None, :, None], shape)
    for n in "kv":
        got, before, want = (np.asarray(a[n]) for a in (new, junk, ref))
        # outside the rows of active slots, the inactive slot's whole
        # cache among them: the bits that came in, and so the reference's
        assert _same_bits(got[keep], before[keep])
        assert _same_bits(want[keep], before[keep])
        np.testing.assert_allclose(f32(new[n])[~keep], f32(ref[n])[~keep],
                                   rtol=tol, atol=tol)
        assert not _same_bits(got[~keep], before[~keep])
        assert _same_bits(got[~keep], np.asarray(new_clean[n])[~keep])


def test_engine_decodes_the_tokens_of_write_then_attend(monkeypatch):
    """32 greedy steps of the engine, two streams in their slots: the
    tokens the engine gives with the decode program PR 56 replaced."""
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.utils.platform import ensure_virtual_cpu

    ensure_virtual_cpu(1)
    kw = dict(preset="gpt2-tiny", max_batch=2, max_seq_len=T, seed=5,
              enable_prefix_caching=False)
    prompts = ["the quick brown fox ", "a decode step writes its rows once"]

    def tokens_of(engine):
        try:
            return [engine.generate(p, max_tokens=32)["token_ids"]
                    for p in prompts]
        finally:
            engine.shutdown()

    got = tokens_of(LLMEngine(**kw))
    monkeypatch.setattr(gpt2, "decode_step", _write_then_attend)
    want = tokens_of(LLMEngine(**kw))
    assert all(len(ids) == 32 for ids in want)
    assert got == want


CHUNK_CASES = {
    # pos0, length, active
    "lengths-0-1-C": ([3, 10, 0, 20], [0, 1, C, 4], [1, 1, 1, 1]),
    "some-inactive": ([3, 10, 0, 20], [5, 1, C, 4], [0, 1, 0, 1]),
    "none-active": ([3, 10, 0, 20], [5, 1, C, 4], [0, 0, 0, 0]),
    # pos0 > T - C: dynamic_update_slice would clamp the window's start
    "clamped-window": ([T - 3, T - 1, T - C, T - C + 1], [3, 1, C, C - 1],
                       [1, 1, 1, 1]),
    "clamped-and-inactive": ([T - 3, T - 1, T - 5, T - 2], [3, 1, 0, 2],
                             [1, 0, 1, 1]),
    "whole-window-at-zero": ([0, 0, 0, 0], [C, C, 1, 0], [1, 0, 1, 1]),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_prefill_chunk_writes_the_valid_lanes_of_active_slots(params, steps,
                                                              case):
    pos0, length, active = (np.asarray(a) for a in CHUNK_CASES[case])
    active = active.astype(bool)
    rng = np.random.default_rng(len(case))
    tokens = rng.integers(0, CFG.vocab_size, (B, C))
    cache = _random_cache(12)
    logits, new_cache = steps[1](
        jax.tree.map(jnp.asarray, cache), jnp.asarray(tokens, jnp.int32),
        jnp.asarray(pos0, jnp.int32), jnp.asarray(length, jnp.int32),
        jnp.asarray(active))
    ref_logits, ref_cache = _reference(params, cache, tokens, pos0, length,
                                       active)
    _check(new_cache, logits, cache, ref_logits, ref_cache,
           _written(pos0, length, active))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 1e-1)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("prompt_len", [1, C, 2 * C + 3, T - 1],
                         ids=lambda n: f"prompt{n}")
def test_chunks_then_decode_match_forward(dtype, tol, prompt_len):
    """A prompt fed C tokens a chunk (the last chunk's window clamped when
    it reaches past T - C), then decoded to the end of the sequence, one
    slot idle throughout: every step's logits are `forward`'s."""
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", dtype=dtype, max_seq_len=T,
                                 attn_impl="dense")
    params = gpt2.init_params(jax.random.key(5), cfg)
    rng = np.random.default_rng(prompt_len)
    toks = rng.integers(0, cfg.vocab_size, (B, T))
    full = np.asarray(gpt2.forward(params, jnp.asarray(toks, jnp.int32),
                                   cfg).astype(jnp.float32))
    step = jax.jit(lambda c, t, pos, a: gpt2.decode_step(
        params, c, t, pos, a, cfg), donate_argnums=(0,))
    chunk = jax.jit(lambda c, t, p0, n, a: gpt2.prefill_chunk(
        params, c, t, p0, n, a, cfg), donate_argnums=(0,))
    active = np.array([True, True, False, True])
    cache = gpt2.init_cache(cfg, B, T)
    for p0 in range(0, prompt_len, C):
        n = min(C, prompt_len - p0)
        lanes = np.zeros((B, C), np.int64)
        lanes[:, :n] = toks[:, p0:p0 + n]
        logits, cache = chunk(
            cache, jnp.asarray(lanes, jnp.int32),
            jnp.full((B,), p0, jnp.int32), jnp.full((B,), n, jnp.int32),
            jnp.asarray(active))
        np.testing.assert_allclose(np.asarray(logits)[active],
                                   full[active, p0 + n - 1], rtol=tol,
                                   atol=tol)
    for pos in range(prompt_len, T):
        logits, cache = step(cache, jnp.asarray(toks[:, pos], jnp.int32),
                             jnp.full((B,), pos, jnp.int32),
                             jnp.asarray(active))
        np.testing.assert_allclose(np.asarray(logits)[active],
                                   full[active, pos], rtol=tol, atol=tol)
    # the idle slot's cache was never written
    assert not np.asarray(cache["k"][:, 2]).any()
    assert not np.asarray(cache["v"][:, 2]).any()


def test_an_undonated_cache_is_left_as_it_was(params):
    """Donation makes the update in place; without it the caller's cache
    is not touched."""
    cache = jax.tree.map(jnp.asarray, _random_cache(13))
    before = jax.tree.map(np.array, cache)
    _, new_cache = jax.jit(lambda c, t, pos, a: gpt2.decode_step(
        params, c, t, pos, a, CFG))(
            cache, jnp.zeros(B, jnp.int32), jnp.arange(B, dtype=jnp.int32),
            jnp.ones(B, jnp.bool_))
    for name in "kv":
        assert np.array_equal(np.asarray(cache[name]), before[name])
        assert not np.array_equal(np.asarray(new_cache[name]), before[name])


# ------------------------------------------------- the resident tree (PR 26)

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
_CONVERTED = [("attn", n) for n in ("wqkv", "bqkv", "wo", "bo")] + \
    [("mlp", n) for n in ("wi", "bi", "wo", "bo")]


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint8)


def _same_bits(a, b) -> bool:
    return a.dtype == b.dtype and np.array_equal(_bits(a), _bits(b))


@pytest.fixture(scope="module", params=list(_DTYPES))
def trees(request):
    """(cfg, a tree of `init_params`, its resident tree) for a compute
    dtype."""
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", dtype=_DTYPES[request.param],
                                 max_seq_len=T, attn_impl="dense")
    source = gpt2.init_params(jax.random.key(7), cfg)
    return cfg, source, gpt2.resident_params(source, cfg)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_a_resident_tree_gives_the_source_trees_bits(trees, program):
    """The weights converted once are the values the step converted them
    to: logits and both caches equal to the bit, in either compute dtype."""
    cfg, source, resident = trees
    rng = np.random.default_rng(21)
    shape = (cfg.n_layer, B, cfg.n_head, T, cfg.head_dim)
    cache = {n: jnp.asarray(rng.standard_normal(shape), cfg.dtype)
             for n in "kv"}
    active = jnp.asarray([True, True, False, True])
    pos0 = jnp.asarray([5, 127, 17, T - C], jnp.int32)
    if program == "decode_step":
        fn = jax.jit(lambda p, c, t, pos, a: gpt2.decode_step(
            p, c, t, pos, a, cfg))
        args = (jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32),
                pos0, active)
    else:
        fn = jax.jit(lambda p, c, t, p0, n, a: gpt2.prefill_chunk(
            p, c, t, p0, n, a, cfg))
        args = (jnp.asarray(rng.integers(0, cfg.vocab_size, (B, C)),
                            jnp.int32),
                pos0, jnp.asarray([1, C, 3, C], jnp.int32), active)
    logits, new = fn(source, cache, *args)
    logits_r, new_r = fn(resident, cache, *args)
    assert logits.dtype == jnp.float32 and np.asarray(logits).any()
    assert _same_bits(logits, logits_r)
    for n in "kv":
        assert not _same_bits(new[n], cache[n])       # the step wrote
        assert _same_bits(new[n], new_r[n])


def test_resident_tree_leaf_dtypes(trees):
    """The eight leaves of a block that the programs convert, and the
    unembedding, are in the compute dtype; the table, the positions and
    every norm stay float32, and are the source's own arrays."""
    cfg, source, resident = trees
    blocks = resident["blocks"]
    for part, name in _CONVERTED:
        leaf = blocks[part][name]
        assert leaf.dtype == cfg.dtype, (part, name)
        assert leaf.shape == source["blocks"][part][name].shape
        assert _same_bits(leaf, source["blocks"][part][name].astype(cfg.dtype))
    assert resident["unembed"].dtype == cfg.dtype
    assert resident["unembed"].shape == (cfg.d_model, cfg.vocab_size)
    assert _same_bits(resident["unembed"],
                      source["wte"].T.astype(cfg.dtype))
    kept = [("wte",), ("wpe",), ("ln_f", "scale"), ("ln_f", "bias")] + \
        [("blocks", ln, n) for ln in ("ln1", "ln2")
         for n in ("scale", "bias")]
    for path in kept:
        a, b = resident, source
        for k in path:
            a, b = a[k], b[k]
        assert a.dtype == jnp.float32, path
        assert a is b, path             # handed on, not copied
    # nothing else: the source's leaves and the unembedding
    assert len(jax.tree.leaves(resident)) == len(jax.tree.leaves(source)) + 1


def test_resident_params_is_idempotent(trees):
    """A resident tree goes in, the same values come out, and the leaves
    that needed nothing are the same arrays; the unembedding is made again
    from the table, so a table that changed reaches the logits."""
    cfg, _, resident = trees
    again = gpt2.resident_params(resident, cfg)
    assert jax.tree.structure(again) == jax.tree.structure(resident)
    for a, b in zip(jax.tree.leaves(again), jax.tree.leaves(resident)):
        assert _same_bits(a, b)
    for part, name in _CONVERTED:
        assert again["blocks"][part][name] is resident["blocks"][part][name]
    moved = {**resident, "wte": resident["wte"] * 2}
    assert _same_bits(gpt2.resident_params(moved, cfg)["unembed"],
                      moved["wte"].T.astype(cfg.dtype))


# ------------------------------------ the kernel's write after the loop (PR 58)

# 125M's heads (12 of 64 lanes) in bfloat16, two layers deep; a window of
# two tiles, so that a row lands in either and on both of their edges
KT = 256
KERNEL_CASES = {
    "all-active": (KT, [0, 127, 128, KT - 1], [1, 1, 1, 1]),
    "some-inactive": (KT, [0, 127, 128, KT - 1], [1, 0, 1, 0]),
    "one-active": (KT, [0, 127, 128, KT - 1], [0, 0, 0, 1]),
    "one-active-first-tile": (KT, [KT - 1, 127, 5, 0], [0, 1, 0, 0]),
    "none-active": (KT, [3, 127, 128, KT - 1], [0, 0, 0, 0]),
    "same-position": (KT, [128, 128, 128, 128], [1, 1, 0, 1]),
    # a serving window that is no multiple of a tile's 128 positions takes
    # `_cache_write`'s windows, wherever it runs
    "windows-at-192": (192, [0, 127, 128, 191], [1, 1, 1, 1]),
}


def _kernel_cfg(T):
    return gpt2.GPT2Config.preset("gpt2-125m", n_layer=2, vocab_size=512,
                                  max_seq_len=T)


@pytest.fixture(scope="module")
def kernel_params():
    return gpt2.init_params(jax.random.key(58), _kernel_cfg(KT))


def _decode(params, cfg, cache, pos, active, donate=True):
    """(logits, cache) of one jitted `decode_step`, traced now."""
    return jax.jit(lambda c, t, pos, a: gpt2.decode_step(
        params, c, t, pos, a, cfg), donate_argnums=(0,) if donate else ())(
            cache, jnp.arange(B, dtype=jnp.int32) + 7,
            jnp.asarray(pos, jnp.int32), jnp.asarray(active, bool))


def _bf16_cache(cfg, T, seed):
    shape = (cfg.n_layer, B, cfg.n_head, T, cfg.head_dim)
    return {n: jax.random.normal(jax.random.key(seed + i), shape, cfg.dtype)
            for i, n in enumerate("kv")}


@pytest.fixture
def through_the_kernel(monkeypatch):
    """`decode_step` on the branch it takes on the chip, its Pallas call
    interpreted; the fixture's value lists the leaves the kernel was given."""
    from functools import partial
    import importlib

    rw = importlib.import_module("ray_tpu.ops.rows_write")
    calls, kernel = [], rw._write_every
    monkeypatch.setattr(rw, "_write_every",
                        lambda c, *a: calls.append(c.shape) or kernel(c, *a))
    monkeypatch.setattr(gpt2, "_decode_write",
                        partial(gpt2._decode_write, interpret=True))
    return calls


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_the_kernels_write_leaves_the_windows_bits(kernel_params, case,
                                                   monkeypatch, request):
    T, pos, active = KERNEL_CASES[case]
    cfg = _kernel_cfg(T)
    want_logits, want = _decode(kernel_params, cfg, _bf16_cache(cfg, T, 3),
                                pos, active)
    calls = request.getfixturevalue("through_the_kernel")
    logits, got = _decode(kernel_params, cfg, _bf16_cache(cfg, T, 3), pos,
                          active)
    # which path a call takes is read off its shapes: a leaf a call
    assert len(calls) == (2 if T % 128 == 0 else 0)
    assert _same_bits(logits, want_logits)
    for name in "kv":
        assert _same_bits(got[name], want[name]), name
    # and those bits are the input's everywhere but at an active slot's row
    before = _bf16_cache(cfg, T, 3)
    row = (np.arange(T) == np.asarray(pos)[:, None]) \
        & np.asarray(active, bool)[:, None]                          # [B, T]
    for name in "kv":
        same = _bits(got[name]) == _bits(before[name])
        kept = np.broadcast_to(~row[None, :, None, :, None], same.shape)
        assert same[kept].all()
        assert not row.any() or not same[~kept].all()


def test_an_undonated_cache_is_left_as_it_was_by_the_kernel(
        kernel_params, through_the_kernel):
    cfg = _kernel_cfg(KT)
    cache = _bf16_cache(cfg, KT, 5)
    before = jax.tree.map(np.array, cache)
    _, new_cache = _decode(kernel_params, cfg, cache, [0, 127, 128, KT - 1],
                           [1, 1, 1, 1], donate=False)
    assert len(through_the_kernel) == 2
    for name in "kv":
        assert np.array_equal(np.asarray(cache[name]), before[name])
        assert not np.array_equal(np.asarray(new_cache[name]), before[name])


def test_a_cache_sharded_over_heads_is_written_a_shard_each(
        through_the_kernel):
    """The tensor-parallel engine's decode step (`serve/llm.py`): GSPMD
    places the program, and the kernel, which no compiler partitions, takes
    each shard's own heads through `shard_map`."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshConfig, build_mesh, traced_on

    cfg = _kernel_cfg(KT)
    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    heads = NamedSharding(mesh, P(None, None, "tp"))
    cache = _bf16_cache(cfg, KT, 7)["k"]
    rows = jax.random.normal(jax.random.key(8), (cfg.n_layer, B, cfg.n_head,
                                                 cfg.head_dim), cfg.dtype)
    pos, on = jnp.array([0, 127, 128, KT - 1]), jnp.array([1, 1, 0, 1], bool)
    want = gpt2._cache_write(cache, None, rows[:, :, :, None], pos,
                             on[:, None])
    got = jax.jit(traced_on(mesh, lambda c, r: gpt2._decode_write(
        c, r, pos, on)), in_shardings=(heads, heads), out_shardings=heads)(
            cache, rows)
    assert through_the_kernel == [
        (cfg.n_layer, B, cfg.n_head // 2, cfg.head_dim, KT)]
    assert got.sharding.is_equivalent_to(heads, got.ndim)
    assert _same_bits(got, want)


# ---------------------------------------------- the whole engine (PR 61)

def _served(kernels: bool, monkeypatch):
    """A tiny float32 GPT-2 engine of 3 slots x 256 positions serves ten
    greedy requests, four at a time, so slots are admitted, finish and are
    taken again; with `kernels` the decode step's two Pallas calls run
    interpreted, the attention in blocks of 128. The layers' matrices are
    eight times the seeded ones: a reply is then no repetition of its
    prompt's last token, and a wrong row attended to is another token.
    Returns ({request: tokens}, engine_stats(), the (pos, active) [B] of
    every decode step, the lanes the chunk steps ran)."""
    import importlib
    import threading
    from functools import partial

    from ray_tpu.serve.llm import LLMEngine

    if kernels:
        monkeypatch.setattr(importlib.import_module(
            "ray_tpu.ops.gqa_attend"), "BLOCK_LAST", 128)
        for name in ("_decode_attend", "_decode_write", "rows_read_block"):
            monkeypatch.setattr(gpt2, name, partial(getattr(gpt2, name),
                                                    interpret=True))
    cfg = gpt2.GPT2Config.preset("gpt2-tiny", max_seq_len=256,
                                 dtype=jnp.float32)
    params = gpt2.init_params(jax.random.key(61), cfg)
    params["blocks"] = jax.tree.map(lambda a: 8 * a if a.ndim == 3 else a,
                                    params["blocks"])
    eng = LLMEngine(preset="gpt2-tiny", max_batch=3, max_seq_len=256,
                    prefill_chunk_size=16, kv_block_size=8,
                    params_override=params, cfg_override=cfg)
    out, decode_steps, chunk_lanes = {}, [], []
    step, chunk = eng._step, eng._chunk_step

    def seen_step(params, cache, ids, pos, active):
        decode_steps.append((np.asarray(pos), np.asarray(active)))
        return step(params, cache, ids, pos, active)

    def seen_chunk(params, cache, tokens, pos, lengths, active):
        chunk_lanes.append(int(np.asarray(active).sum()))
        return chunk(params, cache, tokens, pos, lengths, active)

    eng._step, eng._chunk_step = seen_step, seen_chunk
    try:
        def ask(i):
            # prompts that end short of, on and past the first block's edge
            prompt = [(7 * i + 3 * j * j) % 500 + 1
                      for j in range(100 + 7 * i)]
            out[i] = eng.generate(prompt_ids=prompt, max_tokens=8 + 5 * i,
                                  temperature=0.0)["token_ids"]
        for first in (0, 4, 8):
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(first, min(first + 4, 10))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
                assert not t.is_alive()
        return out, eng.engine_stats(), decode_steps, sum(chunk_lanes)
    finally:
        eng.shutdown()


def test_an_engines_tokens_through_the_kernels_are_the_plain_paths(
        monkeypatch):
    """The attention kernel in the engine's decode program, over a run that
    admits, finishes and re-admits slots: the greedy tokens are the plain
    path's, and `positions_read` counts what the path reads: the kernel a
    decode lane's positions rounded up to a block of 128 (less than a whole
    block more than it attends), the plain lines all 256; a chunk step all
    256 a lane either way."""
    with monkeypatch.context() as m:
        got, stats, decode_steps, chunk_lanes = _served(True, m)
    want, plain, plain_steps, plain_chunk_lanes = _served(False, monkeypatch)
    assert got == want
    assert len({t for reply in want.values() for t in reply}) > 100
    # both engines planned the same steps
    same = ("positions_attended", "chunk_tokens", "total_generated",
            "engine_steps", "chunk_steps")
    assert [stats[n] for n in same] == [plain[n] for n in same]
    assert len(decode_steps) == stats["engine_steps"] - stats["chunk_steps"]
    # a decode lane at position p attends p + 1 positions and reads them
    # rounded up to a block, less than a block more
    lanes = sum(int(on.sum()) for _, on in decode_steps)
    attended = sum(int((pos + 1)[on].sum()) for pos, on in decode_steps)
    read = sum(int(((pos // 128 + 1) * 128)[on].sum())
               for pos, on in decode_steps)
    assert lanes > 100 and attended <= read < attended + 128 * lanes
    assert {int(p) // 128 for pos, on in decode_steps for p in pos[on]} \
        == {0, 1}
    assert stats["positions_read"] == read + 256 * chunk_lanes
    # the plain path reads all T a lane of a step, chunk or decode
    assert plain["positions_read"] == 256 * (sum(
        int(on.sum()) for _, on in plain_steps) + plain_chunk_lanes)
    for s in (stats, plain):
        assert s["positions_read"] >= s["positions_attended"] > attended


@pytest.mark.parametrize("preset,block", [
    ("deepseek-tiny", 0), ("granite-tiny", 64), ("gpt2-tiny", 64)])
def test_only_a_family_that_names_its_block_counts_positions_read(
        preset, block):
    """GPT-2 and granite (since PR 63) name theirs, all T off the chip."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(preset=preset, max_batch=2, max_seq_len=64)
    try:
        assert eng._rows_read_block == block
        assert ("positions_read" in eng.engine_stats()) == bool(block)
        assert "positions_attended" in eng.engine_stats()
    finally:
        eng.shutdown()
