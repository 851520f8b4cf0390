"""What a serving replica holds on the device since PR 26: its weights
once, as the step programs read them (`gpt2.resident_params`), under the
name the benchmark's probe sizes the programs by (`eng.params`). CPU, a
GPT-2 of widths no other test uses, so that a stray float32 array of a
block leaf's shape can only be this file's."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.parallel.mesh import use_mesh
from ray_tpu.serve.llm import LLMEngine

OVERRIDES = dict(d_model=192, d_ff=576)
KW = dict(preset="gpt2-tiny", model_overrides=OVERRIDES, max_batch=2,
          max_seq_len=96, enable_prefix_caching=False)
CFG = gpt2.GPT2Config.preset("gpt2-tiny", max_seq_len=96, **OVERRIDES)
CONVERTED = [("attn", n) for n in ("wqkv", "bqkv", "wo", "bo")] + \
    [("mlp", n) for n in ("wi", "bi", "wo", "bo")]


def _block_leaves(tree) -> list:
    return [tree["blocks"][part][name] for part, name in CONVERTED]


def _bits(a) -> np.ndarray:
    return np.asarray(a).view(np.uint8)


def _live_float32(shapes) -> list:
    return [a.shape for a in jax.live_arrays()
            if a.dtype == jnp.float32 and a.shape in shapes]


def test_the_engine_keeps_one_copy_of_the_weights():
    """Handed float32 weights, the engine converts them once and lets the
    float32 matrices go: with the caller's reference dropped none is alive,
    and what it keeps is the resident tree of them, to the bit."""
    source = gpt2.init_params(jax.random.key(5), CFG)
    assert CFG.dtype == jnp.bfloat16
    # the matrices' shapes: a bias [L, D] has the shape of a norm's scale
    shapes = {leaf.shape for leaf in _block_leaves(source) if leaf.ndim == 3}
    want = jax.tree.map(np.asarray, gpt2.resident_params(source, CFG))
    refs = [weakref.ref(leaf) for leaf in _block_leaves(source)]
    assert len(_live_float32(shapes)) == 4
    eng = LLMEngine(params_override=source, **KW)
    try:
        del source
        gc.collect()
        assert [r() for r in refs] == [None] * len(refs)
        assert _live_float32(shapes) == []
        assert jax.tree.structure(eng.params) == jax.tree.structure(want)
        for got, ref in zip(jax.tree.leaves(eng.params),
                            jax.tree.leaves(want)):
            assert got.dtype == ref.dtype
            assert np.array_equal(_bits(got), _bits(ref))
        assert {leaf.dtype for leaf in _block_leaves(eng.params)} == \
            {jnp.dtype(jnp.bfloat16)}
        assert eng.params["unembed"].dtype == jnp.bfloat16
        assert eng.params["wte"].dtype == jnp.float32
        # and the engine still serves
        assert len(eng.generate("one copy", max_tokens=4)["token_ids"]) == 4
    finally:
        eng.shutdown()


def test_seeded_and_handed_over_weights_serve_the_same_tokens():
    """The engine's own seeded path and `params_override` of the same
    float32 tree end in the same resident tree and the same replies."""
    seeded = LLMEngine(seed=5, **KW)
    handed = LLMEngine(
        params_override=gpt2.init_params(jax.random.key(5), CFG), **KW)
    try:
        for a, b in zip(jax.tree.leaves(seeded.params),
                        jax.tree.leaves(handed.params)):
            assert a.dtype == b.dtype and np.array_equal(_bits(a), _bits(b))
        prompt = "the resident tree"
        assert seeded.generate(prompt, max_tokens=8)["token_ids"] == \
            handed.generate(prompt, max_tokens=8)["token_ids"]
    finally:
        seeded.shutdown()
        handed.shutdown()


def _probe_shapes(args):
    # as benchmarks/chip/harness/replica_probe.program_bytes makes them
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args)


def test_the_probes_lowering_is_the_program_the_loop_runs():
    """The benchmark sizes the programs by lowering `eng._step` and
    `eng._chunk_step` on the shapes and dtypes of `eng.params`: that has
    to be the program the loop runs, one compilation each, with no float32
    matrix of a block among its arguments."""
    eng = LLMEngine(seed=5, **KW)
    try:
        # a prompt longer than a chunk, then decode: both programs ran
        eng.generate("x" * (eng.prefill_chunk_size + 3), max_tokens=4)
        assert eng.chunk_steps > 0 and eng.engine_steps > eng.chunk_steps
        b, c = eng.max_batch, eng.prefill_chunk_size
        ints, on = np.zeros((b,), np.int32), np.zeros((b,), bool)
        programs = {
            "decode": (eng._step, (eng.params, eng.cache, ints, ints, on)),
            "prefill": (eng._chunk_step,
                        (eng.params, eng.cache, np.zeros((b, c), np.int32),
                         ints, ints, on)),
        }
        for name, (jitted, args) in programs.items():
            assert jitted._cache_size() == 1, name
            probe = jitted.lower(*_probe_shapes(args))
            assert probe.as_text() == jitted.lower(*args).as_text(), name
            for part, leaf_name in CONVERTED:
                info = probe.args_info[0][0]["blocks"][part][leaf_name]
                assert info.dtype == jnp.bfloat16, (name, part, leaf_name)
        # with the loop stopped the test may donate the engine's cache:
        # a call on the engine's own tree compiles nothing new
        eng.shutdown()
        eng._thread.join(timeout=10)
        assert not eng._thread.is_alive()
        for name, (jitted, args) in programs.items():
            _, eng.cache = jitted(eng.params, eng.cache,
                                  *map(jnp.asarray, args[2:]))
            assert jitted._cache_size() == 1, name
    finally:
        eng.shutdown()


def test_a_tensor_parallel_engine_places_the_unembedding_by_its_spec(
        devices8):
    """Under tp the resident tree is sharded by `gpt2.resident_specs`: the
    unembedding is the table's placement with the axes reversed, so each
    device holds the vocabulary columns of the table rows it holds."""
    from jax.sharding import PartitionSpec as P

    eng = LLMEngine(seed=5, tensor_parallel_size=2, **KW)
    ref = LLMEngine(seed=5, **KW)
    try:
        with use_mesh(eng.mesh):
            specs = gpt2.resident_specs(eng.cfg)
        assert specs["unembed"] == P(*reversed(specs["wte"]))
        assert specs["unembed"][1] == "tp"          # the vocabulary
        assert jax.tree.structure(
            jax.tree.map(lambda _: 0, specs,
                         is_leaf=lambda s: isinstance(s, P))) == \
            jax.tree.structure(jax.tree.map(lambda _: 0, eng.params))
        un = eng.params["unembed"]
        assert un.sharding.spec == specs["unembed"]
        assert len(un.sharding.device_set) == 2
        D, V = eng.cfg.d_model, eng.cfg.vocab_size
        assert {s.data.shape for s in un.addressable_shards} == {(D, V // 2)}
        assert eng.params["blocks"]["mlp"]["wi"].sharding.spec == \
            specs["blocks"]["mlp"]["wi"]
        # the same values as one device's, and the same replies
        assert np.array_equal(_bits(un), _bits(ref.params["unembed"]))
        prompt = "sharded unembedding"
        assert eng.generate(prompt, max_tokens=6)["token_ids"] == \
            ref.generate(prompt, max_tokens=6)["token_ids"]
    finally:
        eng.shutdown()
        ref.shutdown()


def _adapter(rng, path_shapes, rank=4, alpha=8.0) -> dict:
    return {path: {"A": (rng.normal(size=lead + (rows, rank)) * 0.3
                         ).astype(np.float32),
                   "B": (rng.normal(size=lead + (rank, cols)) * 0.3
                         ).astype(np.float32),
                   "alpha": np.float32(alpha)}
            for path, (lead, rows, cols) in path_shapes.items()}


@pytest.mark.parametrize("tree", ["float32", "resident"])
def test_lora_merge_rounds_once(tree):
    """The delta is float32 whatever the leaf: on float32 leaves the merge
    is `leaf + delta` as it always was, to the bit; on a resident tree it
    is the rounding of (bfloat16 base + float32 delta), not a bfloat16
    product added in bfloat16."""
    L, D, V = CFG.n_layer, CFG.d_model, CFG.vocab_size
    source = gpt2.init_params(jax.random.key(9), CFG)
    base = source if tree == "float32" else gpt2.resident_params(source, CFG)
    adapter = _adapter(np.random.default_rng(1), {
        "blocks.attn.wqkv": ((L,), D, 3 * D), "wte": ((), V, D)})
    merged = gpt2.apply_lora(base, adapter)
    for path in adapter:
        keys = path.split(".")
        leaf, got = base, merged
        for k in keys:
            leaf, got = leaf[k], got[k]
        A = jnp.asarray(adapter[path]["A"], jnp.float32)
        B = jnp.asarray(adapter[path]["B"], jnp.float32)
        delta = (8.0 / 4) * (A @ B)
        assert got.dtype == leaf.dtype
        if leaf.dtype == jnp.float32:
            want = leaf + delta                       # the parent's merge
        else:
            want = (leaf.astype(jnp.float32) + delta).astype(jnp.bfloat16)
            # and not the parent's arithmetic on a bfloat16 leaf
            old = leaf + (8.0 / 4) * (A.astype(leaf.dtype)
                                      @ B.astype(leaf.dtype))
            assert not np.array_equal(_bits(got), _bits(old))
        assert np.array_equal(_bits(got), _bits(want)), path
    # untouched leaves are the base's own arrays
    assert merged["blocks"]["mlp"]["wi"] is base["blocks"]["mlp"]["wi"]
    assert merged["wpe"] is base["wpe"]


def test_an_adapter_engine_shares_the_bases_leaves_and_carries_wte_over():
    """An adapter engine is built on the base engine's resident tree: the
    leaves the adapter did not touch stay the base's arrays (one copy on
    the device), a merged matrix is in the compute dtype, and a merge into
    the table reaches the logits through the unembedding made again."""
    L, D, V = CFG.n_layer, CFG.d_model, CFG.vocab_size
    base = LLMEngine(seed=5, **KW)
    adapter = _adapter(np.random.default_rng(2), {
        "blocks.attn.wqkv": ((L,), D, 3 * D), "wte": ((), V, D)})
    merged = gpt2.apply_lora(base.params, adapter)
    eng = LLMEngine(params_override=merged, cfg_override=base.cfg,
                    weights_id=base.weights_id, **KW)
    try:
        mine, theirs = eng.params, base.params
        assert mine["blocks"]["mlp"]["wi"] is theirs["blocks"]["mlp"]["wi"]
        assert mine["blocks"]["attn"]["wo"] is theirs["blocks"]["attn"]["wo"]
        assert mine["wpe"] is theirs["wpe"]
        assert mine["blocks"]["attn"]["wqkv"].dtype == jnp.bfloat16
        assert not np.array_equal(_bits(mine["blocks"]["attn"]["wqkv"]),
                                  _bits(theirs["blocks"]["attn"]["wqkv"]))
        assert mine["wte"].dtype == jnp.float32
        assert np.array_equal(
            _bits(mine["unembed"]),
            _bits(mine["wte"].T.astype(jnp.bfloat16)))
        assert not np.array_equal(_bits(mine["unembed"]),
                                  _bits(theirs["unembed"]))
        prompt = "adapters share the base"
        assert eng.generate(prompt, max_tokens=6)["token_ids"] != \
            base.generate(prompt, max_tokens=6)["token_ids"]
    finally:
        eng.shutdown()
        base.shutdown()
