"""The MiMo cell's two step programs and its two attention kernels, compiled
by the TPU's own compiler for a chip that is described and not attached
(`tests/test_tpu_compile.py`'s method and fixtures). A file of its own, so
that another worker of the run takes it (ROADMAP D23)."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from test_tpu_compile import (HBM_BYTES, _chip_bench,  # noqa: F401
                              _mosaic_calls, as_on_tpu, chips)

# the chunk program since PR 62 (its temporaries 407,805,952 B: the further
# lanes' stream [64, 128, 4096] float32 twice and a slot's scores by the
# block), the decode program's in the configuration file
MIMO_CHUNK_BYTES = 13_132_291_072


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mimo_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-mimo-mixedqueue`'s two programs, as its configuration
    file has them (MiMo-V2.5's widths, layers 0-6 G S S S S G S with 8 of
    256 experts a sparse layer and an eighth of the vocabulary, 64 slots of
    24,576 positions of keys [.., 4, 192, T] and values [.., 4, T, 128] in
    the two global layers and five rings of 128 positions by 8 heads, chunks
    of 128), from rehearse/compile_mimo_for_v5e.py: the bytes the file
    gives, with ==; **no leaf is padded**: the cache the programs alias is
    the table's rows and rings to the byte (a `[.., T, 192]` leaf in bf16
    would be tiled to 256 lanes, a third more); room for the pool of both
    kinds beside the larger, between 75% and 96% of the chip; the Pallas
    kernels, a body a kind of layer (two `rows_write` and one attention,
    `gqa_attend` over rows or `swa_attend` over a ring, in each of the three
    bodies, one `expert_mlp` in the two sparse ones: 11 in both programs);
    no instruction copies a cache leaf, rows or rings, or one layer's for
    all slots, an expert matrix or the dense MLP's out of its stack, or
    writes a chunk's scores over all of a slot's positions."""
    chip_dir, _ = _chip_bench()
    from compile_mimo_for_v5e import (CONFIG, cache_bytes, compile_step,
                                      made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory, deploy = config["memory"], config["deployment"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(deploy["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == memory["decode_step_bytes"]
        assert sized["temp"] == memory["decode_step_temp_bytes"] < 2 ** 26
    else:
        assert sized["total"] == MIMO_CHUNK_BYTES == memory[
            "prefill_chunk_bytes_by_chunk_size"][chunk]
        assert sized["temp"] < 2 ** 29
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 64 * 128 * 4)      # the chunk's tokens
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 3_276_800, "kv_bytes_per_token": 5120}
    # what the program holds of the cache is what the table counts: 64 slots
    # x (24,576 x 5,120 + 3,276,800) B and the counts' one tile
    slots, T = deploy["max_batch"], deploy["max_seq_len"]
    assert sized["aliased"] == slots * (T * 5120 + 3_276_800) + 1024 \
        == memory["cache_bytes"] + 1024
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert 0.75 * HBM_BYTES <= memory["prefill_chunk_bytes_by_chunk_size"][
        chunk] + pool_bytes(config) <= 0.96 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == 2
    assert sum("/kv_update/" in c and "rows_write" in c for c in calls) == 6
    assert sum("/swa_attend/" in c for c in calls) == 1
    assert sum("/gqa_attend/" in c for c in calls) == 2
    assert made_of(hlo, config) == {
        "kernels": 11,
        "whole_slot_scores": [], "leaf_copies": {}, "layer_copies": {},
        "expert_matrix_copies": [], "dense_matrix_copies": []}


@pytest.mark.parametrize("ring,sink", [(False, False), (True, True)],
                         ids=["global-rows", "sliding-ring-with-sink"])
def test_gqa_attend_kernel_takes_mimos_two_widths_where_the_leaves_lie(
        chips, as_on_tpu, ring, sink):
    """`ops/gqa_attend.py` and `ops/rows_write.py` at the cell's two shapes:
    a global layer's 64 slots x 4 heads x 24,576 positions, keys [.., 192,
    T] beside values [.., T, 128], 16 float32 queries a head (32 rows as two
    pieces); a sliding layer's rings, 8 heads, keys [.., 192, 128], 8
    queries a head and a sink a head as the fold's start, under the name
    `swa_attend`. Mosaic accepts both, and neither program holds anything
    beside its arguments: no layer of a leaf is sliced out or re-laid."""
    op = importlib.import_module("ray_tpu.ops.gqa_attend")
    write = importlib.import_module("ray_tpu.ops.rows_write")
    one = SingleDeviceSharding(chips[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    L, G, T = (5, 8, 128) if ring else (2, 4, 24576)
    ck, cv = arr((L, 64, G, 192, T)), arr((L, 64, G, T, 128))
    slots = (arr((), jnp.int32), arr((64,), jnp.int32), arr((64,), jnp.bool_))
    b = (arr((G, 64 // G), jnp.float32),) if sink else ()
    compiled = jax.jit(lambda q, ck, cv, layer, pos, live, *b: op.gqa_attend(
        q, ck, cv, layer, pos, live, 192 ** -0.5, ring=ring,
        sink=b[0] if b else None)).lower(
        arr((64, G, 64 // G, 192), jnp.float32), ck, cv, *slots,
        *b).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1
    assert ("swa_attend" if ring else "gqa_attend") in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    # no leaf is padded (keys [.., T, 192] would be a third more): the
    # arguments are the two leaves, q and the slots' integers
    assert compiled.memory_analysis().argument_size_in_bytes < 1.05 * (
        ck.size + cv.size) * 2
    for leaf, d in ((ck, 192), (cv, 128)):
        compiled = jax.jit(lambda c, layer, val, pos, on: write.rows_write(
            c, layer, val, pos, on, ring=ring), donate_argnums=(0,)).lower(
            leaf, slots[0], arr((64, G, d)), *slots[1:]).compile()
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
