"""The main path's kernels and step programs, compiled by the TPU's own
compiler for a chip that is described and not attached (`v5e:2x2`).

Nothing runs here, so nothing is said about results or times: a pass means
the chip's compiler accepts the program (tiling, VMEM, partitioning,
per-device memory), which interpret mode and the CPU backend cannot tell.
Code that asks `jax.default_backend()` sees the CPU in this process, so the
tests steer it onto its TPU branch themselves. The module keeps the run's
persistent compilation cache (conftest.py) off while its tests run: an
executable compiled for a described chip cannot be read back without one.
"""

import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import gpt2, lm
from ray_tpu.ops import slot_rows
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.train.spmd import (compile_gpt2_train, compile_pipeline_train,
                                default_optimizer)

flash = importlib.import_module("ray_tpu.ops.flash_attention")

HBM_BYTES = 16909336064        # a v5e chip's memory_stats()["bytes_limit"]


@pytest.fixture(scope="module")
def chips():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    # a program this module compiles twice would be found in the cache, fail
    # to load ("DeserializeLoadedExecutable not implemented") and compile
    # again, and every full-size executable would be written out for nothing.
    # JAX asks once a process whether it uses the cache: make it ask again.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Take the branches the program takes on the chip."""
    monkeypatch.setattr(flash, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _per_device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def _chip_bench():
    """benchmarks/chip and its rehearsals on the path; (directory,
    `harness.spec`)."""
    import sys

    chip_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "chip")
    sys.path[:0] = [p for p in (chip_dir, os.path.join(chip_dir, "rehearse"))
                    if p not in sys.path]
    from harness import spec

    return chip_dir, spec


def _flash_program(grad: bool):
    def fwd(q, k, v):
        return flash.flash_attention(q, k, v, True)

    if not grad:
        return jax.jit(fwd)
    return jax.jit(jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))


# ------------------------------------------------------------------ kernels

@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
@pytest.mark.parametrize("shape", [
    (4, 12, 2048, 64), (2, 25, 2048, 64),
    # the training cells' per-device shapes
    (20, 12, 1024, 64), (8, 25, 1024, 64), (8, 16, 4096, 128)],
    ids=["125m-heads", "1.5b-heads", "train-small-1k", "train-xl-fsdp4-1k",
         "train-olmoe-4k"])
def test_flash_attention_compiles(chips, as_on_tpu, shape, grad):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(chips[0]))
    compiled = _flash_program(grad).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The long sequences take the compiler 75-95 s a case here (329 s of the
# run's 1,470: ROADMAP D23) and are marked `slow`; each keeps a case of the
# same test at a length that compiles in seconds, past one tile of K and V,
# which holds the grid axis and the count
_LONG = pytest.mark.slow


@pytest.mark.parametrize("seq", [pytest.param(16384, marks=_LONG), 3072],
                         ids=["16k", "3k"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd+bwd"])
def test_flash_attention_compiles_at_16k(chips, as_on_tpu, grad, seq):
    """K and V come in tiles over a grid axis (PR 31): the first kernel,
    which kept a head's whole K/V in VMEM, refused 16k by name."""
    x = jax.ShapeDtypeStruct((1, 12, seq, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(chips[0]))
    compiled = _flash_program(grad).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("seq", [pytest.param(8192, marks=_LONG),
                                 pytest.param(65536, marks=_LONG),
                                 1536, 2560])
def test_flash_kernels_hold_no_sequence_in_vmem(chips, as_on_tpu, seq):
    """What a program holds is a tile of each operand and its accumulators:
    the scoped VMEM the compiler gives the forward and backward kernels
    does not grow with the sequence (the first kernel's grew until the
    compiler refused it between 8k and 16k)."""
    x = jax.ShapeDtypeStruct((1, 2, seq, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(chips[0]))
    text = _flash_program(True).lower(x, x, x).compile().as_text()
    assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                          text)) == 3


# ------------------------------------------------------------ serving steps

def _serving_step(chips, program, C=16, preset="gpt2-125m", **widths):
    """The donated step program of `serve/llm.LLMEngine` at a preset's
    widths (125M unless told), lowered for one described chip on the tree
    the engine holds (`gpt2.resident_params`). Returns (lowered, cfg,
    (B, T))."""
    B, T = 8, 1024
    one = SingleDeviceSharding(chips[0])
    cfg = gpt2.GPT2Config.preset(preset, max_seq_len=T, **widths)
    params = _on(one, jax.eval_shape(
        lambda: gpt2.resident_params(
            gpt2.init_params(jax.random.key(0), cfg), cfg)))
    cache = _on(one, jax.eval_shape(lambda: gpt2.init_cache(cfg, B, T)))

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # as serve/llm.LLMEngine jits them: the cache is donated
    if program == "decode_step":
        fn = jax.jit(lambda p, c, t, pos, a:
                     gpt2.decode_step(p, c, t, pos, a, cfg),
                     donate_argnums=(1,))
        args = (params, cache, arr((B,), jnp.int32), arr((B,), jnp.int32),
                arr((B,), jnp.bool_))
    else:
        fn = jax.jit(lambda p, c, t, pos0, n, a:
                     gpt2.prefill_chunk(p, c, t, pos0, n, a, cfg),
                     donate_argnums=(1,))
        args = (params, cache, arr((B, C), jnp.int32), arr((B,), jnp.int32),
                arr((B,), jnp.int32), arr((B,), jnp.bool_))
    return fn.lower(*args), cfg, (B, T)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_serving_step_compiles_at_125m_widths(chips, as_on_tpu, program):
    compiled = _serving_step(chips, program)[0].compile()
    assert _per_device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("tp", [2, 4])
def test_tensor_parallel_decode_step_writes_a_shard_each(chips, as_on_tpu,
                                                         tp):
    """The decode step as `serve/llm.LLMEngine` jits it with
    `tensor_parallel_size` 2 or 4, at 125M widths over chips of the 2x2:
    the weights by their specs, the cache's heads over `tp`, the placement
    GSPMD's. The TPU's compiler partitions no Pallas call, so
    `gpt2._decode_write` and, since PR 61, `gpt2._decode_attend` (the loop's
    one call) run their kernels inside a `shard_map`, each chip on its own
    6 or 3 heads: no leaf is gathered, copied or re-laid on the way."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import (MeshConfig, build_mesh, traced_on,
                                       use_mesh)

    B, T = 8, 1024
    cfg = gpt2.GPT2Config.preset("gpt2-125m", max_seq_len=T)
    mesh = build_mesh(MeshConfig(tp=tp), devices=chips[:tp])
    with use_mesh(mesh):
        specs = gpt2.resident_specs(cfg)
    shapes = jax.eval_shape(lambda: gpt2.resident_params(
        gpt2.init_params(jax.random.key(0), cfg), cfg))
    params = jax.tree.map(lambda a, spec: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh, spec)), shapes, specs)
    heads, rep = NamedSharding(mesh, P(None, None, "tp")), \
        NamedSharding(mesh, P())
    cache = _on(heads, jax.eval_shape(lambda: gpt2.init_cache(cfg, B, T)))
    compiled = jax.jit(
        traced_on(mesh, lambda p, c, t, pos, a: gpt2.decode_step(
            p, c, t, pos, a, cfg)), donate_argnums=(1,),
        out_shardings=(rep, {"k": heads, "v": heads})).lower(
            params, cache, *_on(rep, (
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_)))).compile()
    hlo = compiled.as_text()
    L, H, Dh = cfg.n_layer, cfg.n_head // tp, cfg.head_dim
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', hlo)) == 3
    assert not _written_arrays(hlo, f"{L},{B},{cfg.n_head},({T},{Dh}|{Dh},{T})")
    written = _written_arrays(hlo, f"{L},{B},{H},({T},{Dh}|{Dh},{T})")
    assert {op for op, _ in written} <= {
        "parameter", "get-tuple-element", "tuple", "while", "bitcast",
        "custom-call"}, written
    assert _per_device_bytes(compiled) < 709_166_080 * (1 / tp + 0.2)


def _written_arrays(hlo: str, dims: str, dtype: str = "bf16",
                    entry: bool = None) -> list:
    """(operation, type) of every instruction outside a fused computation
    whose result holds a `<dtype>[<dims>]` (`dims` a regular expression):
    what the program materialises. Inside a fusion a slice or a broadcast
    of that shape is only read. `entry` True keeps the entry computation's
    instructions alone, False those of every other (a loop's body and
    condition)."""
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    holds = re.compile(r"%s\[(?:%s)\]" % (dtype, dims))
    found, skip = [], False
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            skip = head.group(2) in fused or (
                entry is not None and entry != bool(head.group(1)))
        m = re.match(r"\s+(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(", line)
        if m and not skip and holds.search(m.group(1)):
            found.append((m.group(2), m.group(1)))
    return found


# the parent of PR 24 scanned the cache in and out of the loop over the
# layers: its programs rewrote every layer's cache whole and copied the
# whole cache besides. At these shapes its compiled programs held 2 `copy`,
# 2 `fusion` and 2 AllocateBuffer `custom-call` of the whole
# bf16[12,8,12,1024,64] and, in the loop, 6 `copy`, 3 `fusion` and a
# `copy-start`/`copy-done` of a layer's bf16[1,8,12,1024,64] or
# bf16[8,12,1024,64] (the decode program; the chunk program's alike), and
# cost_analysis() counted 2.42 (decode), 2.82 (chunk of 16) and 2.97 GB
# (chunk of 128) accessed against 0.80 GB of weights and cache; PR 24: 0.75,
# 0.81 and 1.43 GB (a chunk of 128 reads and writes [8,12,128,1024] scores).
# Until PR 26 the programs took float32 weights and converted all of them
# in every step; on the resident tree (the matrices bf16 once, the float32
# table only gathered from) they hold 0.71 GB and access 0.45, 0.51 and
# 0.68 GB: 0.64, 0.72 and 0.97 of what they hold, where PR 24's were 0.94,
# 1.01 and 1.79. PR 56's decode program wrote its rows after the loop, a
# window all the layers deep a slot a leaf (cost_analysis() counts a loop's
# body once, so its count rose to 0.59 GB at these shapes though the step
# moved no more). Since PR 58 the rows go in through `ops/rows_write.py`, one
# Pallas call a leaf on the leaf's own bytes viewed [.., Dh, T]: no window,
# no `dynamic-update-slice`, and nothing of a call's traffic in
# cost_analysis(): 0.44 GB, 0.61 of what the program holds
@pytest.mark.parametrize("program,C,most", [
    ("decode_step", 0, 0.7), ("prefill_chunk", 16, 0.9),
    ("prefill_chunk", 128, 1.2)],
    ids=["decode_step", "prefill_chunk-16", "prefill_chunk-128"])
def test_serving_step_updates_the_cache_in_place(chips, as_on_tpu, program,
                                                 C, most):
    lowered, cfg, (B, T) = _serving_step(chips, program, C)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    L, H, Dh = cfg.n_layer, cfg.n_head, cfg.head_dim
    layer = f"{B},{H},{T},{Dh}"
    whole = _written_arrays(hlo, f"{L},{layer}")
    handed_on = {"parameter", "get-tuple-element", "tuple", "while",
                 "bitcast"}
    if program == "decode_step":
        # the loop only reads the leaves it closes over, each viewed
        # [.., Dh, T] (a bitcast: the chip holds [.., T, 64] with T on the
        # lanes) for its one kernel, the attention's (PR 61); after it each
        # is viewed so again, written by one kernel in place, and viewed back
        assert {op for op, _ in whole} <= handed_on, whole
        view = _written_arrays(hlo, f"{L},{B},{H},{Dh},{T}")
        assert sorted(op for op, _ in view) == [
            "bitcast", "bitcast", "bitcast", "bitcast", "custom-call",
            "custom-call"], view
        assert len(re.findall(r'custom_call_target="tpu_custom_call"',
                              hlo)) == 3
        # T stays the minor-most axis, Dh the next: one layout
        assert {re.search(r"\](\{[\d,]*)", t).group(1) for _, t in view} \
            == {"{4,3,2,1,0"}, view
        # and no slot's scores over all T positions are written
        assert not _written_arrays(hlo, f"{B},{H},{T}", "f32")
        assert not _written_arrays(hlo, f"{B},{H},{Dh},{T}")
    else:
        # the chunk program's windows are written in the loop over the
        # layers, which carries the leaves
        updates = [op for op, _ in _written_arrays(hlo, f"{L},{layer}",
                                                   entry=False)]
        assert updates.count("dynamic-update-slice") == 2 * B, updates
        assert {op for op, _ in whole} <= handed_on | {
            "dynamic-update-slice"}, whole
        # a window per slot and cache is written into the carry
        assert sum(op == "dynamic-update-slice" for op, _ in whole) == 2 * B
    # one layout from the argument to the result: nothing re-lays the cache
    assert len({re.search(r"\[%s\](\{[^}]*\})" % f"{L},{layer}",
                          t).group(1) for _, t in whole}) == 1, whole
    # and no copy of one layer's cache is made on the way to the scores
    assert not _written_arrays(hlo, layer)
    assert not _written_arrays(hlo, "1," + layer)
    accessed = compiled.cost_analysis()["bytes accessed"]
    held = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(lowered.args_info))
    assert accessed < most * held, (accessed, held)


# what an instruction may do with a stack of the layers' weights without
# writing one: hand the argument into the loop
_HANDED_ON = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


@pytest.mark.parametrize("program,C", [
    ("decode_step", 0), ("prefill_chunk", 128)],
    ids=["decode_step", "prefill_chunk-128"])
@pytest.mark.parametrize("widths", [
    dict(preset="gpt2-125m"),
    dict(preset="gpt2-1.5b", vocab_size=50304)],     # the benchmark's
    ids=["125m", "xl"])
def test_serving_step_converts_no_weights(chips, as_on_tpu, program, C,
                                          widths):
    """On the tree the engine holds, a step makes no bf16 copy of any
    [n_layer, ...] stack of weights (until PR 26 eight `convert`s a step,
    hoisted out of the loop: 13.6 ms of a 31.6 ms decode step at XL) and
    neither transposes, converts nor copies the table for the logits: the
    unembedding is read where the caller put it."""
    lowered, cfg, _ = _serving_step(chips, program, C, **widths)
    hlo = lowered.compile().as_text()
    # (not a stack of weights: a slot's new rows of all layers, [n_layer, H,
    # Dh], which the decode program slices out of its loop's stacked output)
    stacks = _written_arrays(hlo, r"%d,(?!%d,%d\])\d+(,\d+)?" % (
        cfg.n_layer, cfg.n_head, cfg.head_dim))
    assert stacks and {op for op, _ in stacks} <= _HANDED_ON, stacks
    V, D = cfg.vocab_size, cfg.d_model
    # (at 125M the compiler prefetches it into fast memory while the loop
    # runs, `copy-start`/`copy-done` in the layout it has: that is a read)
    unembed = _written_arrays(hlo, f"{D},{V}|{V},{D}")
    assert {op for op, _ in unembed} <= {"parameter", "copy-start",
                                         "copy-done"}, unembed
    # the float32 table is only gathered from. At 1600 wide the chip's
    # own layout of [V, 1600] has the vocabulary along the lanes (1600 is
    # no multiple of 128), and the gather has it copied row-major first:
    # 322 MB a step, `copy.4` in the traces since PR 22 (PERF.md, PR 26)
    table = [op for op, _ in _written_arrays(hlo, f"{V},{D}", "f32")]
    relaid = ["copy"] if D % 128 else []
    assert sorted(table) == sorted(["parameter"] + relaid), table


# GPT-2 XL's two serving programs as the serving cells run them (8 slots of
# 1,024 positions, chunks of 128, the padded vocabulary), per device: the
# parent of PR 29 compiled to these bytes, and to the same instructions but
# for the table of file names and line numbers (PR 29 changed the engine
# and the pool around them, not them). The decode program is PR 58's: its
# rows go into the cache after the loop through two calls of
# `ops/rows_write.py` on the leaves' own bytes, where PR 56's held a pair of
# blended windows [48,1,25,128,64] (6,315,223,552 B; 6,314,675,200 before
# PR 56, which wrote in the loop): 0.81 MB less (6,314,417,152 B). Since PR
# 61 the loop's attention is a call of `ops/gqa_attend.py` on the same views
# of the leaves: 32,256 B more, the kernel's operands a slot (q, the own
# rows) for PR 58's float32 scores [8,25,1024] and probabilities. A leaf
# copied would show as 1.26 GB more. The chunk program is still PR 29's
# parent's
GPT2_XL_SERVING_BYTES = {"decode_step": 6_314_449_408,
                         "prefill_chunk": 6_314_743_808}


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_gpt2_xl_serving_programs_are_the_parents(chips, as_on_tpu, program):
    lowered, _, _ = _serving_step(chips, program, 128, preset="gpt2-1.5b",
                                  vocab_size=50304)
    assert _per_device_bytes(lowered.compile()) == \
        GPT2_XL_SERVING_BYTES[program]


# Kanana's chunk program since PR 39: the decode program's work on every
# slot's first lane and the further lanes only of the slots that prefill. The
# configuration file is the benchmark's and keeps the all-lanes form's
# 15,700,020,736 B (`memory.prefill_chunk_bytes_by_chunk_size`) and a
# `decode_step_bytes` of 11,773,044,224 until a `benchmark` issue, so the
# pins are this file's own. Since PR 41 every slot's first lane attends
# through the `mla_attend` kernel (one in the dense layer's body, one in the
# expert layers'): the chunk program lost the first lanes' scores. Since PR
# 43 the slots that prefill are `lm.each_slot`'s, a loop of as many turns.
# Since PR 48 the layers' loop closes over the stack of every layer's routed
# experts and slices none out of it: the decode program's temporaries were a
# copy of one matrix at a time (0.40 GB of its 11,773,834,752 B) and are the
# kernels' operands now (3.8 MB), the chunk program held all three across
# the loop over the slots (1.21 GB of its 12,785,953,792 B). Since PR 60 a
# layer's MLP takes every valid lane of a chunk step in one call
# (`lm.all_lanes`): the chunk program holds the experts' three kernels once
# and no longer a slot's float32 rows by expert beside the first lanes'
# (11,574,284,800 B until then, 49,668,608 more)
KANANA_CHUNK_BYTES = 11_524_616_192
KANANA_DECODE_BYTES = 11_371_212_288


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_kanana_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-kanana-docqa`'s two programs, as its configuration
    file has them (Kanana-2-30B-A3B's widths, 1 + 7 layers, 32 slots of
    4,096 positions, chunks of 128): room for the prefix pool beside the
    larger, no copy of a cache leaf or of a layer of one, and **none of a
    routed expert matrix**, a layer's [128, d, F] or the stack's
    [896, d, F]: until PR 48 the scan sliced a layer's three out of the
    stack and the compiler copied each for the kernels, 22.5 of a decode
    step's 37.5 ms (PERF.md, PR 48). The decode program: the
    experts' three grouped-matmul kernels in the loop's body and the
    `mla_attend` kernel in both bodies (the dense layer's and the loop's:
    `made_of` counts every Pallas kernel under the older name), no float32
    scores `[32, 32, 1, 4096]` written. The chunk program: the same
    kernels and no more, whatever the number of slots that prefill (their
    further lanes are rows of the first lanes' call); no array
    over all 32 x 128 lanes' scores `[32,32,128,4096]` (4.33 GB of
    temporaries before PR 39), and temporaries of a fifth of a gigabyte,
    one slot's scores and the lanes' float32 stream."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_kanana_for_v5e import (CONFIG, compile_step, made_of,
                                        pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    assert sized["arguments"] >= 0.6 * HBM_BYTES
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert sized["total"] + pool_bytes(config) <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    d, heads = config["deployment"], config["model"]["num_attention_heads"]
    assert d["max_batch"] == heads == 32     # the two 32s below
    # every slot's first lane: its scores never leave the kernel
    assert _written_arrays(hlo, "32,32,(?:1,)?4096", "f32") == []
    if program == "decode":
        assert sized["total"] == KANANA_DECODE_BYTES \
            < memory["decode_step_bytes"]
        assert sized["temp"] < 2 ** 22
        assert made_of(hlo, config) == {
            "grouped_matmul_kernels": 3 + 2, "cache_copies": [],
            "expert_weight_copies": []}
        return
    chunk = str(config["deployment"]["prefill_chunk_size"])
    assert sized["total"] == KANANA_CHUNK_BYTES \
        < memory["prefill_chunk_bytes_by_chunk_size"][chunk]
    assert sized["temp"] < 2 ** 28
    assert _written_arrays(hlo, f"32,32,{chunk},4096", r"\w+") == []
    assert _written_arrays(hlo, f"1,32,{chunk},4096", "f32")     # one slot's
    # no whole leaf is written anywhere: a slot that prefills writes its
    # window where the leaf lies, as the first lanes do
    assert made_of(hlo, config) == {
        "grouped_matmul_kernels": 3 + 2, "cache_copies": [],
        "expert_weight_copies": []}


# Brumby's and granite's chunk programs since PR 43: `lm.each_slot` turns
# over the slots that have lanes, where a loop over all slots held a
# conditional each. The configuration files are the benchmark's and keep the
# older form's bytes (13,163,250,176 and 13,507,884,032) until a `benchmark`
# issue, so the pins are this file's own, and no larger
BRUMBY_CHUNK_BYTES = 13_159_184_896
# granite's since PR 63: every slot's first lane attends through
# `ops/gqa_attend.py` (the decode program's temporaries were 39,895,040 B of
# its 13,389,182,976, where one attention layer's float32 scores
# [48, 8, 4, 8192] shared the head's pieces' space; the chunk program needed
# 13,457,487,872, 258,048 fewer, all of it temporaries)
GRANITE_DECODE_BYTES = 13_351_506_944
GRANITE_DECODE_TEMP_BYTES = 2_219_008
GRANITE_CHUNK_BYTES = 13_457_745_920


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_brumby_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-brumby-fewshot`'s two programs, as its configuration
    file has them (Brumby-14B-Base's widths, 8 layers, 16 slots of 275 MB of
    float32 state, chunks of 64): the bytes the file gives, room for the
    pool's four snapshots beside the larger; the decode program one Pallas
    kernel in the layers' loop and no second copy of the state, of a layer
    of it or of a slot's worth (its temporaries are 35 MB, a layer's
    expanded queries and keys, against 4.4 GB of state: the kernel writes
    the leaf where it reads it); the chunk program no kernel, no copy of the
    leaf, and temporaries under two slots' state."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_brumby_for_v5e import (CONFIG, compile_step, made_of,
                                        pool_bytes, program_bytes,
                                        state_bytes_per_slot)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    want = (memory["decode_step_bytes"] if program == "decode" else memory[
        "prefill_chunk_bytes_by_chunk_size"][
            str(config["deployment"]["prefill_chunk_size"])])
    if program == "prefill":
        assert BRUMBY_CHUNK_BYTES <= want
        want = BRUMBY_CHUNK_BYTES
    assert sized["total"] == want
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 16 * 64 * 4 * 2)   # the chunk's tokens
    assert sized["arguments"] >= 0.7 * HBM_BYTES
    slot = state_bytes_per_slot(config)
    assert slot == memory["state_bytes_per_slot"] == 274_759_680
    assert pool_bytes(config) == memory["prefix_pool_bytes"] == 4 * slot
    assert sized["total"] + pool_bytes(config) <= 0.95 * HBM_BYTES
    assert sized["total"] + pool_bytes(config) >= 12e9
    assert sized["temp"] < 2 * slot
    if program == "decode":
        assert sized["temp"] == memory["decode_step_temp_bytes"] < slot // 4
    assert made_of(compiled.as_text(), config) == {
        "retention_kernels": int(program == "decode"),
        "state_copies": [], "layer_copies": []}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_granite_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-granite-docgen`'s two programs, as its configuration
    file has them (granite-4.0-h-micro whole: 36 Mamba-2 and 4 attention
    layers, 48 slots of 77 MB of float32 state and 8,192 positions of rows,
    chunks of 64): under the bytes the file gives, room for the pool of both
    kinds beside the larger; five Pallas kernels in both programs (the
    state's update in each of the two Mamba runs' loop bodies, a row's write
    for keys and for values and the one `gqa_attend` in the attention layer:
    a chunk's first lane is the decode program's); **no float32 scores of
    every slot's first lane over all 8,192 positions, `[48, 8, 4, 8192]`,
    nor their probabilities in bf16: they stay in VMEM**; no instruction
    copies a cache leaf (the kernels alias the SSM state and take `k` and
    `v` whole with the layer's index, the layers' loops carry the four
    leaves) or materialises one layer's state for all slots; the decode
    program's temporaries are 2 MB, the chunk program's under a quarter of a
    gigabyte: neither computes the padding of 48 x 64 lanes."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_granite_for_v5e import (CONFIG, cache_bytes, compile_step,
                                         made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    if program == "decode":
        assert sized["total"] == GRANITE_DECODE_BYTES \
            < memory["decode_step_bytes"]
    else:
        assert sized["total"] == GRANITE_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][
                str(config["deployment"]["prefill_chunk_size"])]
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 48 * 64 * 4 * 2)   # the chunk's tokens
    assert sized["arguments"] >= 0.75 * HBM_BYTES
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 77_377_536, "kv_bytes_per_token": 8192}
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert sized["total"] + pool_bytes(config) <= 0.95 * HBM_BYTES
    if program == "decode":
        assert sized["temp"] == GRANITE_DECODE_TEMP_BYTES \
            < memory["decode_step_temp_bytes"] // 16
    else:
        assert sized["temp"] < 2 ** 28
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum("/kv_update/" in c and "rows_write" in c for c in calls) == 2
    assert sum("/gqa_attend/" in c for c in calls) == 1
    assert sum("/ssm_update/" in c for c in calls) == 2
    for dtype in ("f32", "bf16"):
        assert _written_arrays(hlo, "48,8,4,8192", dtype) == []
    assert made_of(hlo, config) == {
        "kernels": 5, "leaf_copies": {}, "ssm_layer_copies": []}


@pytest.mark.parametrize("T,slots,block", [
    (4096, 8, 256), (16384, 8, 1024), (131072, 2, 1024)],
    ids=["a-16th", "the-longest-block", "the-models-own-length"])
def test_granites_decode_program_compiles_at_other_lengths_of_leaf(
        chips, as_on_tpu, T, slots, block):
    """`gqa_attend.block_last`'s rule away from the two lengths that were
    timed: the TPU's compiler takes the decode program at a 16th of a
    shorter leaf, at `slot_rows.BLOCK` where a 16th passes it, and at
    granite-4.0-h-micro's own 131,072 positions (blocks of 1,024: VMEM holds
    them, a 16th it could not), the attention one kernel and no float32
    scores over all T written. How fast, no run has said (PERF.md §7)."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_granite_for_v5e import CONFIG, compile_step
    from ray_tpu.ops.gqa_attend import block_last

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    config["deployment"].update(max_seq_len=T, max_batch=slots)
    assert block_last(T) == block
    hlo = compile_step(config, chips, "decode").as_text()
    assert sum("/gqa_attend/" in c for c in _mosaic_calls(hlo)) == 1
    assert _written_arrays(hlo, f"{slots},8,4,{T}", "f32") == []


# Kimi's programs since PR 42: every slot's first lane attends through the
# `mla_attend` kernel (PR 41: the decode program's temporaries are no longer
# one MLA layer's float32 scores for all slots, 168 MB of the file's
# `decode_step_temp_bytes` 174,842,368, but the head's pieces), and an expert
# layer's routed SwiGLU is one `expert_mlp` kernel (PR 42: the rows' pieces
# and both pieces' float32 products `[2048, 1024]`, `[2048, 1024]`,
# `[2048, 2304]` are no temporaries any more; with the three grouped matmuls
# the programs needed 13,642,395,648 / 31,733,760 and 14,075,437,568). The
# configuration file is the benchmark's and keeps PR 40's bytes
# (13,785,504,256 and 14,075,276,800) until a `benchmark` issue. Since PR 60
# the chunk program's MLPs take every valid lane of the step in one call
# (`lm.all_lanes`): one `expert_mlp` an expert layer's body where the
# further lanes had their own (14,072,986,624 B until then, 229,888 fewer)
KIMI_DECODE_BYTES = 13_639_474_176
KIMI_DECODE_TEMP_BYTES = 28_812_288
KIMI_CHUNK_BYTES = 14_073_216_512


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_kimi_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-kimi-longgen`'s two programs, as its configuration
    file has them (Kimi-Linear-48B-A3B's widths, layers 1-9 with 64 of 256
    experts a layer and a quarter of the vocabulary, 128 slots of 15.7 MB of
    float32 state and 10,240 positions of latent rows, chunks of 128): the
    bytes the file gives, room for the pool of both kinds beside the larger;
    the Pallas kernels (the delta rule's update in the dense layer's body
    and in the KDA expert layers', one `expert_mlp` in each of the two
    expert bodies, `mla_attend` in the MLA body: 5 in both programs, a slot
    that prefills adds rows to an expert layer's call and no call; the chunk
    program's chunked delta rule and its attention over one slot's rows are
    no kernels); no float32 scores `[128, 32, 1, 10240]` of every slot's
    first lane written, and none of the experts' products of both pieces of
    a call's rows, `[2048 | 4096, F]` or `[.., d]`: g, u and h stay in VMEM;
    no instruction copies a cache leaf (the kernel aliases the state, the layers' loops
    carry the four leaves, and no layer's kind is a branch: a loop a kind
    that turns as often as the run is long or not at all) or materialises
    one layer's state for all slots; **none materialises an expert matrix**,
    one layer's [64, d, F] or the stack's [512, d, F] (ROADMAP S12a: the
    kernel reads the stack where it lies); the decode program's
    temporaries are the head's pieces, the chunk program's under half a
    gigabyte: neither computes the padding of 128 x 128 lanes."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_kimi_for_v5e import (CONFIG, cache_bytes, compile_step,
                                      made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(config["deployment"]["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == KIMI_DECODE_BYTES \
            < memory["decode_step_bytes"]
    else:
        assert sized["total"] == KIMI_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][chunk]
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 128 * 128 * 4)     # the chunk's tokens
    assert sized["arguments"] >= 0.75 * HBM_BYTES
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 15_712_256, "kv_bytes_per_token": 2304}
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert sized["total"] + pool_bytes(config) <= 0.95 * HBM_BYTES
    if program == "decode":
        assert sized["temp"] == KIMI_DECODE_TEMP_BYTES \
            < memory["decode_step_temp_bytes"] // 5
    else:
        assert sized["temp"] < 2 ** 29
    hlo = compiled.as_text()
    assert _written_arrays(hlo, "128,32,(?:1,)?10240", "f32") == []
    # both pieces of a call's rows by expert: 128 x 8 in the decode program,
    # 256 x 8 in the chunk program (whose rows by expert, one piece, are the
    # `[2048, 2304]` that `moe._experts` sorts)
    pieces = "20(?:48|32)" if program == "decode" else "40(?:96|64)"
    assert _written_arrays(hlo, pieces + ",(?:1024|2304)", "f32") == []
    # one call an expert layer's body in both programs (an MLA and a KDA
    # body): a slot that prefills adds rows to it, not a call
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in _mosaic_calls(hlo)) == 2
    assert made_of(hlo, config) == {
        "kernels": 5, "leaf_copies": {},
        "kda_layer_copies": [], "expert_matrix_copies": []}


# The cell `serve-solar-longctx`'s two programs, from
# rehearse/compile_solar_for_v5e.py. Since PR 52 every slot's first lane
# attends through `ops/gqa_attend.py`: the decode program's temporaries were
# 814,899,712 B, the two pieces' float32 scores over all 25,600 positions and
# their probabilities, and the chunk program held the same beside its own.
# The configuration file is the benchmark's and keeps PR 49's bytes
# (12,181,509,632 and 12,353,619,968) until a `benchmark` issue
SOLAR_DECODE_BYTES = 11_394_551_296
SOLAR_DECODE_TEMP_BYTES = 27_941_376
# since PR 60 the chunk program's MLPs take every valid lane of the step in
# one call (`lm.all_lanes`): 11,888,031,232 B until then, 54,536,192 more
SOLAR_CHUNK_BYTES = 11_833_495_040


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_solar_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-solar-longctx`'s two programs, as its configuration
    file has them (Solar-Open2-250B's widths, one period of layers with 40 of
    320 experts a layer and an eighth of the vocabulary, 40 slots of 13.5 MB
    of float32 state and 25,600 positions of keys and values by head, chunks
    of 128): under the bytes the file gives, and room for the pool of both
    kinds beside the larger, between 70% and 95% of the chip; the Pallas
    kernels (the two `rows_write` and the one `gqa_attend` of the softmax
    layer's body, the delta rule's update at 64 heads in the KDA body, one
    `expert_mlp` in each of the two bodies: 6 in both programs, a slot that
    prefills adds rows to an expert layer's call, not a call); no float32
    scores of one slot's 8 x 128 queries over all 25,600 positions (the
    further lanes attend a block at a time) **and none of every slot's first
    lane, `[40, 8, 16 | 8, 25600]`, nor their probabilities in bf16: they
    stay in VMEM**; no instruction copies a cache leaf (the kernel takes `k`
    and `v` whole with the layer's index: no 2.1 GB temporary; they are held
    [.., 8, 25600, 128]: with the positions last the compiler re-laid both
    round every chunk step, and under one scatter for all slots' positions
    round every decode step, 2.1 GB each) or materialises one layer's state
    for all slots or an expert matrix."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_solar_for_v5e import (CONFIG, cache_bytes, compile_step,
                                       made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(config["deployment"]["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == SOLAR_DECODE_BYTES \
            < memory["decode_step_bytes"]
        assert sized["temp"] == SOLAR_DECODE_TEMP_BYTES \
            < memory["decode_step_temp_bytes"] // 25
    else:
        assert sized["total"] == SOLAR_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][chunk]
        assert sized["temp"] < 2 ** 30
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 40 * 128 * 4)      # the chunk's tokens
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 13_467_648, "kv_bytes_per_token": 4096}
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert 0.70 * HBM_BYTES <= SOLAR_CHUNK_BYTES + pool_bytes(config) \
        <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == 2
    assert sum("/kv_update/" in c and "rows_write" in c for c in calls) == 2
    assert sum("/gqa_attend/" in c for c in calls) == 1
    assert sum("/kda_update/" in c for c in calls) == 1
    for dtype in ("f32", "bf16"):
        assert _written_arrays(hlo, "40,8,(?:16|8),25600", dtype) == []
    assert made_of(hlo, config) == {
        "kernels": 6, "whole_slot_scores": [], "leaf_copies": {},
        "kda_layer_copies": [], "expert_matrix_copies": []}


# The chunk programs of the three cells below since PR 60: a layer's
# token-wise half takes every valid lane of the step in one call
# (`lm.all_lanes`, `lm.pack_lanes`). The configuration files are the
# benchmark's and keep the by-slot form's bytes until a `benchmark` issue:
# Nemotron's 13,516,041,728 (96,768 more), K-EXAONE's 13,927,469,056 (774,656
# more) and LongCat's 14,963,080,704, 267,887,104 fewer: a call's rows by
# expert are 256 x 12 of 6,144 float32 where a slot's and the first lanes'
# were 128 x 12 each, and `moe._experts` holds them seven times over
NEMOTRON_CHUNK_BYTES = 13_515_944_960
LONGCAT_CHUNK_BYTES = 15_230_967_808
EXAONE_CHUNK_BYTES = 13_926_694_400


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_nemotron_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-nemotron-reasoning`'s two programs, as its
    configuration file has them (Nemotron-3-Super-120B-A12B's widths, layers
    0-10 with 128 of 512 experts a layer and a quarter of the vocabulary, 128
    slots of 21.6 MB of float32 state and 4,608 positions of keys and values
    by 2 heads, chunks of 128), from rehearse/compile_nemotron_for_v5e.py:
    the bytes the file gives (the chunk program `NEMOTRON_CHUNK_BYTES`),
    with ==, and room for the pool of both kinds
    beside the larger, between 70% and 95% of the chip; the Pallas kernels
    (the state's update at 8 groups in the Mamba-2 body, the two
    `rows_write` and the one `gqa_attend` in the attention body, one
    `expert_mlp` of two matrices in the expert body: 5 in both programs,
    an expert layer of a chunk step is one call whoever prefills); no
    instruction copies a cache leaf (with the SSD form's state
    reshaped to [N, groups, lanes] the compiler re-laid the whole 2.7 GB
    leaf, N last, round every Mamba-2 layer of a chunk step: the form takes
    a group's stretch of lanes at a time) or materialises one layer's state
    for all slots or an expert matrix."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_nemotron_for_v5e import (CONFIG, cache_bytes, compile_step,
                                          made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(config["deployment"]["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == memory["decode_step_bytes"]
        assert sized["temp"] == memory["decode_step_temp_bytes"] < 2 ** 26
    else:
        assert sized["total"] == NEMOTRON_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][chunk]
        assert sized["temp"] < 2 ** 30
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 128 * 128 * 4)     # the chunk's tokens
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 21_585_920, "kv_bytes_per_token": 1024}
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert 0.70 * HBM_BYTES <= memory["prefill_chunk_bytes_by_chunk_size"][
        chunk] + pool_bytes(config) <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == 1
    assert sum("/kv_update/" in c and "rows_write" in c for c in calls) == 2
    assert sum("/gqa_attend/" in c for c in calls) == 1
    assert sum("/ssm_update/" in c for c in calls) == 1
    assert made_of(hlo, config) == {
        "kernels": 5, "whole_slot_scores": [],
        "leaf_copies": {}, "ssm_layer_copies": [],
        "expert_matrix_copies": []}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_longcat_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-longcat-assistant`'s two programs, as its
    configuration file has them (LongCat-Flash-Chat's widths, double layers
    0-3 with 16 of 512 routed experts a layer beside 256 zero-compute
    outputs and an eighth of the vocabulary, 128 slots of 3,072 positions of
    latent rows in 8 sublayers, chunks of 128), from
    rehearse/compile_longcat_for_v5e.py: the bytes the file gives (the
    chunk program `LONGCAT_CHUNK_BYTES`), with ==, and room for the pool
    beside the larger, between 75% and 95% of the chip; the Pallas kernels
    (two `mla_attend` at 64 heads and one `expert_mlp` at d 6,144 x F 2,048
    in the layers' one loop body: 3 in the decode program; in the chunk
    program a layer's experts and its two dense FFNs take a step's lanes in
    one call whoever prefills, cut to one of four numbers of rows, a branch
    each of which a round takes one: `lm.row_buckets`, four `expert_mlp`
    in the text and one run); no instruction copies a cache leaf or one sublayer's
    rows for all slots, an expert matrix or a dense FFN's out of its
    stack."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_longcat_for_v5e import (CONFIG, compile_step,
                                         kv_bytes_per_token, made_of,
                                         pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(config["deployment"]["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == memory["decode_step_bytes"]
        assert sized["temp"] == memory["decode_step_temp_bytes"] < 2 ** 28
    else:
        assert sized["total"] == LONGCAT_CHUNK_BYTES
        assert sized["temp"] < 2 ** 30 + 2 ** 28
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 128 * 128 * 4)     # the chunk's tokens
    assert kv_bytes_per_token(config) == memory["kv_bytes_per_token"] == 9216
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert 0.75 * HBM_BYTES <= LONGCAT_CHUNK_BYTES + pool_bytes(config) \
        <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    buckets = 1 if program == "decode" else 4
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == buckets
    assert sum("/mla_attend/" in c for c in calls) == 2
    if program == "prefill":
        # the branches are one conditional's, of which a round runs one
        assert len(re.findall(r" conditional\(", hlo)) == 2   # two calls
    assert made_of(hlo, config) == {
        "kernels": 2 + buckets, "leaf_copies": {},
        "sublayer_rows_copies": {}, "expert_matrix_copies": [],
        "dense_matrix_copies": []}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_exaone_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-kexaone-hotdocs`'s two programs, as its configuration
    file has them (K-EXAONE-236B-A23B's widths, layers 0-7 L L L G L L L G
    with 8 of 128 experts a sparse layer and an eighth of the vocabulary, 64
    slots of 10,240 positions of keys and values by 8 heads in the two
    global layers and six rings of 128 rows, chunks of 128), from
    rehearse/compile_exaone_for_v5e.py: the bytes the file gives (the chunk
    program `EXAONE_CHUNK_BYTES`), with ==, and room for the pool of both
    kinds beside the larger, between 75% and 95% of the chip; the Pallas kernels, a body a kind of layer (two
    `rows_write` and one attention, `swa_attend` over a ring or `gqa_attend`
    over rows, in each of the three bodies, one `expert_mlp` in the two
    sparse ones: 11 in both programs, a sparse layer of a chunk step is one
    call whoever prefills); no instruction copies a cache leaf,
    rows or rings, or one layer's for all slots, an expert matrix or the
    dense MLP's out of its stack, or writes a chunk's scores over all of a
    slot's positions."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_exaone_for_v5e import (CONFIG, cache_bytes, compile_step,
                                        made_of, pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = str(config["deployment"]["prefill_chunk_size"])
    if program == "decode":
        assert sized["total"] == memory["decode_step_bytes"]
        assert sized["temp"] == memory["decode_step_temp_bytes"] < 2 ** 26
    else:
        assert sized["total"] == EXAONE_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][chunk]
        assert sized["temp"] < 2 ** 30
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 64 * 128 * 4)      # the chunk's tokens
    assert cache_bytes(config) == {
        "state_bytes_per_slot": memory["state_bytes_per_slot"],
        "kv_bytes_per_token": memory["kv_bytes_per_token"]} == {
        "state_bytes_per_slot": 3_145_728, "kv_bytes_per_token": 8192}
    assert pool_bytes(config) == memory["prefix_pool_bytes"]
    assert 0.75 * HBM_BYTES <= memory["prefill_chunk_bytes_by_chunk_size"][
        chunk] + pool_bytes(config) <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == 2
    assert sum("/kv_update/" in c and "rows_write" in c for c in calls) == 6
    assert sum("/swa_attend/" in c for c in calls) == 2
    assert sum("/gqa_attend/" in c for c in calls) == 1
    assert made_of(hlo, config) == {
        "kernels": 11,
        "whole_slot_scores": [], "leaf_copies": {}, "layer_copies": {},
        "expert_matrix_copies": [], "dense_matrix_copies": []}


# The cell `serve-keye-longdoc`'s two programs, from
# rehearse/compile_keye_for_v5e.py. Since PR 54 every slot's first lane
# attends through `ops/dsa_attend.py`: the decode program's temporaries were
# 68,891,648 B (a layer's indexer products for all slots, one leaf's gathered
# rows `[32, 2048, 512]` and the head's pieces in turn) and the chunk
# program's 137,844,736. The configuration file is the benchmark's and keeps
# PR 46's bytes (14,403,657,728 and 14,472,610,816) until a `benchmark`
# issue. Since PR 60 the chunk program's experts take every valid lane of the
# step in one call (`lm.all_lanes`): 14,403,216,896 B until then, 844,288 more
KEYE_DECODE_BYTES = 14_336_121_344
KEYE_DECODE_TEMP_BYTES = 1_355_264
KEYE_CHUNK_BYTES = 14_402_372_608


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_keye_serving_programs_compile_at_the_configurations_sizes(
        chips, as_on_tpu, program):
    """The cell `serve-keye-longdoc`'s two programs, as its configuration
    file has them (Keye-VL-2.0-30B-A3B's language model at depth 6 of 48,
    all 128 experts a layer, the whole vocabulary, 32 slots of 13,312
    positions of three leaves a token, chunks of 128): under the bytes the
    file gives and no more temporaries than before the kernel, and room for
    the pool of all three leaves beside the larger; the Pallas kernels (one
    `dsa_attend` and one `expert_mlp` in the layers' loop, and the chunk
    program's second `expert_mlp` for the further lanes; the indexer and
    the choice are XLA's); **no gather of the chosen rows, `bf16[65536,
    512]`, nor the copy laid out by head, `[32, 2048, 4, 128]`, nor their
    scores `[32, 4, 8, 2048]`: the rows go through VMEM where they lie**;
    no instruction copies a cache leaf (`k`, `v` or `ik`, whole or a layer
    of it: the layers' loop carries the three and writes rows in place,
    and the kernel's call carries none through VMEM and back, as Solar's
    `conv` leaf was at a kernel limit of 64 MB); none copies an expert
    matrix out of the stack, a layer's [128, d, F] or the whole [768, d,
    F] (Kanana's form until PR 48 did); and the decode program writes no
    float32 `[32, 32, 13312]` array of every slot's scores over every
    position."""
    import json

    chip_dir, _ = _chip_bench()
    from compile_keye_for_v5e import (CONFIG, compile_step,
                                      kv_bytes_per_token, made_of,
                                      pool_bytes, program_bytes)

    with open(os.path.join(chip_dir, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    memory = config["memory"]
    compiled = compile_step(config, chips, program)
    sized = program_bytes(compiled)
    chunk = config["deployment"]["prefill_chunk_size"]
    if program == "decode":
        assert sized["total"] == KEYE_DECODE_BYTES \
            < memory["decode_step_bytes"]
        assert sized["temp"] == KEYE_DECODE_TEMP_BYTES \
            < memory["decode_step_temp_bytes"] // 25
    else:
        assert sized["total"] == KEYE_CHUNK_BYTES < memory[
            "prefill_chunk_bytes_by_chunk_size"][str(chunk)]
        assert sized["temp"] < 2 ** 27
    assert sized["arguments"] == memory["arguments_bytes"] + (
        0 if program == "decode" else 32 * chunk * 4)    # the chunk's tokens
    assert sized["arguments"] >= 0.75 * HBM_BYTES
    assert kv_bytes_per_token(config) == memory["kv_bytes_per_token"] \
        == 6 * (2 * 4 * 128 + 64) * 2 == 13_056
    assert pool_bytes(config) == memory["prefix_pool_bytes"] \
        == 416 * 128 * 13_056
    assert sized["total"] + pool_bytes(config) <= 0.95 * HBM_BYTES
    hlo = compiled.as_text()
    calls = _mosaic_calls(hlo)
    assert sum(c.endswith("/moe_experts/expert_mlp/pallas_call")
               for c in calls) == 1
    assert sum("/attn/dsa_attend/" in c for c in calls) == 1
    for dtype in ("bf16", "f32"):
        assert _written_arrays(
            hlo, "65536,512|32,2048,(?:512|4,128)|32,4,8,(?:1,)?2048",
            dtype) == []
    assert made_of(hlo, config) == {
        "kernels": 2, "leaf_copies": {},
        "expert_matrix_copies": [], "dense_scores": []}


def _pools_programs(config: dict, chips):
    """(leaf, its shape in the cell's cache, the pool's, the plan's, the
    pool's (copy_out, copy_in) that loop over a plan's blocks) for every
    rows leaf of the cell whose configuration file this is, all on the
    described chip."""
    from ray_tpu.models import serving_family
    from ray_tpu.serve.kv_cache import PagedKVCache

    _, spec = _chip_bench()
    d = config["deployment"]
    _, model, _ = serving_family(d["preset"])
    family = spec.family(config["family"])
    # GPT-2's family takes the file's `model` block, the later ones the file
    cfg = family.program_config(
        config["model"] if config["family"] == "gpt2" else config)
    cache = jax.eval_shape(lambda: model.init_cache(
        cfg, d["max_batch"], d["max_seq_len"]))
    kv = PagedKVCache(1, 1, 1, num_blocks=1, block_size=1)  # any: a maker
    one = SingleDeviceSharding(chips[0])
    for name, axis in model.CACHE_TOKEN_AXIS.items():
        leaf = cache[name]
        block = list(leaf.shape)
        block[1], block[axis] = 1, d["kv_block_size"]
        pool = [d["kv_blocks"] if i == 1 else n for i, n in enumerate(block)]
        most = leaf.shape[axis] // d["kv_block_size"]
        yield (name,
               jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one),
               jax.ShapeDtypeStruct(tuple(pool), leaf.dtype, sharding=one),
               jax.ShapeDtypeStruct((3 + 2 * most,), jnp.int32, sharding=one),
               lambda loop, block=tuple(block), axis=axis:
               kv._copy_programs(block, axis, loop))


@pytest.mark.parametrize("config_name,apart", [
    ("gpt2-xl-serve-1chip", True),
    ("kanana-2-30b-a3b-serve-1chip", False),
    ("keye-vl-2.0-30b-a3b-serve-1chip", False),
    ("solar-open2-250b-serve-1chip", False)])
def test_the_prefix_pools_programs_move_blocks_in_place(chips, config_name,
                                                        apart):
    """`serve/kv_cache.py`'s two programs a rows leaf, at the three
    rows-only pools' and caches' sizes from their configuration files: the
    loop over a plan's blocks writes each into the donated carry in place (no
    instruction writes a whole leaf or a whole pool but a window into the
    carry), its temporaries a few blocks' bytes and not a leaf's.

    GPT-2's pool by head is the exception the pool reads off its arrays
    (`_laid_apart`): the chip lays `[48, 128, 25, 16, 64]` out with the 128
    blocks along the lanes (16 x 64 is less than a tile) and the cache with
    its positions there, the loop that reads such a pool is compiled with a
    copy of the whole pool in the cache's layout (2.5 GB, eight times the
    pool's bytes: it would not fit beside the cell's 15.5 GB), and so its
    blocks move a call each, by the loop's body alone, as PR 46's did (a
    block padded to 128 lanes: 315 MB of temporaries, as then)."""
    import json

    chip_dir, _ = _chip_bench()
    with open(os.path.join(chip_dir, "configs", config_name + ".json")) as f:
        config = json.load(f)
    in_place = {"parameter", "get-tuple-element", "tuple", "while",
                "dynamic-update-slice", "bitcast"}
    for name, leaf, pool, plan, programs in _pools_programs(config, chips):
        dims = "|".join(",".join(map(str, a.shape)) for a in (leaf, pool))
        a_block = math.prod(pool.shape) // pool.shape[1] * 2
        copy_out, copy_in = programs(True)
        out = copy_out.lower(pool, leaf, plan).compile()
        back = copy_in.lower(leaf, pool, plan).compile()
        cache_order, pool_order = (
            f.layout.major_to_minor for f in back.input_formats[0][:2])
        assert (cache_order != pool_order) == apart, (name, cache_order,
                                                      pool_order)
        if apart:
            assert back.memory_analysis().temp_size_in_bytes > \
                math.prod(leaf.shape) * 2, name     # what the loop would cost
            copy_out, copy_in = programs(False)
            out = copy_out.lower(pool, leaf, plan).compile()
            back = copy_in.lower(leaf, pool, plan).compile()
        for compiled, whole in ((out, pool), (back, leaf)):
            hlo = compiled.as_text()
            written = _written_arrays(hlo, dims)
            # the one window into the carry, alone or fused with the slice
            # it writes (a fusion that ends in a window writes in place)
            assert {op for op, _ in written} <= in_place | {"fusion"}, (
                name, written)
            assert sum(op in ("dynamic-update-slice", "fusion")
                       for op, _ in written) == 1, (name, written)
            m = compiled.memory_analysis()
            assert m.alias_size_in_bytes == math.prod(whole.shape) * 2
            assert m.temp_size_in_bytes < (
                130 if apart else 4) * a_block, (name, m)


@pytest.mark.parametrize("rows,F,tiles", [
    (1024, 1024, None), (1016, 1024, None), (1024, 1024, (128, 32, 256)),
    (1024, 1280, (256, 64, 512)), (1024, 1280, None)],
    ids=["kimi", "rows-that-end-inside-a-block", "smaller-tiles",
         "F-that-ends-inside-a-tile", "F-in-two-tiles-of-640"])
def test_expert_mlp_kernel_reads_the_stack_where_it_lies(chips, rows, F,
                                                         tiles):
    """`ops/expert_mlp.py` alone at Kimi's widths, rows and stack (float32
    rows, the 8 x 64 held experts' bf16 matrices, the groups' sizes with the
    one past the stack's end): Mosaic accepts the blocks (the whole
    contraction over 2,304 lanes in one, a last block of rows or of F that
    hangs over the end), it is one kernel, and the program holds nothing
    beside its arguments and its result but the plan's few kilobytes: no
    slice or copy of a layer's matrices, no pieces, no products."""
    op = importlib.import_module("ray_tpu.ops.expert_mlp")
    assert (op.TILE_ROWS, op.SUB_ROWS, op.TILE_F) == (256, 64, 512)
    one = SingleDeviceSharding(chips[0])
    D, G = 2304, 512

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda *a: op.expert_mlp(*a, tiles=tiles)).lower(
        arr((rows, D), jnp.float32), arr((G, D, F)), arr((G, D, F)),
        arr((G, F, D)), arr((G + 1,), jnp.int32),
        arr((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_expert_mlp_kernel_at_solars_widths(chips):
    """d = 4,096 and F = 1,280 with the stack of 4 x 40 held experts and a
    decode step's 320 rows: the column tile is 640 (two steps, no overhang),
    its three matrices' double buffers 31.5 MB of VMEM, and Mosaic takes
    them beside the rows' and the output's blocks."""
    op = importlib.import_module("ray_tpu.ops.expert_mlp")
    one = SingleDeviceSharding(chips[0])
    D, F, G, rows = 4096, 1280, 160, 320
    assert op._tiles(rows, D, F, 2) == (256, 64, 640)

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(op.expert_mlp).lower(
        arr((rows, D), jnp.float32), arr((G, D, F)), arr((G, D, F)),
        arr((G, F, D)), arr((G + 1,), jnp.int32),
        arr((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_expert_mlp_kernel_at_longcats_widths(chips):
    """d = 6,144 and F = 2,048, the widest row the kernel meets, with the
    stack of 4 x 16 held experts and a decode step's 1,536 pairs: the column
    tile is 512 (four steps an expert), its three matrices' double buffers
    37.7 MB of `WEIGHT_TILES_BYTES` 40, and Mosaic takes them beside the
    float32 rows' and output's blocks of 256 rows, 6.3 MB each, under
    `VMEM_LIMIT_BYTES`."""
    op = importlib.import_module("ray_tpu.ops.expert_mlp")
    one = SingleDeviceSharding(chips[0])
    D, F, G, rows = 6144, 2048, 64, 1536
    assert op._tiles(rows, D, F, 2) == (256, 64, 512)
    assert 2 * 3 * D * 512 * 2 <= op.WEIGHT_TILES_BYTES

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(op.expert_mlp).lower(
        arr((rows, D), jnp.float32), arr((G, D, F)), arr((G, D, F)),
        arr((G, F, D)), arr((G + 2,), jnp.int32),
        arr((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_mla_attend_kernel_at_64_heads(chips):
    """`ops/mla_attend.py` at LongCat's head count and cache shape, 8
    sublayers x 128 slots x 3,072 positions: `[64, 1024]` float32 scores and
    a `[64, 512]` accumulator a grid step fit beside the blocks' buffers
    under the kernel's 32 MB, and the program holds nothing beside its
    arguments."""
    op = importlib.import_module("ray_tpu.ops.mla_attend")
    assert slot_rows.block_of(3072) == 1024
    one = SingleDeviceSharding(chips[0])
    L, B, T = 8, 128, 3072

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda *a: op.mla_attend(
        *a, 192 ** -0.5, kernel=True)).lower(
        arr((B, 64, 512)), arr((B, 64, 64)), arr((L, B, T, 512)),
        arr((L, B, T, 64)), arr((), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("L,B,T,block", [
    (2, 128, 10240, 1024), (8, 32, 4096, 1024), (2, 8, 1000, 256)],
    ids=["kimi", "kanana", "a-ragged-last-block"])
def test_mla_attend_kernel_reads_the_leaves_where_they_lie(
        chips, monkeypatch, L, B, T, block):
    """`ops/mla_attend.py` alone at the published head count and widths and
    the two cells' cache shapes: Mosaic accepts the blocks (a length that no
    whole block divides too), and the program holds nothing beside its
    arguments: the rotary key's leaf, which the compiler keeps with the
    positions on the lanes, is handed over as it lies, not copied into 128
    padded lanes a position (671 MB a call at Kimi's shape)."""
    op = importlib.import_module("ray_tpu.ops.mla_attend")
    assert slot_rows.BLOCK == 1024
    monkeypatch.setattr(slot_rows, "BLOCK", block)
    assert slot_rows.block_of(T) == block
    one = SingleDeviceSharding(chips[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda *a: op.mla_attend(
        *a, 192 ** -0.5, kernel=True)).lower(
        arr((B, 32, 512)), arr((B, 32, 64)), arr((L, B, T, 512)),
        arr((L, B, T, 64)), arr((), jnp.int32), arr((B,), jnp.int32),
        arr((B,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("T,block", [(13312, 1024), (13312, 512),
                                     (13312, 2048), (13000, 1024)],
                         ids=["the-cells", "half", "a-ragged-last-block",
                              "a-length-no-block-divides"])
def test_dsa_attend_kernel_reads_the_leaves_where_they_lie(chips, T, block):
    """`ops/dsa_attend.py` alone at Keye's cell's shape, 32 slots x 13,312
    positions of 4 x 128 lanes a leaf and 8 queries a head: Mosaic accepts a
    block of positions of both leaves whole (1 MB each at 1,024), a head's
    keys as 128 of the block's 512 lanes where they lie, products of 8
    query rows, the mask's block `[1, block]` of int32 and a last block
    that hangs over the leaf's end, inside `VMEM_LIMIT_BYTES`; and the
    program holds nothing beside its arguments but the mask as int32: no
    layer of a leaf is sliced out (436 MB each), no row is gathered."""
    op = importlib.import_module("ray_tpu.ops.dsa_attend")
    assert slot_rows.BLOCK == 1024 and slot_rows.block_of(13312) == 1024
    assert slot_rows.VMEM_LIMIT_BYTES == 32 * 2 ** 20
    one = SingleDeviceSharding(chips[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda q, ck, cv, keep, *slots: slot_rows.attend(
        op.rows_kernel(q, ck, cv, keep, 128 ** -0.5), *slots,
        block=block)).lower(
        arr((32, 4, 8, 128)), arr((6, 32, T, 512)), arr((6, 32, T, 512)),
        arr((32, T), jnp.bool_), arr((), jnp.int32), arr((32,), jnp.int32),
        arr((32,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes <= 32 * T * 4 \
        + 2 ** 16


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32-q-two-pieces", "bf16-q-one-piece"])
@pytest.mark.parametrize("T,block", [(25600, 1024), (25600, 2048)],
                         ids=["whole-blocks", "a-ragged-last-block"])
def test_gqa_attend_kernel_reads_the_leaves_where_they_lie(
        chips, monkeypatch, T, block, q_dtype):
    """`ops/gqa_attend.py` alone at Solar's cell's shape, 40 slots x 8
    key-value heads x 25,600 positions of 128 lanes and 8 queries a head:
    Mosaic accepts a block of all 8 heads of both leaves (2 MB each at
    1,024 positions), the batched products of 16 (or 8) query rows, the two
    pieces' concatenation and a last block that hangs over the leaf's end,
    inside `VMEM_LIMIT_BYTES`; and the program holds nothing beside its
    arguments: no layer of a leaf is sliced out (2.1 GB each)."""
    op = importlib.import_module("ray_tpu.ops.gqa_attend")
    assert slot_rows.BLOCK == 1024 and slot_rows.block_of(T) == 1024
    one = SingleDeviceSharding(chips[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(lambda q, ck, cv, *slots: slot_rows.attend(
        op.rows_kernel(q, ck, cv, 128 ** -0.5), *slots, block=block)).lower(
        arr((40, 8, 8, 128), q_dtype), arr((1, 40, 8, T, 128)),
        arr((1, 40, 8, T, 128)), arr((), jnp.int32), arr((40,), jnp.int32),
        arr((40,), jnp.bool_)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("q_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32-q-two-pieces", "bf16-q-one-piece"])
def test_gqa_attend_kernel_takes_a_ring_leaf_at_the_exaone_cells_shape(
        chips, as_on_tpu, q_dtype):
    """`ops/gqa_attend.py` and `ops/rows_write.py` with `ring=True` at the
    cell `serve-kexaone-hotdocs`'s shape, 64 slots x 8 key-value heads x a
    ring of 128 rows of 128 lanes in 6 sliding layers: Mosaic accepts the
    one block a slot (a square leaf: nothing is read off its shape), under
    the name `swa_attend`; and neither program holds anything beside its
    arguments: no layer of a leaf is sliced out."""
    op = importlib.import_module("ray_tpu.ops.gqa_attend")
    write = importlib.import_module("ray_tpu.ops.rows_write")
    one = SingleDeviceSharding(chips[0])

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    ring = arr((6, 64, 8, 128, 128))
    slots = (arr((), jnp.int32), arr((64,), jnp.int32), arr((64,), jnp.bool_))
    compiled = jax.jit(lambda q, ck, cv, *slots: op.gqa_attend(
        q, ck, cv, *slots, 128 ** -0.5, ring=True)).lower(
        arr((64, 8, 8, 128), q_dtype), ring, ring, *slots).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 1 and "swa_attend" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
    compiled = jax.jit(lambda c, layer, val, pos, on: write.rows_write(
        c, layer, val, pos, on, ring=True), donate_argnums=(0,)).lower(
        ring, slots[0], arr((64, 8, 128)), *slots[1:]).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_token_selection_compiles_at_xl_vocabulary(chips):
    """`serve/sampling.select_tokens` over the serving cells' [8, 50304]
    logits: one program whose sort is in a branch of a conditional, and
    which needs no more of the chip than a few copies of the logits."""
    from ray_tpu.serve.sampling import select_tokens

    B, V = 8, 50304
    one = SingleDeviceSharding(chips[0])

    def program(logits, prev, produce, temperature, top_k, top_p, step):
        key = jax.random.fold_in(jax.random.key(0), step)
        return select_tokens(logits, prev, produce, temperature, top_k,
                             top_p, key)

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = jax.jit(program).lower(
        on((B, V), jnp.float32), on((B,), jnp.int32), on((B,), bool),
        on((B,), jnp.float32), on((B,), jnp.int32), on((B,), jnp.float32),
        on((), jnp.uint32)).compile()
    hlo = compiled.as_text()
    assert re.search(r"\bconditional\(", hlo)
    entry = hlo[hlo.index("ENTRY"):]
    assert not re.search(r"\bsort\(", entry.split("\n}")[0])
    assert re.search(r"\bsort\(", hlo)
    assert _per_device_bytes(compiled) < 32 * B * V * 4


# --------------------------------------------------------------- train step

def _compile_train_step(train, batch, seq):
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(train.init_fn, jax.random.key(0)),
        train.state_sharding)
    data = {"tokens": jax.ShapeDtypeStruct((batch, seq + 1), jnp.int32,
                                           sharding=train.batch_sharding)}
    return train.step_fn.lower(state, data).compile()


def _head_products(text: str, vocab: int = 50304) -> list:
    """(result, operands) of every product (a `dot`, or the `convolution`
    the TPU compiler makes of one) with a vocabulary-wide operand or
    result: the passes of the vocabulary head over the step's tokens. A
    loop's body is in the text once, whatever its trip count."""
    shapes = dict(re.findall(r"(%[\w.\-]+) = (\w+\[[\d,]*\])", text))
    found = []
    for res, args in re.findall(
            r"= (\w+\[[\d,]*\])\S* (?:dot|convolution)\(([^)]*)\)", text):
        ops = [shapes.get(a.strip().split(" ")[-1], "?")
               for a in args.split(",")]
        if any(re.search(rf"\b{vocab}\b", s) for s in [res, *ops]):
            found.append((res, ops))
    return found


def _cell_step(chips, config_name: str):
    """(a training cell's step as its configuration file has it, compiled
    for the described chips; the file)."""
    chip_dir, spec = _chip_bench()
    config = spec.load_json(os.path.join(chip_dir, "configs",
                                         config_name + ".json"))
    n = math.prod(config["job"]["mesh"].values())
    prog = spec.family(config["family"]).build_train(
        config["model"], config["job"], chips[:n], 0)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(prog.program.init_fn, jax.random.key(0)),
        prog.program.state_sharding)
    return prog.compile_step(state), config


def _mosaic_calls(text: str) -> list:
    """The name stacks of the step's Mosaic kernels."""
    calls = re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
    return calls + re.findall(
        r'op_name="([^"]*)"[^\n]*custom_call_target="tpu_custom_call"', text)


@pytest.mark.parametrize("axes,n_chips", [({}, 1), ({"dp": 2, "tp": 2}, 4)],
                         ids=["1chip", "dp2tp2"])
def test_125m_train_step_compiles(chips, as_on_tpu, axes, n_chips):
    """The smoke's step: T=1024 resolves to the flash kernels (forward in
    the layers' loop; dq and dk/dv in its transpose, and under `dots` no
    second forward: the residuals are saved by name), inside `shard_map`
    under a mesh, and no `[B, H, 1024, 1024]` tensor exists anywhere in
    the program, at a global batch that fits either layout."""
    batch, seq = 16, 1024
    mesh = build_mesh(MeshConfig(**axes), devices=chips[:n_chips])
    cfg = gpt2.GPT2Config.preset("gpt2-125m", max_seq_len=seq, remat=True,
                                 remat_policy="dots")
    train = compile_gpt2_train(cfg, mesh,
                               optimizer=default_optimizer(total_steps=100))
    compiled = _compile_train_step(train, batch, seq)
    text = compiled.as_text()
    calls = _mosaic_calls(text)
    assert len(calls) == 3 and all("/attn/" in c for c in calls), calls
    assert all(("shard_map" in c) == (n_chips > 1) for c in calls), calls
    assert not re.search(r"\[\d+,\d+,%d,%d\]" % (seq, seq), text)
    assert _per_device_bytes(compiled) < HBM_BYTES
    assert "nvoluntary full rematerialization" not in text
    if n_chips > 1:
        assert re.search(r"\ball-reduce(-start)?\(", text)


# The training cells' steps with the kept attention kernel (PR 31) and the
# fused loss (PR 34). The configuration files, which are the benchmark's,
# still record 16,623,250,432 for OLMoE (the first kernel's `lse` and
# `delta` were `[B, H, T, 1]` operands of the Mosaic calls, padded to 128
# lanes in HBM; PR 31's step needed 16,490,938,368), 14,682,482,176 for
# GPT-2 small (dense attention; 16,215,508,480 with the kernels' residuals
# saved by name and whole float32 logits) and 14,074,273,792 for GPT-2 XL.
OLMOE_STEP_BYTES = 16_269_134_336
SMALL_STEP_BYTES = 12_672_434_688
XL_STEP_BYTES = 11_712_688_128


def test_gpt2_xl_fsdp4_step_compiles_with_the_kernel_in_shard_map(
        chips, as_on_tpu):
    """The cell `train-xl-fsdp4-1k`'s step as its configuration file has
    it (GPT-2 XL whole, fsdp=4, global batch 32, remat `full`) for the
    described v5e:2x2: the compiler cannot partition a Mosaic kernel, so
    each device runs it on its 8 sequences' 25 heads through `shard_map`;
    `full` runs the forward kernel again in the backward pass (4 calls);
    no `[., ., 1024, 1024]` tensor; and a chip needs less than the dense
    step's 14.07 GB, which the file records (11.71 GB, PRs 31 and 34). A
    chip's 8 x 1,024 tokens are one chunk of the fused loss: the head's
    three products as before (d(head) in the two halves the partitioner
    makes of it), each chip's logits its own and no more."""
    compiled, config = _cell_step(chips, "gpt2-xl-train-fsdp4")
    text = compiled.as_text()
    products = _head_products(text)
    assert len(products) == 4 and sum(
        "[8,1024,50304" in res for res, _ in products) == 1, products
    assert not re.search(r"\[(16|32),1024,50304", text)
    calls = _mosaic_calls(text)
    assert len(calls) == 4 and all(
        c.endswith("attn/shard_map/pallas_call") for c in calls), calls
    assert not re.search(r"\[\d+,\d+,1024,1024\]", text)
    assert re.search(r"\ball-gather(-start)?\(", text)
    assert _per_device_bytes(compiled) == XL_STEP_BYTES < config["memory"][
        "step_program_bytes_compiled_for_v5e"] == 14_074_273_792


def test_gpt2_small_step_holds_a_chunk_of_logits(chips, as_on_tpu):
    """The cell `train-small-1k`'s step as its configuration file has it
    (batch 20, T=1024, remat `dots`): the fused loss takes the sequence in
    chunks, so no `[20, 1024, 50304]` array exists in any dtype (the parent
    wrote one in float32, 4.1 GB of its 16.2, and read it back in the
    backward pass), the head is passed over three times, and the step
    needs SMALL_STEP_BYTES where the file, which is the benchmark's, still
    records the dense step's 14.68 GB."""
    compiled, config = _cell_step(chips, "gpt2-small-train-1chip")
    text = compiled.as_text()
    assert not re.search(r"\[20,1024,50304", text)
    assert len(_head_products(text)) == 3
    calls = _mosaic_calls(text)
    assert len(calls) == 3 and all("/attn/" in c for c in calls), calls
    assert _per_device_bytes(compiled) == SMALL_STEP_BYTES < config["memory"][
        "step_program_bytes_compiled_for_v5e"]


def test_olmoe_train_step_compiles_at_the_published_widths(chips, as_on_tpu):
    """The cell `train-olmoe-4k`'s step, as its configuration file has it
    (OLMoE-1B-7B's widths, depth 1, 8 sequences of 4,096): it fits the
    chip and fills four fifths of it, in no more than the file records;
    attention is the three Pallas flash kernels (T=4096 resolves to them)
    and the experts are nine grouped matmul kernels (three products
    forward, d-lhs and d-rhs of each backward); no `[., 4096, 64, .]`
    one-hot dispatch tensor and no dense product of an expert's width
    exists; and the vocabulary head is passed over three times (a chunk's
    logits, d(x), d(head): the parent's checkpointed scan made the logits
    again in its backward pass, four), with no `[8, 4096, 50304]` array."""
    _chip_bench()
    from compile_olmoe_for_v5e import CONFIG, made_of

    compiled, config = _cell_step(chips, CONFIG)
    total = _per_device_bytes(compiled)
    text = compiled.as_text()
    products = _head_products(text)
    assert len(products) == 3, products
    assert not re.search(r"\[8,4096,50304", text)
    assert total == OLMOE_STEP_BYTES
    assert total <= config["memory"]["step_program_bytes_compiled_for_v5e"]
    assert 0.8 * HBM_BYTES <= total < HBM_BYTES
    assert made_of(text, config["model"], config["job"]["seq_len"]) == {
        "flash_kernels": 3, "grouped_matmul_kernels": 9,
        "one_hot_dispatch_tensors": 0, "dense_expert_products": 0}


@pytest.mark.parametrize("axes", [dict(ep=4), dict(dp=2, ep=2),
                                  dict(fsdp=2, tp=2)],
                         ids=["ep4", "dp2ep2", "fsdp2tp2"])
def test_moe_train_step_compiles_under_a_mesh(chips, as_on_tpu, axes):
    """The compiler refuses to partition the grouped-matmul kernel on its
    own, so under a mesh `moe._experts_on_mesh` runs it per device through
    shard_map against that device's shard of the experts: all nine kernels
    (three products forward, d-lhs and d-rhs of each) are in the step,
    and so are attention's three."""
    from ray_tpu.models import moe
    from ray_tpu.train.spmd import compile_model_train

    cfg = moe.MoEConfig.preset(
        "olmoe-1b-7b", n_layer=1, d_model=512, n_head=4, n_kv_head=4,
        d_ff=256, n_experts=8, experts_per_token=2, vocab_size=1024,
        max_seq_len=512, remat=False)
    mesh = build_mesh(MeshConfig(**axes), devices=chips)
    train = compile_model_train(moe, cfg, mesh,
                                optimizer=default_optimizer(total_steps=10))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(train.init_fn, jax.random.key(0)),
        train.state_sharding)
    data = {"tokens": jax.ShapeDtypeStruct((8, 513), jnp.int32,
                                           sharding=train.batch_sharding)}
    calls = _mosaic_calls(train.step_fn.lower(state, data).compile()
                          .as_text())
    assert sum(c.endswith(("jit(gmm)/pallas_call", "jit(tgmm)/pallas_call"))
               for c in calls) == 9, calls
    # T=512 resolves to the flash kernels, per device through shard_map
    assert sum(c.endswith("attn/shard_map/pallas_call")
               for c in calls) == 3, calls
    assert len(calls) == 12, calls


def test_flash_attention_compiles_under_a_mesh(chips, as_on_tpu):
    """The compiler refuses to partition a Mosaic kernel on its own, so
    under dp2·tp2 the models call it per (batch, heads) shard through
    shard_map; `auto` resolves to it wherever its tiles divide T."""
    from jax.sharding import NamedSharding

    from ray_tpu.parallel.mesh import logical_to_spec, use_mesh

    mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=chips)
    assert lm.resolve_attn_impl(gpt2.GPT2Config().attn_impl, 2048) == "flash"
    with use_mesh(mesh):
        spec = logical_to_spec("batch", "heads", None, None)
        x = jax.ShapeDtypeStruct((4, 12, 2048, 64), jnp.bfloat16,
                                 sharding=NamedSharding(mesh, spec))
        step = jax.jit(jax.grad(
            lambda q, k, v: flash.flash_attention_on_mesh(q, k, v)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2)))
        assert "tpu_custom_call" in step.lower(x, x, x).compile().as_text()


def test_pipeline_step_compiles_with_the_tpu_lowering(chips, as_on_tpu):
    """pp2·dp2 over four chips through `pipeline_apply`'s TPU side — the
    bf16 shard_map boundary the CPU backend cannot take."""
    mesh = build_mesh(MeshConfig(pp=2, dp=2), devices=chips)
    cfg = gpt2.GPT2Config.preset("gpt2-125m", max_seq_len=256, remat=True,
                                 n_layer=4, attn_impl="dense")
    train = compile_pipeline_train(
        gpt2, cfg, mesh, n_microbatches=4,
        optimizer=default_optimizer(total_steps=100))
    text = _compile_train_step(train, 8, 256).as_text()
    assert re.search(r"\bcollective-permute(-start)?\(", text)
    # the stage outputs are psum'd across pp in the compute dtype
    assert re.search(r"bf16\[[^\]]*\]\S* all-reduce(-start)?\(", text)
