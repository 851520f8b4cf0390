"""The Brumby family file on the CPU: its configuration against the
published one, its reference against a per-token recurrence written here,
its arithmetic against hand counts, the traffic file, the check of what was
served (the window's route, each limit alone), the readers of the new scopes
and counters on hand-made records, and the cell end to end at a tiny size."""

import ast
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import trace_reduce as tr
from conftest import CHIP_DIR, REPO
from families import brumby as family
from generators import closed_loop_documents
from harness import spec
from metrics import _retention_scopes, _scopes
from test_hot_path_metrics import DEVICE, _msg, _plane
from test_kanana_family import DECODE

PUBLISHED = {   # manifestai/Brumby-14B-Base config.json (the catalog's row)
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
CONFIG = spec.load_json(os.path.join(CHIP_DIR, "configs",
                                     "brumby-14b-serve-1chip.json"))
TRAFFIC = spec.load_json(os.path.join(CHIP_DIR, "traffic",
                                      "fewshot-generation.json"))
CELL = "serve-brumby-fewshot"
TINY = {"vocab_size": 512, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "hidden_size": 64,
        "intermediate_size": 128, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "hidden_act": "silu", "attention_bias": False}


# ------------------------------------------------------------ configuration

def test_the_configuration_is_the_published_one_less_depth():
    changed = {k for k in PUBLISHED if CONFIG["model"].get(k) != PUBLISHED[k]}
    assert changed == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert set(CONFIG["model"]) == set(PUBLISHED)
    assert {k: CONFIG[k] for k in PUBLISHED} == CONFIG["model"]
    assert CONFIG["model"]["num_hidden_layers"] == 8
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    assert (CONFIG["kind"], CONFIG["family"]) == ("serve", "brumby")
    assert CONFIG["deployment"] == {
        "preset": "brumby-14b", "max_seq_len": 4096, "max_batch": 16,
        "scheduler": "continuous", "enable_prefix_caching": True,
        "prefill_chunk_size": 64, "kv_blocks": 4, "kv_block_size": 128}
    # every assumption the issue lists has its reason written down
    assert {"degree", "gate", "gate_bias", "normaliser", "qk_norm_and_rope",
            "state_dtype", "state_layout", "weights"} <= set(CONFIG["assumed"])
    assert any("switch-over" in d for d in CONFIG["departures"])
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["name"] == "Brumby-14B-Base"]
    assert row["config"] == PUBLISHED and row["source_url"] == CONFIG["source"]


def test_the_compiled_programs_hold_twelve_gigabytes_and_leave_room():
    memory = CONFIG["memory"]
    chip = memory["chip_bytes_limit"]
    assert chip == 16_909_336_064
    chunk = memory["prefill_chunk_bytes_by_chunk_size"][
        str(CONFIG["deployment"]["prefill_chunk_size"])]
    held = chunk + memory["prefix_pool_bytes"]
    assert 12e9 <= held <= 0.95 * chip
    assert memory["decode_step_bytes"] < chunk
    assert memory["decode_step_temp_bytes"] < 2 ** 26   # no copy of the state
    slot = memory["state_bytes_per_slot"]
    assert slot == 8 * 8 * 129 * 8320 * 4 == 274_759_680
    assert memory["prefix_pool_bytes"] == 4 * slot
    assert family.state_bytes_per_slot(CONFIG["model"]) == 272_646_144
    assert slot / 272_646_144 == pytest.approx(8320 / 8256)


def test_the_program_is_built_at_the_published_widths():
    cfg = family.program_config(CONFIG)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.d_ff,
            cfg.queries_per_kv) == (5120, 40, 8, 128, 17408, 5)
    assert (cfg.n_layer, cfg.vocab_size, cfg.max_seq_len) == (8, 151936, 4096)
    assert (cfg.rope_theta, cfg.norm_eps, cfg.retention_eps) == (1e6, 1e-6,
                                                                 1e-6)
    assert family.RETENTION_EPS == cfg.retention_eps
    assert family.CharTokenizer.eos_id == 151643 < cfg.vocab_size
    tok = family.CharTokenizer()
    assert tok.encode(tok.decode([0, 151935, 7])) == [0, 151935, 7]


OWN = {"retention_update_time_pct", "retention_update_roofline_pct",
       "retention_chunk_time_pct", "retention_project_time_pct",
       "state_bytes_per_slot", "engine_attn_time_pct", "engine_mlp_time_pct",
       "engine_head_time_pct", "engine_prefix_pool_time_pct"}


def the_cell_reads_what_it_reads(bench):
    """Holds the cell to what it reads, never to who else reads it nor to
    where in the file its entries stand: later PRs append and join lists
    (`test_a_tenth_cell.py`)."""
    cell = spec.cell(bench, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert {m["name"] for m in cell["end_to_end"]} == {"serve_tokens_per_s",
                                                       "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert DECODE <= names
    # the experts' three can only read null here: the model has none
    assert names.isdisjoint({"moe_router_time_pct.decode",
                             "moe_dispatch_time_pct.decode",
                             "moe_experts_time_pct.decode"})
    assert OWN <= names
    for m in bench["per_layer"]:
        if m["name"] in OWN:
            assert CELL in m["workloads"]
            assert m["moves"] == "serve_tokens_per_s"
    assert {"worker_ready_s", "compile_cache_new"} <= names - OWN - DECODE


def test_the_cell_reads_the_decode_metrics_that_exist_for_it_and_its_own():
    the_cell_reads_what_it_reads(spec.benchmark())


def test_the_traffic_is_the_issues_letter_for_letter():
    assert {k: TRAFFIC[k] for k in (
        "generator", "clients", "requests_per_client", "documents",
        "document_uniform", "document_block", "question_uniform",
        "output_uniform", "schedule_seed", "ramp_s", "reference_sample",
        "trace_at", "trace_seconds")} == {
        "generator": "closed_loop_documents", "clients": 32,
        "requests_per_client": 8, "documents": 4,
        "document_uniform": [1024, 2048], "document_block": 128,
        "question_uniform": [16, 64], "output_uniform": [512, 1024],
        "schedule_seed": 33, "ramp_s": 10.0, "reference_sample": 4,
        "trace_at": 0.4, "trace_seconds": 5.0}
    d = CONFIG["deployment"]
    assert TRAFFIC["clients"] == 2 * d["max_batch"]
    assert TRAFFIC["document_block"] == d["kv_block_size"]
    assert TRAFFIC["documents"] == d["kv_blocks"]
    assert TRAFFIC["question_uniform"][1] <= d["prefill_chunk_size"]
    assert (TRAFFIC["document_uniform"][1] + TRAFFIC["question_uniform"][1]
            + TRAFFIC["output_uniform"][1]) < d["max_seq_len"] - 2


@pytest.mark.parametrize("seed", [1, 2_400_000_123])
def test_the_preambles_the_items_and_the_lengths(seed):
    plan = closed_loop_documents.generate(TRAFFIC, CONFIG, seed, 51.0)
    requests = plan["requests"]
    assert len(requests) == 32 * 8 and plan["clients"] == 32
    assert [r["client"] for r in requests[:33]] == list(range(32)) + [0]
    preambles = {}
    for r in requests:
        n = len(r["prompt_ids"])
        blocks = max(b for b in range(8, 17) if b * 128 <= n - 16)
        assert 16 <= n - blocks * 128 <= 64
        assert 512 <= r["max_tokens"] <= 1024 and r["temperature"] == 0.0
        assert max(r["prompt_ids"]) < 151936
        head = tuple(r["prompt_ids"][:blocks * 128])
        assert preambles.setdefault(r["document"], head) == head
    assert sorted(preambles) == [0, 1, 2, 3]
    per = [sum(r["document"] == d for r in requests) for d in range(4)]
    assert per == [64] * 4                                   # stratified
    # the warm-up: each preamble once, the first twice
    assert len(plan["warmup"]) == 5
    for w, d in zip(plan["warmup"], [0, 1, 2, 3, 0]):
        assert tuple(w["prompt_ids"][:len(preambles[d])]) == preambles[d]
        assert w["max_tokens"] == 2
    # the schedule is the file's, the tokens the seed's
    other = closed_loop_documents.generate(TRAFFIC, CONFIG, seed + 1, 51.0)
    assert [(len(r["prompt_ids"]), r["max_tokens"], r["document"])
            for r in requests] == [
        (len(r["prompt_ids"]), r["max_tokens"], r["document"])
        for r in other["requests"]]
    assert requests[0]["prompt_ids"] != other["requests"][0]["prompt_ids"]


def test_roofline_costs_against_hand_counts():
    m = CONFIG["model"]
    assert family.content_width(m) == 8256
    one = family.retention_update_cost(m, 1.0)
    # a slot and layer: eight heads of S [8256, 128] and z [8256], float32,
    # read and written
    assert one["bytes"] == 8 * 8256 * 129 * 4 * 2 == 68_161_536
    assert one["flops"] == 8 * 8256 * 129 * 13
    step = family.retention_update_cost(m, 16.0)
    assert 8 * step["bytes"] == pytest.approx(8.72e9, rel=2e-3)  # the issue's
    costs = family.roofline_costs(m)
    assert costs == {"retention_layers": 8, "retention_update_per_slot": one,
                     "state_content_bytes_per_slot": 272_646_144}
    assert round(272_646_144 / 1e6, 1) == 272.6


# --------------------------------------------------------------- reference

def tiny_layer(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    d, heads, kv, hd, ff = 64, 4, 2, 16, 128

    def w(*shape, std=0.3):
        return (rng.standard_normal(shape) * std).astype(np.float32)

    return {"attn_norm": {"scale": 1 + w(d, std=0.1)},
            "attn": {"wq": w(d, heads, hd), "wk": w(d, kv, hd),
                     "wv": w(d, kv, hd), "wg": w(d, kv),
                     "bg": rng.uniform(2.0, 5.0, kv).astype(np.float32),
                     "q_norm": {"scale": 1 + w(hd, std=0.1)},
                     "k_norm": {"scale": 1 + w(hd, std=0.1)},
                     "wo": w(heads * hd, d)},
            "mlp_norm": {"scale": 1 + w(d, std=0.1)},
            "mlp": {"wg": w(d, ff), "wu": w(d, ff), "wd": w(ff, d)}}


def layer_by_a_recurrence(x, p, m):
    """The layer in float64 numpy, a token at a time: S and z carried as
    dense [d, d, d_v] and [d, d] sums of k k^T (x) v, no symmetric packing,
    no quadratic form, none of the reference's code."""
    x = np.asarray(x, np.float64)
    p = json.loads(json.dumps(p, default=lambda a: np.asarray(a).tolist()))
    a = {k: (np.asarray(v, np.float64) if not isinstance(v, dict)
             else np.asarray(v["scale"], np.float64))
         for k, v in p["attn"].items()}
    heads, kv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                     m["head_dim"])
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])

    def norm(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale

    def rope(v, t):
        half = hd // 2
        inv = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
        c, s = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([v[..., :half] * c - v[..., half:] * s,
                               v[..., half:] * c + v[..., :half] * s], -1)

    S = np.zeros((kv, hd, hd, hd))
    z = np.zeros((kv, hd, hd))
    out = []
    for t, xt in enumerate(x):
        h = norm(xt, np.asarray(p["attn_norm"]["scale"], np.float64))
        q = rope(norm(np.einsum("d,dhk->hk", h, a["wq"]), a["q_norm"]), t)
        k = rope(norm(np.einsum("d,dhk->hk", h, a["wk"]), a["k_norm"]), t)
        v = np.einsum("d,dhk->hk", h, a["wv"])
        gate = 1.0 / (1.0 + np.exp(-(h @ a["wg"] + a["bg"])))
        y = np.zeros((heads, hd))
        for g in range(kv):
            kk = np.outer(k[g], k[g])
            S[g] = gate[g] * S[g] + kk[:, :, None] * v[g][None, None, :]
            z[g] = gate[g] * z[g] + kk
            for r in range(heads // kv):
                qq = np.outer(q[g * (heads // kv) + r],
                              q[g * (heads // kv) + r])
                y[g * (heads // kv) + r] = np.einsum(
                    "ij,ijv->v", qq, S[g]) / ((qq * z[g]).sum() + 1e-6)
        xt = xt + y.reshape(-1) @ a["wo"]
        h = norm(xt, np.asarray(p["mlp_norm"]["scale"], np.float64))
        mlp = {k: np.asarray(v, np.float64) for k, v in p["mlp"].items()}
        gate_in = h @ mlp["wg"]
        out.append(xt + (gate_in / (1 + np.exp(-gate_in)) * (h @ mlp["wu"]))
                   @ mlp["wd"])
    return np.stack(out)


@pytest.mark.parametrize("seed", [0, 1])
def test_reference_agrees_with_a_per_token_recurrence(seed):
    p = tiny_layer(seed)
    x = np.random.default_rng(seed + 10).standard_normal((19, 64)).astype(
        np.float32)
    got = np.asarray(family.reference_layer(x, p, TINY))
    np.testing.assert_allclose(got, layer_by_a_recurrence(x, p, TINY),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("degrade", family.DEGRADE[1:])
def test_a_degraded_reference_is_another_function(degrade):
    p = tiny_layer(3)
    x = np.random.default_rng(4).standard_normal((40, 64)).astype(np.float32)
    plain = np.asarray(family.reference_layer(x, p, TINY))
    off = np.asarray(family.reference_layer(x, p, TINY, degrade))
    assert np.abs(off - plain).max() > 1e-3
    if degrade == "bfloat16_state":
        # the recurrence it runs is the same sum when nothing is rounded
        family_round, family._through_bfloat16 = (family._through_bfloat16,
                                                  lambda a: a)
        try:
            same = np.asarray(family.reference_layer(x, p, TINY, degrade))
        finally:
            family._through_bfloat16 = family_round
        np.testing.assert_allclose(same, plain, rtol=2e-4, atol=2e-4)
    with pytest.raises(AssertionError):
        family.reference_layer(x, p, TINY, "float8_state")


def test_bfloat16_keeps_seven_bits_of_mantissa():
    import jax.numpy as jnp

    a = jnp.asarray([1.0 + 2.0 ** -7, 1.0 + 2.0 ** -9, 3.14159274, 1e-30])
    got = np.asarray(family._through_bfloat16(a))
    assert got.tolist() == np.asarray(a.astype(jnp.bfloat16),
                                      np.float32).tolist()
    assert got[0] == 1.0 + 2.0 ** -7 and got[1] == 1.0


def test_the_reference_imports_nothing_from_the_program():
    """Its arithmetic is its own: `ray_tpu` appears only where the serving
    half builds the program's config, weights and engine."""
    with open(os.path.join(CHIP_DIR, "families", "brumby.py")) as f:
        tree = ast.parse(f.read())
    reference = {"_rms_norm", "_rope", "_through_bfloat16", "_second_power",
                 "_retention_quadratic", "_retention_recurrent_bfloat16",
                 "reference_layer", "reference_head", "Reference",
                 "retention_update_cost", "content_width",
                 "state_bytes_per_slot"}
    seen = set()
    for node in tree.body:
        name = getattr(node, "name", None)
        if name in reference:
            seen.add(name)
            for sub in ast.walk(node):
                if isinstance(sub, (ast.Import, ast.ImportFrom)):
                    module = getattr(sub, "module", None) or ""
                    names = [a.name for a in sub.names]
                    assert not module.startswith("ray_tpu"), (name, module)
                    assert not any(n.startswith("ray_tpu") for n in names)
    assert seen == reference


# ------------------------------------------------------------ what decides

def tiny_config() -> dict:
    config = json.loads(json.dumps(CONFIG))
    config["model"].update({k: v for k, v in TINY.items()
                            if k in config["model"]})
    config["deployment"].update({
        "preset": "brumby-tiny", "max_seq_len": 128, "max_batch": 4,
        "prefill_chunk_size": 16, "kv_blocks": 3, "kv_block_size": 8})
    return config


@pytest.fixture(scope="module")
def served():
    """What a busy engine served: four greedy replies, prompts of 36-45
    tokens sharing two preambles, through `LLMEngine.generate`."""
    from ray_tpu.serve.llm import LLMEngine

    config = tiny_config()
    rng = np.random.default_rng(7)
    heads = [rng.integers(1, 512, 32).tolist() for _ in range(2)]
    prompts = [heads[i % 2] + rng.integers(1, 512, 4 + 3 * i).tolist()
               for i in range(4)]
    eng = LLMEngine(**family.engine_options(config, 11))
    try:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(4) as pool:
            replies = list(pool.map(lambda p: eng.generate(
                prompt_ids=p, max_tokens=14)["token_ids"], prompts))
        stats = eng.engine_stats()
    finally:
        eng.shutdown()
    assert stats["snapshots_pooled"] >= 2
    return config, [{"id": i, "prompt_ids": p, "token_ids": r}
                    for i, (p, r) in enumerate(zip(prompts, replies))]


def test_check_served_passes_what_a_busy_engine_served_and_refuses_others(
        served):
    config, replies = served
    good = family.check_served(config, 11, replies)
    assert good["ok"] is True and good["tokens_checked"] == 4 * 14
    assert good["served_not_engine_top_share"] <= 0.06
    assert good["engine_logit_mean_abs"] <= family.ENGINE_LOGIT_MEAN_ABS_LIMIT
    assert family.check_served(config, 11, [])["ok"] is False
    # another seed's weights did not choose these tokens
    assert family.check_served(config, 12, replies)["ok"] is False
    # nor did this engine choose another reply's
    swapped = [{**a, "token_ids": b["token_ids"]}
               for a, b in zip(replies, replies[1:] + replies[:1])]
    assert family.check_served(config, 11, swapped)["ok"] is False


def _parents_decode_loop(eng, served, slots, rows):
    """The decode loop of `_engine_logits_together` as it stood before
    PR 45: each step's rows converted before the next step is dispatched."""
    B = eng.max_batch
    at = np.asarray(slots)
    pos = [len(s["prompt_ids"]) for s in served]
    for j in range(max(len(s["token_ids"]) for s in served) - 1):
        tokens, where = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        for i, (slot, s) in enumerate(zip(slots, served)):
            if j < len(s["token_ids"]) - 1:
                tokens[slot], where[slot] = s["token_ids"][j], pos[i] + j
                live[slot] = True
        logits, eng.cache = eng._step(eng.params, eng.cache, tokens, where,
                                      live)
        step = np.asarray(logits[at])
        for i, slot in enumerate(slots):
            if live[slot]:
                rows[i].append(step[i])
    return [np.stack(r) for r in rows]


def test_the_checks_loop_reads_a_step_late_and_returns_the_same_bits(
        served, monkeypatch):
    """The loop dispatches a step before it reads the one before (at most
    two in flight); what it returns is bit for bit what the parent's loop,
    kept above, returns from the same engine state. Replies of different
    lengths, so that a slot goes dead while others still decode."""
    config, replies = served
    replies = [{**r, "token_ids": r["token_ids"][:14 - 3 * i]}
               for i, r in enumerate(replies[:2])]      # half the slots
    new = family.engine_logits(family.stopped_engine(config, 11), replies)
    # the parent's: the same prefill, then its loop in the new one's place
    eng = family.stopped_engine(config, 11)
    short = [{**r, "token_ids": r["token_ids"][:1]} for r in replies]
    first = family.engine_logits(eng, short)       # prefill: no decode step
    old = _parents_decode_loop(eng, replies, [1, 3],
                               [[row[0]] for row in first])
    assert [a.shape for a in new] == [(14, 512), (11, 512)]
    for a, b in zip(new, old):
        assert a.dtype == b.dtype == np.float32 and (a == b).all()
    # the order of the loop, on a step that only writes down what is asked
    # of it: a step is dispatched before the one before it is read, and no
    # more than two are unread at any time
    log = []

    class Rows:
        def __init__(self, j):
            self.j = j

        def __getitem__(self, at):
            return self

        def copy_to_host_async(self):
            log.append(("sent", self.j))

        def __array__(self, dtype=None, copy=None):
            log.append(("read", self.j))
            return np.zeros((2, 512), np.float32)

    eng = family.stopped_engine(config, 11)
    monkeypatch.setattr(eng, "_step", lambda params, cache, *a: (
        log.append(("step", len([e for e in log if e[0] == "step"])))
        or Rows(log[-1][1]), cache))
    family.engine_logits(eng, replies)
    assert [e for e in log if e[0] != "sent"][:5] == [
        ("step", 0), ("step", 1), ("read", 0), ("step", 2), ("read", 1)]
    assert log[-2:] == [("read", 11), ("read", 12)]
    unread = 0
    for what, _ in log:
        unread += {"step": 1, "read": -1, "sent": 0}[what]
        assert 0 <= unread <= 2
    assert unread == 0 and sum(e[0] == "sent" for e in log) == 13


def test_the_checks_engine_takes_the_windows_route(served):
    """Prefill of the whole blocks in one slot, a snapshot between two chunk
    steps, a hit copied into another slot, the rest as a chunk, then decode
    through the kernel's program: the pool's counters say so, and the logits
    are those of a plain prefill in one slot."""
    config, replies = served
    eng = family.stopped_engine(config, 11)
    by_route = family.engine_logits(eng, replies[:2])
    assert eng.kv.stats()["prefix_hits"] == 2
    assert eng.kv.stats()["tokens_reused"] == 2 * 32
    assert eng.kv.stats()["blocks_used"] == 2
    plain = family.stopped_engine(config, 11)
    plain.kv = None
    for reply, got in zip(replies[:2], by_route):
        assert got.shape == (14, 512)
        assert got.argmax(axis=-1).tolist() == reply["token_ids"]


def test_each_limit_refuses_alone():
    ok = {"served_not_engine_top_share": 0.01, "engine_logit_mean_abs": 0.005}
    assert family.verdict(ok)["ok"] is True
    assert family.verdict({**ok, "served_not_engine_top_share": 0.07})[
        "ok"] is False
    assert family.verdict({**ok, "engine_logit_mean_abs": 1.01
                           * family.ENGINE_LOGIT_MEAN_ABS_LIMIT})[
        "ok"] is False
    assert family.verdict({"error": "non-finite logits"})["ok"] is False


# ------------------------------------------------------------------ readers

@pytest.mark.parametrize("tf_op,own,old", [
    ("jit(_step)/layers/while/body/closed_call/attn/retention_update/"
     "jit(retention_update)/pallas_call", "retention_update", "attn"),
    ("jit(_step)/layers/while/body/attn/retention_update/div:",
     "retention_update", "attn"),
    ("jit(_chunk)/layers/while/body/attn/retention_chunk/while/body/cond/"
     "branch_1_fun/bcrw,bvw->bcrv/dot_general", "retention_chunk", "attn"),
    ("jit(_step)/layers/while/body/attn/retention_project/ln/mul:",
     "retention_project", "ln"),
    ("jit(_step)/attn/retention_project/weights_cast/convert_element_type:",
     "retention_project", "weights_cast"),
    ("jit(_reset)/kv_update/dynamic_update_slice:", None, "kv_update"),
    ("jit(_copy_in)/prefix_pool/dynamic_update_slice:", None, "prefix_pool"),
    ("jit(_step)/layers/while/body/mlp/dot_general:", None, "mlp"),
    ("retention_update", None, "unscoped"), (None, None, "unscoped")])
def test_where_an_operation_belongs(tf_op, own, old):
    assert _retention_scopes.retention_scope_of(tf_op) == own
    assert _scopes.scope_of(tf_op) == old


STEP_OPS = {         # event -> tf_op; 10 ns each
    "%retention_update.1 = f32[8]{0} custom-call()":
        "jit(_step)/layers/while/body/attn/retention_update/"
        "jit(retention_update)/pallas_call",
    "%fusion.2 = f32[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/retention_update/mul:",
    "%fusion.3 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/attn/retention_project/dot_general:",
    "%fusion.4 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/dot_general:",
    "%fusion.5 = bf16[8]{0} fusion()":
        "jit(_step)/layers/while/body/mlp/mul:",
    "%fusion.6 = f32[8]{0} fusion()": "jit(_step)/unembed_loss/dot_general:",
    "%fusion.7 = f32[8]{0} fusion()":
        "jit(_chunk)/layers/while/body/attn/retention_chunk/dot_general:",
    "%fusion.8 = f32[8]{0} fusion()":
        "jit(_copy_in)/prefix_pool/dynamic_update_slice:"}


@pytest.fixture(scope="module")
def served_record(tmp_path_factory):
    """Two whole executions of `jit__step`, each running every operation of
    `STEP_OPS` for 10 ns, and the counters of a window of 10 steps that
    generated 120 tokens."""
    ops, modules = [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(STEP_OPS)]
    space = _msg((1, _plane(DEVICE, {tr.OPS_LINE: ops,
                                     tr.MODULES_LINE: modules}, STEP_OPS)))
    d = tmp_path_factory.mktemp("brumby_trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    peaks = spec.peaks()["TPU v5 lite"]
    # so that a step's least time comes out at 8 ns: 12 slots x 8 layers
    costs = {"retention_layers": 8, "retention_update_per_slot": {
        "bytes": 8e-9 * peaks["hbm_bytes_per_s"] / 96, "flops": 1.0}}
    return {"trace_dir": str(d), "peaks": peaks, "counters": {
        "before": {"engine_steps": 100, "total_generated": 1000},
        "after": {"engine_steps": 110, "total_generated": 1120,
                  "state_bytes_per_slot": 274_759_680,
                  "roofline_costs": costs}}}


@pytest.mark.parametrize("name,want", [
    ("retention_update_time_pct", 25.0), ("retention_project_time_pct", 12.5),
    ("retention_chunk_time_pct", 12.5),
    ("engine_attn_time_pct", 50.0),
    ("engine_mlp_time_pct", 25.0),
    ("engine_head_time_pct", 12.5),
    ("engine_prefix_pool_time_pct", 12.5),
    ("kv_update_time_pct.decode", 0.0),
    ("state_bytes_per_slot", 274_759_680),
    # 8 ns of the 20 a step spends under retention_update
    ("retention_update_roofline_pct", 40.0)])
def test_every_new_entry_reads_its_number(served_record, name, want):
    assert spec.metric_reader(name).read(served_record) == pytest.approx(want)


OWN_READERS = ["retention_update_time_pct", "retention_chunk_time_pct",
               "retention_project_time_pct", "retention_update_roofline_pct",
               "state_bytes_per_slot"]


@pytest.mark.parametrize("name", OWN_READERS)
def test_a_program_without_the_scopes_and_counters_reads_as_nothing(
        name, served_record):
    """The parent's engine has neither: None, not 0 and not a crash."""
    parent = {"trace_dir": None, "peaks": served_record["peaks"],
              "counters": {"before": {"engine_steps": 1, "chunk_steps": 0,
                                      "total_generated": 0},
                           "after": {"engine_steps": 9, "chunk_steps": 2,
                                     "total_generated": 90}}}
    assert spec.metric_reader(name).read(parent) is None
    assert spec.metric_reader(name).read({"counters": None}) is None
    assert spec.metric_reader(name).read({}) is None
    # a traced program that has none of the scopes (GPT-2's, Kanana's)
    other = {**served_record, "counters": parent["counters"]}
    if name != "state_bytes_per_slot":
        assert spec.metric_reader(name).read(
            {**parent, "trace_dir": served_record["trace_dir"]}) in (
            None, pytest.approx(spec.metric_reader(name).read(other)))


# --------------------------------------------------- the cell, end to end

def test_the_cell_runs_end_to_end_on_the_cpu_at_a_tiny_size():
    """`rehearse/cpu_cell_brumby.py`: the generator, the warm-up, the
    snapshot hits, the engine's counters and `check_served`, through the
    harness's own phases and readers."""
    out = subprocess.run(
        [sys.executable, os.path.join(CHIP_DIR, "rehearse",
                                      "cpu_cell_brumby.py"),
         "--workload", CELL, "--seconds", "6", "--seed", "2400000123"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 20
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    other = json.loads(out.stderr.split(
        "the other set of metrics:")[1].strip().splitlines()[0])
    assert other["prefix_reuse_pct.decode"]["value"] > 80
    assert other["state_bytes_per_slot"]["value"] == 2 * 2 * 17 * 144 * 4
    assert "'ok': True" in out.stderr and "'tokens_checked'" in out.stderr


def test_a_program_without_the_family_fails_before_any_replica_starts():
    """The parent commit under this benchmark: `build_app` raises in the
    phase's own process, so the command ends at once with an error."""
    import importlib

    import ray_tpu.models as models

    saved = dict(models._SERVING)
    models._SERVING.pop("brumby")
    try:
        with pytest.raises(ValueError, match="brumby-14b"):
            importlib.import_module("ray_tpu.serve.llm").LLMEngine(
                **family.engine_options(CONFIG, 1))
    finally:
        models._SERVING.update(saved)
