"""The readers of what the program itself names: operation metadata
(`_xmeta`), named scopes (`_scopes`), engine phases (`_phases`), the
engine's cumulative counters. On hand-made events, on the trace recorded
on the chip, and, for every entry this added to BENCHMARK.json, on a record
shaped like a traced serving or training run's."""

import os
import struct
import threading
import time

import pytest

import trace_reduce as tr
from conftest import CHIP_DIR
from harness import spec
from metrics import _events, _phases, _scopes, _xmeta, step_hbm_gb

ONE_CHIP = os.path.join(CHIP_DIR, "testdata", "one_chip.xplane.pb")
DEVICE = "/device:TPU:0"


# ------------------------------------------------- on the recorded trace

def test_xmeta_reads_where_an_operation_came_from():
    planes = _xmeta.read(ONE_CHIP)
    (fusion,) = [v for v in planes[DEVICE]["meta"].values()
                 if v["name"].startswith("%fusion.286 ")]
    assert {k: fusion[k] for k in _xmeta.KEPT} == {
        "tf_op": "jit(_step)/jvp()/dot_general:",
        "flops": 2159540224, "bytes_accessed": 28334080}
    assert not any(set(v) & set(_xmeta.KEPT)
                   for v in planes[tr.HOST_PLANE]["meta"].values())


def test_the_one_loader_reads_the_events_profile_data_reads():
    from jax.profiler import ProfileData

    devices, host_lines = _events.load(ONE_CHIP)
    assert list(devices) == [DEVICE]
    planes = {p.name: p for p in ProfileData.from_file(ONE_CHIP).planes}
    lines = {ln.name: ln for ln in planes[DEVICE].lines}
    meta = devices[DEVICE]["meta"]
    for mine, line in (
            ([(s, e, meta[i]["name"]) for s, e, i in devices[DEVICE]["ops"]],
             lines[tr.OPS_LINE]),
            (devices[DEVICE]["modules"], lines[tr.MODULES_LINE]),
            *zip(host_lines, planes[tr.HOST_PLANE].lines)):
        theirs = tr._events(line)
        assert [n for _, _, n in mine] == [n for _, _, n in theirs]
        # ProfileData rounds picoseconds down to whole nanoseconds
        assert all(abs(a[0] - b[0]) < 2 and abs(a[1] - b[1]) < 2
                   for a, b in zip(mine, theirs))


def test_most_of_the_recorded_self_time_carries_a_tf_op():
    devices, host_lines = _events.load(ONE_CHIP)
    assert host_lines
    meta = devices[DEVICE]["meta"]
    named = total = 0.0
    for ident, own in tr.self_intervals(devices[DEVICE]["ops"]):
        total += tr.length(own)
        named += tr.length(own) if meta[ident].get("tf_op") else 0.0
    assert named / total == pytest.approx(0.84, abs=0.005)


def test_a_program_without_scopes_reads_as_nothing_not_as_unscoped():
    # the recording predates the named scopes, as a parent commit does
    record = {"trace_dir": os.path.join(CHIP_DIR, "testdata")}
    assert _events.path_of(record).endswith(".xplane.pb")
    assert _scopes.share(record, "attn") is None
    assert _scopes.share(record, "unscoped") is None
    assert _phases.idle_pct(record) is None
    assert _scopes.share({"trace_dir": None}, "attn") is None
    assert _phases.idle_pct({}) is None and step_hbm_gb.read({}) is None


def test_the_recorded_steps_bytes_by_the_compilers_estimate():
    # three executions of one program: on the file's own picosecond
    # times every operation falls inside its execution, so the sums agree
    devices, _ = _events.load(ONE_CHIP)
    runs = step_hbm_gb.bytes_per_execution(
        _events.walk(ONE_CHIP)[DEVICE]["leaves"], devices[DEVICE]["modules"],
        devices[DEVICE]["meta"])
    assert runs == [846487000] * 3


# ------------------------------------------------------ on hand-made events

@pytest.mark.parametrize("tf_op,want", [
    ("jit(_step)/transpose(jvp(attn))/while/body/dot_general:", "attn"),
    ("jit(_step)/jvp()/while/body/closed_call/attn/weights_cast/"
     "convert_element_type:", "weights_cast"),
    ("jit(_step)/layers/while/body/attn/kv_update/select_n:", "kv_update"),
    ("jit(_step)/layers/while/body/mlp/ln/mul:", "ln"),
    ("jit(_step)/layers/while/body/dynamic_update_slice:", "layers"),
    ("jit(_step)/transpose(jvp(layers))/while/body/add_any:", "layers"),
    ("jit(_step)/jvp(unembed_loss)/reduce_sum:", "unembed_loss"),
    ("jit(_copy_out)/prefix_pool/dynamic_update_slice:", "prefix_pool"),
    ("jit(_step)/optimizer/mul:", "optimizer"),
    # a primitive is never a scope; neither is a longer word
    ("jit(_step)/jvp()/while/body/attn:", "unscoped"),
    ("jit(_step)/jvp(attnx)/mlp_like/add:", "unscoped"),
    ("jit(_step)/transpose(jvp())/while:", "unscoped"),
    ("", "unscoped"), (None, "unscoped")])
def test_scope_of(tf_op, want):
    assert _scopes.scope_of(tf_op) == want


def test_self_time_by_scope_counts_a_loop_less_its_body():
    # operations by the id of their metadata: 3 has none
    ops = [(0, 100, 1), (10, 40, 2), (40, 70, 4), (100, 120, 3),
           (120, 150, 9)]
    meta = {1: {"tf_op": "jit(_step)/layers/while:"},
            2: {"tf_op": "jit(_step)/layers/while/body/attn/dot_general:"},
            4: {"tf_op": "jit(_step)/layers/while/body/mlp/weights_cast/"
                         "convert_element_type:"},
            9: {"tf_op": "jit(_step)/add:"}}
    assert _scopes.self_time_by_scope(_events.walked(ops)["own"], meta) == {
        "layers": 40, "attn": 30, "weights_cast": 30, "unscoped": 50}


def test_idle_by_phase_takes_whole_phases_not_self_time():
    gaps = [[100, 200], [300, 400], [500, 520]]
    host = [(90, 150, "engine.fetch"),          # 50 of the first gap
            (100, 140, "$array.py:631 _value"),  # a frame beneath: ignored
            (150, 190, "engine.sample"),        # 40
            (190, 310, "engine.notify"),        # 10 + 10
            (320, 390, "engine.empty"),         # 70
            (505, 515, "PjitFunction(_step)")]  # not the engine's
    assert _phases.phase_intervals(host)["fetch"] == [[90, 150]]
    assert _phases.idle_by_phase(gaps, host) == {
        "fetch": 50, "sample": 40, "notify": 20, "empty": 70}


def test_leaves_and_bytes_per_execution():
    ops = [(0, 50, "while"), (5, 20, "a"), (20, 45, "b"), (50, 60, "c"),
           (100, 110, "a"), (200, 210, "c")]
    leaves = _events.walked(ops)["leaves"]
    assert leaves == tr.leaves(ops) == [
        (5, 20, "a"), (20, 45, "b"), (50, 60, "c"), (100, 110, "a"),
        (200, 210, "c")]
    meta = {"while": {"bytes_accessed": 10 ** 6},
            "a": {"bytes_accessed": 3}, "b": {"bytes_accessed": 4}, "c": {}}
    modules = [(0, 60, "jit__step(1)"), (100, 111, "jit__step(1)"),
               (199, 211, "jit__chunk(2)")]
    assert step_hbm_gb.bytes_per_execution(leaves, modules, meta) == [7, 3]


# ------------------------- every new entry on a record like a traced run's

def _varint(n: int) -> bytes:
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields) -> bytes:
    """(number, int | bytes | str) fields as one protobuf message."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return out


def _plane(name: str, lines: dict, tf_ops: dict) -> bytes:
    """An XPlane whose lines hold `(start_ns, end_ns, event)` and whose
    event metadata carry `tf_op` and `bytes_accessed`. An event is a name,
    or `(name, tf_op)` for one of several metadata of that name."""
    ids = {}
    for events in lines.values():
        for _, _, event in events:
            ids.setdefault(event, len(ids) + 1)
    fields = [(2, name),
              (5, _msg((1, 1), (2, _msg((1, 1), (2, "tf_op"))))),
              (5, _msg((1, 2), (2, _msg((1, 2), (2, "bytes_accessed")))))]
    for event, i in ids.items():
        stats = [(5, _msg((1, 2), (3, 1000)))]
        label, tf_op = (event if isinstance(event, tuple)
                        else (event, tf_ops.get(event)))
        if tf_op:
            stats.append((5, _msg((1, 1), (5, tf_op))))
        fields.append((4, _msg((1, i), (2, _msg((1, i), (2, label), *stats)))))
    for n, (line, events) in enumerate(lines.items()):
        fields.append((3, _msg((1, n + 1), (2, line), (3, 0), *[
            (4, _msg((1, ids[ev]), (2, s * 1000), (3, (e - s) * 1000)))
            for s, e, ev in events])))
    return _msg(*fields)


SCOPED_OPS = {          # event -> tf_op, 10 ns each, one of every scope
    f"%fusion.{i} = f32[8]{{0}} fusion()": f"jit(_step)/{path}/add:"
    for i, path in enumerate([
        "jvp(embed)", "jvp(layers)/while/body/attn/ln",
        "transpose(jvp(layers))/while/body/checkpoint/attn",
        "jvp(layers)/while/body/mlp", "jvp(unembed_loss)", "optimizer",
        "layers/while/body", "layers/while/body/attn/kv_update",
        "layers/while/body/mlp/weights_cast", "prefix_pool", "jvp()"])}


@pytest.fixture(scope="module")
def traced_dir(tmp_path_factory):
    """A trace as the chip's profiler lays it out: one device that runs
    each scope's operation inside two `jit__step` executions with an idle
    gap after each, and the engine's thread with its phases."""
    ops, modules, host = [], [], []
    for k in range(2):
        t = k * 1000
        modules.append((t, t + 400, "jit__step(7)"))
        ops += [(t + 10 * i, t + 10 * i + 10, name)
                for i, name in enumerate(SCOPED_OPS)]
        host += [(t, t + 5, "PjitFunction(_step)"),
                 (t + 100, t + 500, "engine.fetch"),
                 (t + 500, t + 700, "engine.sample"),
                 (t + 700, t + 800, "engine.notify"),
                 (t + 800, t + 900, "engine.empty")]
    space = _msg(
        (1, _plane(DEVICE, {tr.OPS_LINE: ops, tr.MODULES_LINE: modules},
                   SCOPED_OPS)),
        (1, _plane(tr.HOST_PLANE, {"engine": host}, {})))
    d = tmp_path_factory.mktemp("trace")
    os.makedirs(d / "plugins" / "profile" / "t")
    (d / "plugins" / "profile" / "t" / "vm.xplane.pb").write_bytes(space)
    return str(d)


@pytest.fixture(scope="module")
def engine_counters():
    """`counters` as `harness/serve_cell.py` records them: the replica's
    whole `stats()` at the window's edges, here of a `gpt2-tiny` server."""
    from ray_tpu.serve.llm import LLMServer

    server = LLMServer(preset="gpt2-tiny", max_batch=2, max_seq_len=64)
    try:
        server({"prompt_ids": [1, 2, 3], "max_tokens": 2})
        before, before_at = server.stats(), time.time()
        threads = [threading.Thread(target=server, args=(
            {"prompt_ids": [5, 6, 7, 8 + i], "max_tokens": 4,
             "temperature": 0.5},)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        after, after_at = server.stats(), time.time()
    finally:
        server.engine.shutdown()
    return {"before": before, "after": after, "before_at": before_at,
            "after_at": after_at}


def new_entries(bench):
    return [m for m in bench["per_layer"] if m["name"].split(".")[0] in {
        "engine_host_ms", "engine_busy_step_ms", "queue_wait_mean_ms",
        "engine_ttft_mean_ms", "idle_in_fetch_pct", "idle_in_sample_pct",
        "idle_in_loop_pct", "idle_in_empty_pct", "attn_time_pct",
        "mlp_time_pct", "loss_time_pct", "optimizer_time_pct", "ln_time_pct",
        "embed_time_pct", "weights_cast_time_pct", "unscoped_time_pct",
        "layers_time_pct", "kv_update_time_pct", "prefix_pool_time_pct",
        "step_hbm_gb"}]


NEW = new_entries(spec.benchmark())


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_every_new_entry_reads_a_number(metric, traced_dir, engine_counters):
    record = {"counters": engine_counters, "trace_dir": traced_dir}
    value = spec.metric_reader(metric["name"]).read(record)
    assert isinstance(value, float) and value >= 0
    reading = metric["name"].split(".")[0]
    # the window: first operation's start to the last one's end; its one
    # gap, from the first execution's last operation to the second's
    # first, lies under the first pass's phases
    busy = 10 * len(SCOPED_OPS)
    window = 1000 + busy
    want = {"idle_in_fetch_pct": 100 * (500 - busy) / window,
            "idle_in_sample_pct": 100 * 200 / window,
            "idle_in_loop_pct": 100 * 100 / window,     # the notify
            "idle_in_empty_pct": 100 * 100 / window,
            "step_hbm_gb": len(SCOPED_OPS) * 1000 / 1e9}
    if reading in want:
        assert value == pytest.approx(want[reading])
    elif reading.endswith("_time_pct"):
        # as many operations as scopes, each as long as the others
        assert value == pytest.approx(100 / len(SCOPED_OPS))
    elif reading == "engine_host_ms":
        c = engine_counters
        steps = c["after"]["engine_steps"] - c["before"]["engine_steps"]
        busy = c["after"]["loop_busy_s"] - c["before"]["loop_busy_s"]
        assert 0 < value < busy * 1e3 / steps


def test_the_new_entries_are_the_ones_the_issue_lists(
        bench=spec.benchmark()):
    """The 29 entries of these readers that the issue listed, in all nine
    cells of its day; a later PR may list one of the readers again for
    another end-to-end metric and add cells (`test_a_tenth_cell.py`)."""
    new = new_entries(bench)
    assert len(new) >= 29
    cells = {w["name"] for w in bench["workloads"]}
    listed = {c for m in new for c in m["workloads"]}
    assert listed <= cells and len(listed) >= 9


def test_two_programs_operations_of_one_name_keep_their_own_metadata(
        tmp_path):
    # `%copy.3` of `jit__step` and of `jit__chunk` on one device plane
    same = "%copy.3 = bf16[8]{0} copy()"
    step = (same, "jit(_step)/layers/while/body/attn/kv_update/copy:")
    chunk = (same, "jit(_chunk)/layers/while/body/mlp/copy:")
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(_msg((1, _plane(DEVICE, {
        tr.OPS_LINE: [(0, 30, step), (50, 60, chunk)],
        tr.MODULES_LINE: [(0, 30, "jit__step(1)"), (50, 60, "jit__chunk(2)")],
    }, {}))))
    devices, _ = _events.load(str(path))
    d = devices[DEVICE]
    assert [d["meta"][i]["name"] for _, _, i in d["ops"]] == [same, same]
    assert _scopes.self_time_by_scope(
        _events.walked(d["ops"])["own"], d["meta"]) == {
        "kv_update": 30, "mlp": 10}


def test_struct_is_what_a_fixed64_would_need():
    # `_xmeta` skips fixed-width fields by size; a double stat is one
    buf = _msg((1, 5)) + b"\x11" + struct.pack("<d", 1.5) + _msg((3, "x"))
    assert [(f, bytes(v) if not isinstance(v, int) else v)
            for f, v in _xmeta._fields(memoryview(buf))] == [
        (1, 5), (2, struct.pack("<d", 1.5)), (3, b"x")]


# --------------------------------------------- the prefix pool's share (PR 45)

@pytest.mark.parametrize("reused,prefilled,want", [
    (900, 100, 90.0),         # a document from the pool, a question prefilled
    (0, 640, 0.0),            # distinct prompts: nothing reused, a true 0
    (4096, 0, 100.0),         # the most it can read
    (0, 0, None)])            # no prompt token in the window: nothing to read
def test_prefix_reuse_is_a_share_of_the_tokens_the_engine_took_in(
        reused, prefilled, want):
    """Numerator and denominator are the replica's own counts of the same
    tokens; the client's count of the prompts that *ended* in the window
    (110% in a closed loop over long documents) is not read."""
    reader = spec.metric_reader("prefix_reuse_pct.decode")
    record = {"prompt_tokens_counted": 1,           # in the record, unread
              "counters": {
                  "before": {"tokens_prefilled": 50,
                             "kv_cache": {"tokens_reused": 7}},
                  "after": {"tokens_prefilled": 50 + prefilled,
                            "kv_cache": {"tokens_reused": 7 + reused}}}}
    assert reader.read(record) == want
    assert reader.read({"counters": None}) is None
    assert reader.read({"counters": {"before": {}, "after": {}}}) is None


def test_prefix_reuse_reads_the_engines_own_counters(engine_counters):
    got = spec.metric_reader("prefix_reuse_pct").read(
        {"counters": engine_counters})
    c = engine_counters
    prefilled = (c["after"]["tokens_prefilled"]
                 - c["before"]["tokens_prefilled"])
    assert prefilled == 3 * 4 and 0.0 <= got <= 100.0
