"""The 18 `per_layer` entries PR 45 folded: one reader and one end-to-end
metric moved had been listed once a cell under a suffix (`.fewshot`,
`.docgen`, `.longgen`). Each kept entry now lists every cell that reads it;
here every (kept entry, listed cell) pair is in that cell's result line and
its reader gives a number on that family's own hand-made record."""

import pytest

from harness import spec
from test_brumby_family import served_record as brumby_record  # noqa: F401
from test_granite_family import served_record as granite_record  # noqa: F401
from test_kanana_family import served_record as kanana_record  # noqa: F401
from test_kimi_family import served_record as kimi_record  # noqa: F401

BENCH = spec.benchmark()
RECORD = {"serve-kanana-docqa": "kanana_record",
          "serve-brumby-fewshot": "brumby_record",
          "serve-granite-docgen": "granite_record",
          "serve-kimi-longgen": "kimi_record"}
DOCUMENT_CELLS = list(RECORD)
ROWS = ["serve-kanana-docqa", "serve-granite-docgen", "serve-kimi-longgen"]
LATENT_EXPERTS = ["serve-kanana-docqa", "serve-kimi-longgen"]
KEPT = {"engine_attn_time_pct": DOCUMENT_CELLS,
        "engine_mlp_time_pct": DOCUMENT_CELLS,
        "engine_head_time_pct": DOCUMENT_CELLS,
        "engine_prefix_pool_time_pct": DOCUMENT_CELLS,
        "kv_bytes_per_token": ROWS,
        "mla_attend_time_pct": LATENT_EXPERTS,
        "mla_attend_roofline_pct": LATENT_EXPERTS,
        "moe_experts_time_pct.decode": LATENT_EXPERTS,
        "moe_experts_decode_roofline_pct": LATENT_EXPERTS}
PAIRS = [(entry, cell) for entry, cells in KEPT.items() for cell in cells]


def test_the_table_of_the_fold(bench=BENCH):
    """Each kept entry lists at least the cells that were folded into it;
    who else lists it, how many entries the file has and what other
    suffixes it uses are a later PR's to add to (`bench`: the file, or
    `test_a_tenth_cell.py`'s copy with a cell and entries appended).
    `test_benchmark_json.test_one_entry_a_reading` keeps the fold."""
    assert len(PAIRS) == 27
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for entry, cells in KEPT.items():
        assert set(cells) <= set(by_name[entry]["workloads"])
        assert by_name[entry]["moves"] == "serve_tokens_per_s"
    # no entry carries one of the folded cells' suffixes any more
    assert {"fewshot", "docgen", "longgen"}.isdisjoint(
        m["name"].split(".")[1] for m in bench["per_layer"]
        if "." in m["name"])


@pytest.mark.parametrize("entry,cell", PAIRS)
def test_a_kept_entry_is_in_the_cells_line_and_reads_its_familys_record(
        entry, cell, request):
    names = [m["name"] for m in spec.cell(BENCH, cell)["per_layer"]]
    assert names.count(entry) == 1
    value = spec.metric_reader(entry).read(
        request.getfixturevalue(RECORD[cell]))
    assert isinstance(value, (int, float)) and not isinstance(value, bool)
    assert value == value and value >= 0          # a number, never a NaN
