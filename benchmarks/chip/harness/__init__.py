"""The benchmark's harness: what is common to every cell.

Nothing in this package or in `run.py` names a model family, a cell or a
metric. Whatever belongs to one configuration, one traffic mix, one
per-layer metric or one model family is a file of its own under
`configs/`, `traffic/`, `layer_metrics/` or `families/`, found by the name
`BENCHMARK.json` gives.
"""
