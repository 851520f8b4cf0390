"""One reader per metric, found by the metric's name in BENCHMARK.json.

`read(record)` takes the run's record (`harness/<kind>_cell.py` writes it:
marks on the wall clock, the window, the program's counters at its edges,
the client's or the loop's log, the compiler's sizes, and with `--trace 1`
the reduced trace) and returns one number as measured, or None when what
it reads is not there; the harness then leaves the metric out.
"""
