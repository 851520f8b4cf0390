"""The benchmark's CPU tests: generators, metric arithmetic, the trace
reduction, the reference and the contract of BENCHMARK.json. Seconds, no
cluster, and nothing here loads the TPU library."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP_DIR = os.path.join(REPO, "benchmarks", "chip")
for p in (REPO, CHIP_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
