#!/usr/bin/env python3
"""`cpu_cell.py` for the OLMoE cell: the same rehearsal (one cell end to end
on the CPU at a tiny size, nothing it prints a measurement), with the
model cut in OLMoE's key names, which `cpu_cell.TINY_MODEL` does not know.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell_olmoe.py \
        --workload train-olmoe-4k [--seconds 8] [--trace 1]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell  # noqa: E402

cpu_cell.TINY_MODEL = {
    "hidden_size": 128, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 2,
    "vocab_size": 512, "max_position_embeddings": 128,
    "num_hidden_layers": 2}
cpu_cell.TINY["train"]["job"] = {"seq_len": 64, "global_batch": 4}

if __name__ == "__main__":
    sys.exit(cpu_cell.main())
