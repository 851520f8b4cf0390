#!/usr/bin/env python3
"""`cpu_cell.py` for the Keye cell: the same rehearsal (one cell end to end
on the CPU at a tiny size, nothing it prints a measurement), with the model
cut in the source's key names and the documents cut to the tiny window,
which `cpu_cell.TINY` does not know: a topk of 16 under documents of 48-80,
so that every decode lane stands past it, as the cell's do.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell_keye.py \
        --workload serve-keye-longdoc [--seconds 8] [--trace 1]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell  # noqa: E402

TINY_MODEL = {"vocab_size": 512, "num_hidden_layers": 3, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "moe_intermediate_size": 32,
              "num_experts": 8, "num_local_experts": 8,
              "num_experts_per_tok": 3,
              "rope_scaling": {"mrope_section": [2, 3, 3],
                               "rope_type": "default", "type": "default"},
              "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                            "q_chunk_size": 512, "topk": 16}}
TINY_DEPLOYMENT = {"preset": "keye-tiny", "max_seq_len": 128,
                   "max_batch": 4, "prefill_chunk_size": 16,
                   "kv_blocks": 48, "kv_block_size": 8}
TINY_TRAFFIC = {"clients": 5, "requests_per_client": 500, "documents": 3,
                "document_uniform": [48, 80], "document_block": 8,
                "question_uniform": [2, 8], "output_uniform": [8, 24],
                "ramp_s": 2.0, "trace_seconds": 1.0}

cpu_cell.TINY_MODEL = TINY_MODEL
cpu_cell.TINY["serve"] = {"deployment": TINY_DEPLOYMENT,
                          "traffic": TINY_TRAFFIC}

if __name__ == "__main__":
    sys.exit(cpu_cell.main())
