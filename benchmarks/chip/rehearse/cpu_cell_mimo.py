#!/usr/bin/env python3
"""`cpu_cell.py` for the MiMo cell: the same rehearsal (one cell end to end
on the CPU at a tiny size, nothing it prints a measurement), with the model
cut in the source's key names and the documents cut to the tiny window,
which `cpu_cell.TINY` does not know. The share stays the file's: the router
scores 256 outputs, 3 a token, of which the first 4 are held. G S S G S, a
window of 16 = the pool's block = the chunk; short and long documents in one
queue, 16 to 80 tokens.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell_mimo.py \
        --workload serve-mimo-mixedqueue [--seconds 8] [--trace 1]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell  # noqa: E402

TINY_MODEL = {"vocab_size": 512, "num_hidden_layers": 5, "hidden_size": 64,
              "intermediate_size": 128, "moe_intermediate_size": 32,
              "num_attention_heads": 8, "swa_num_attention_heads": 8,
              "num_key_value_heads": 2, "swa_num_key_value_heads": 4,
              "head_dim": 24, "swa_head_dim": 24, "v_head_dim": 16,
              "swa_v_head_dim": 16, "n_routed_experts": 4,
              "num_experts_per_tok": 3, "sliding_window": 16,
              "sliding_window_size": 16,
              "hybrid_layer_pattern": [0, 1, 1, 0, 1] + [1] * 43}
TINY_DEPLOYMENT = {"preset": "mimo-tiny", "max_seq_len": 128,
                   "max_batch": 4, "prefill_chunk_size": 16,
                   "kv_blocks": 64, "kv_block_size": 16}
TINY_TRAFFIC = {"clients": 6, "requests_per_client": 500, "documents": 4,
                "document_uniform": [16, 80], "document_block": 16,
                "question_uniform": [3, 7], "output_uniform": [8, 16],
                "ramp_s": 2.0, "trace_seconds": 1.0}

cpu_cell.TINY_MODEL = TINY_MODEL
cpu_cell.TINY["serve"] = {"deployment": TINY_DEPLOYMENT,
                          "traffic": TINY_TRAFFIC}

if __name__ == "__main__":
    sys.exit(cpu_cell.main())
