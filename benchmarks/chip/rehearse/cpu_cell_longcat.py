#!/usr/bin/env python3
"""`cpu_cell.py` for the LongCat cell: the same rehearsal (one cell end to
end on the CPU at a tiny size, nothing it prints a measurement), with the
model cut in the source's key names and the preambles cut to the tiny
window, which `cpu_cell.TINY` does not know. The share stays the file's: the
router scores 768 outputs, 12 a token, of which the first 16 are held
experts and the last 256 zero-compute.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell_longcat.py \
        --workload serve-longcat-assistant [--seconds 8] [--trace 1]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell  # noqa: E402

TINY_MODEL = {"vocab_size": 512, "num_layers": 2, "hidden_size": 64,
              "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
              "num_attention_heads": 4, "q_lora_rank": 24,
              "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16}
TINY_DEPLOYMENT = {"preset": "longcat-tiny", "max_seq_len": 128,
                   "max_batch": 4, "prefill_chunk_size": 16,
                   "kv_blocks": 64, "kv_block_size": 8}
TINY_TRAFFIC = {"clients": 6, "requests_per_client": 500, "documents": 3,
                "document_uniform": [32, 56], "document_block": 8,
                "question_uniform": [3, 7], "output_uniform": [8, 16],
                "ramp_s": 2.0, "trace_seconds": 1.0}

cpu_cell.TINY_MODEL = TINY_MODEL
cpu_cell.TINY["serve"] = {"deployment": TINY_DEPLOYMENT,
                          "traffic": TINY_TRAFFIC}

if __name__ == "__main__":
    sys.exit(cpu_cell.main())
