#!/usr/bin/env python3
"""`cpu_cell.py` for the Nemotron cell: the same rehearsal (one cell end to
end on the CPU at a tiny size, nothing it prints a measurement), with the
model cut in the source's key names and the preambles cut to the tiny
window, which `cpu_cell.TINY` does not know. The share stays the file's: the
router scores 512 experts, 22 a token, of which the first 128 are held.

    JAX_PLATFORMS=cpu python benchmarks/chip/rehearse/cpu_cell_nemotron.py \
        --workload serve-nemotron-reasoning [--seconds 8] [--trace 1]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell  # noqa: E402

TINY_MODEL = {"vocab_size": 512, "num_hidden_layers": 7,
              "hybrid_override_pattern": "MEM*EME", "hidden_size": 64,
              "mamba_num_heads": 4, "mamba_head_dim": 32,
              "ssm_state_size": 16, "n_groups": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "moe_intermediate_size": 40,
              "moe_latent_size": 32,
              "moe_shared_expert_intermediate_size": 48}
TINY_DEPLOYMENT = {"preset": "nemotron-tiny", "max_seq_len": 128,
                   "max_batch": 4, "prefill_chunk_size": 16,
                   "kv_blocks": 64, "kv_block_size": 8}
TINY_TRAFFIC = {"clients": 6, "requests_per_client": 500, "documents": 3,
                "document_uniform": [32, 56], "document_block": 8,
                "question_uniform": [4, 16], "output_uniform": [8, 16],
                "ramp_s": 2.0, "trace_seconds": 1.0}

cpu_cell.TINY_MODEL = TINY_MODEL
cpu_cell.TINY["serve"] = {"deployment": TINY_DEPLOYMENT,
                          "traffic": TINY_TRAFFIC}

if __name__ == "__main__":
    sys.exit(cpu_cell.main())
