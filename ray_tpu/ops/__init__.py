"""TPU-native hot ops (Pallas kernels + shard_map collectives).

The reference has no equivalent (its hot ops live in torch/CUDA inside user
frameworks); SURVEY.md §5.7 flags long-context attention as new design work
for the TPU build.

Serving's one-token decode kernels are two scaffolds and six bodies:
`slot_rows` (the grid over a slot's rows: `mla_attend`, `gqa_attend`,
`dsa_attend`) and `slot_state` (the pass over a slot's state, in place:
`power_retention`, `ssm_update`, `kda_update`; also the one `on_tpu` /
`use_kernel` every kernel here asks). Beside them `rows_write`,
`expert_mlp`, `grouped_matmul` and training's `flash_attention`.
"""

from ray_tpu.ops.flash_attention import flash_attention, mha_reference
from ray_tpu.ops.ring_attention import ring_attention, ulysses_attention

__all__ = [
    "flash_attention",
    "mha_reference",
    "ring_attention",
    "ulysses_attention",
]
