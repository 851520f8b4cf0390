"""Share of a decode step's device time under the program's `moe_zero`
scope: the zero-compute experts' term of an expert block
(`models/moe.py` `_experts`: the gates of a token's pairs that chose one,
summed, times the block's input), every layer (`_step_scope`:
`jit(_step)/layers/while/body/mlp/moe_zero/mul` -> `moe_zero`). None for a
program without the scope, and without a traced step."""

from . import _step_scope

_scope_of, read = _step_scope.reader("moe_zero")
