"""Share of a decode step's device time under the program's `mlp_dense`
scope: the dense FFNs that stand beside the expert block in a
shortcut-connected layer (`models/longcat.py`: two SwiGLUs of 12,288 lanes a
layer), every layer (`_step_scope`:
`jit(_step)/layers/while/body/mlp/mlp_dense/dot_general` -> `mlp_dense`).
None for a program without the scope, and without a traced step."""

from . import _step_scope

_scope_of, read = _step_scope.reader("mlp_dense")
