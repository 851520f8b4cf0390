"""Share of a decode step's device time under the program's `swa_attend`
scope: a sliding-window layer's attention over its ring, the last 128
positions a slot (`models/exaone.py`: three such layers to each global one,
whose attention stays under `gqa_attend`), every sliding layer
(`_step_scope`: `jit(_step)/layers/while/body/attn/swa_attend/...` ->
`swa_attend`). None for a program without the scope, and without a traced
step."""

from . import _step_scope

_scope_of, read = _step_scope.reader("swa_attend")
