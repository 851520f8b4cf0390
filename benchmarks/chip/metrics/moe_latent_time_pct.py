"""Share of a decode step's device time under the program's `moe_latent`
scope: the two projections that stand round a LatentMoE layer's dispatch
(`models/nemotron.py`: the normed input into the latent the routed experts
live in, 4,096 -> 1,024, and their gated sum back), every expert layer; the
median device self time under the scope inside one execution of the decode
program over the median device time of that program. None for a program
without the scope (no operation carries it), and without a traced step."""

from . import _events, _trace

SCOPES = ("moe_latent",)
STEP_MODULE = "jit__step"


def _scope_of(tf_op):
    """`jit(_step)/layers/while/body/mlp/moe_latent/dot_general` ->
    `moe_latent`; None outside it."""
    return _events.innermost(tf_op, SCOPES)


_, step_seconds = _events.readers(_scope_of, STEP_MODULE)


def read(record):
    under = step_seconds(record, "moe_latent")
    step_ms = _trace.module_ms(record, STEP_MODULE)
    if not under or not step_ms:
        return None
    return 100.0 * under * 1e3 / step_ms
