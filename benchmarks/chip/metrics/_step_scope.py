"""A scope's share of one decode step: the median device self time under
the scope inside one execution of the decode program over the median device
time of that program. What the readers of a scope that one family's decode
step adds share (`moe_zero_time_pct`, `mlp_dense_time_pct`)."""

from . import _events, _trace

STEP_MODULE = "jit__step"


def reader(scope: str):
    """(`scope_of`, `read`) for the scope named `scope`: `scope_of(tf_op)`
    is the scope where it is the innermost on the operation's path, else
    None; `read(record)` is the share in per cent, None for a program
    without the scope (no operation carries it) and without a traced
    step."""
    def scope_of(tf_op):
        return _events.innermost(tf_op, (scope,))

    _, step_seconds = _events.readers(scope_of, STEP_MODULE)

    def read(record):
        under = step_seconds(record, scope)
        step_ms = _trace.module_ms(record, STEP_MODULE)
        if not under or not step_ms:
            return None
        return 100.0 * under * 1e3 / step_ms

    return scope_of, read
