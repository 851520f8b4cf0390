"""Of the prompt tokens the engine took in over the window, the share the
prefix pool supplied: the pool's `tokens_reused` over that and the engine's
`tokens_prefilled`, both the replica's own counts at the window's edges. A
prompt token is either reused or prefilled, so the share cannot pass 100.
(Until PR 45 the denominator was the client's count of the prompts of the
requests that *ended* in the window, other requests than the numerator's:
a closed loop over long documents read 110.)"""

from . import _engine


def read(record):
    reused = _engine.kv_delta(record, "tokens_reused")
    prefilled = _engine.delta(record, "tokens_prefilled")
    if reused is None or prefilled is None or not reused + prefilled:
        return None
    return 100.0 * reused / (reused + prefilled)
