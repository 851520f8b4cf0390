"""Absorbed attention over the latent cache in a decode step against the
chip's roofline: the least the step must move or multiply there (the
family's `mla_attend_cost`: the latents and rotary keys up to each slot's
position, by the decode program's own count, read once; or the absorbed
form's operations; whichever bounds), every layer, over the step's device
time under the `mla_attend` scope."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found:
        return None
    counts, costs = found
    positions = counts["attended_positions"] * costs["attention_layers"]
    cost = {k: positions * v
            for k, v in costs["mla_attend_per_position"].items()}
    return _mla_scopes.roofline_pct(
        record, cost, _mla_scopes.step_seconds(record, "mla_attend"))
