"""Experts that got at least one row in an expert layer of a decode step,
as the decode program counts them itself (`_mla_scopes.decode_step_counts`):
their weights are what the grouped matmuls must read."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found or not found[0]["expert_layer_steps"]:
        return None
    return found[0]["experts_touched"] / found[0]["expert_layer_steps"]
