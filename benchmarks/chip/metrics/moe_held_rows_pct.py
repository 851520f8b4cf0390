"""Of the (lane, expert) pairs the router made for a decode step's valid
lanes, the share that fell on the experts this replica holds, as the decode
program counts both itself (`_mla_scopes.decode_step_counts`:
`expert_rows` over `expert_rows_all`): a quarter under a balanced load when
64 of 256 are held. None for a program that holds every expert and counts
no such column."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found or not found[0].get("expert_rows_all"):
        return None
    return 100.0 * found[0]["expert_rows"] / found[0]["expert_rows_all"]
