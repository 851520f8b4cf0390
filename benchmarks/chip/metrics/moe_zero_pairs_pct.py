"""Of the (lane, expert) pairs the router made for a decode step's valid
lanes, the share that chose a zero-compute expert, as the decode program
counts both itself (`_mla_scopes.decode_step_counts`: `zero_rows` over
`expert_rows_all`): a third under a balanced load when 256 of the router's
768 outputs are zero-compute. None for a program that counts no such
column."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found or "zero_rows" not in found[0] \
            or not found[0].get("expert_rows_all"):
        return None
    return 100.0 * found[0]["zero_rows"] / found[0]["expert_rows_all"]
