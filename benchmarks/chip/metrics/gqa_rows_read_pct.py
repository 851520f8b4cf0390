"""Of the positions a decode step's valid lanes attend to, the positions
whose rows the grouped-head softmax layer read for them
(`read_positions` over `attended_positions`, the decode program's own
counts a step): 100 is a read to each lane's own position; the plain form
reads all T positions a lane whatever its position, T over the mean
position. None for a program that counts neither or has no such layer."""

from . import _mla_scopes


def read(record):
    found = _mla_scopes.decode_step_counts(record)
    if not found or "gqa_layers" not in found[1] \
            or not found[0].get("attended_positions") \
            or "read_positions" not in found[0]:
        return None
    return 100.0 * found[0]["read_positions"] / found[0]["attended_positions"]
