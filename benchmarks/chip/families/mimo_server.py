"""The replica class the MiMo serving cell deploys (`families/mimo.py`'s
`build_app`). A module of its own so that it is pickled by name and
imported inside the replica's worker."""

from __future__ import annotations

from families.brumby_server import BenchServer as _StateFamilyServer


class BenchServer(_StateFamilyServer):
    """`families/brumby_server.py`'s replica as it is (the program's
    `OpenAIServer` with the harness's probe and the family's unit costs
    beside the engine's counters in `stats()`; neither touches a request's
    path): nothing in it names a family. The weights are the engine's own
    default: the serving module's `init_params` from `seed`, on the device,
    a layer at a time, in the dtype they are held in."""
