"""Solar Open2's layers for serving (`upstage/Solar-Open2-250B`, presets
`solar-*`): `models/kimi.py`'s two programs, which hold its delta rule, its
gated grouped-head softmax layers and its share of the routed experts (that
module's docstring has the layer), under this family's word on the cache's
leaves. The serving protocol reads that word from the module
(`models/__init__.py`), and Kimi's rows are latents where these are keys and
values by the 8 key-value heads, `k`, `v` [softmax layers, slots, 8, T,
128]; nothing else differs, so nothing else is here."""

from ray_tpu.models.kimi import (CACHE_STATE, COUNTS, PRESETS,  # noqa: F401
                                 KimiConfig, decode_step, init_cache,
                                 init_ends, init_layer, init_params,
                                 num_params, prefill_chunk, resident_params,
                                 resident_specs)

CACHE_TOKEN_AXIS = {"k": 3, "v": 3}
