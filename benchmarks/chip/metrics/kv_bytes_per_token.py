"""Bytes one token leaves in the replica's cache, all layers: a gauge in
the engine's `stats()` (9,216 for the latent cache of eight DeepSeek-V3
layers; keys and values by head would be 163,840 there)."""


def read(record):
    return ((record.get("counters") or {}).get("after") or {}).get(
        "kv_bytes_per_token")
