"""Bytes one slot's recurrent state holds in the replica's cache, all
layers: a gauge in the engine's `stats()` (274,759,680 for eight Brumby
layers in the padded layout, 272,646,144 of them content; the prefix pool
keeps snapshots of that size)."""


def read(record):
    return ((record.get("counters") or {}).get("after") or {}).get(
        "state_bytes_per_slot")
