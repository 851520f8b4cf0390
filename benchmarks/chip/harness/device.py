"""What the benchmark requires of the device, in one place.

A rehearsal on the CPU replaces `require_chip` and `chip_request` in its
own process (tests and `rehearse/` do; the command has no option for it).
"""

from __future__ import annotations

from . import spec


class NoChip(Exception):
    pass


def block(devices: list) -> dict:
    """The `device` object of the result from a worker's device reports."""
    peak = max((d.get("memory_peak_bytes") or 0) for d in devices)
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices), "memory_peak_bytes": int(peak)}


def require_chip(devices: list, chips: int) -> None:
    """Raises unless the worker holds `chips` TPU devices of a kind whose
    peaks the benchmark knows."""
    kinds = {d["kind"] for d in devices}
    platforms = {d["platform"] for d in devices}
    if platforms != {"tpu"}:
        raise NoChip(f"the worker runs JAX on {sorted(platforms)} "
                     f"({len(devices)} x {sorted(kinds)}), not on 'tpu': "
                     f"no result is produced")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s) and the worker "
                     f"holds {len(devices)}")
    unknown = kinds - set(spec.peaks())
    if unknown:
        raise NoChip(f"peaks.json has no entry for device kind "
                     f"{sorted(unknown)}; add it with its source")


def chip_request(chips: int) -> int:
    """How many TPU chips the cell's worker asks the scheduler for."""
    return chips


def worker_devices() -> list:
    """Inside the process that holds the chips: what JAX reports there."""
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id, "platform": d.platform,
                    "kind": d.device_kind,
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                    "bytes_limit": stats.get("bytes_limit")})
    return out
