"""From the phases' results to the one JSON object the command prints."""

from __future__ import annotations

import os

from . import device, spec


def result_line(cell: dict, results: dict, traced: bool, log) -> dict:
    """`results` maps phase name to what the phase wrote. The first phase
    measured and carries the record the metric readers read; every phase
    may add checks. Metrics whose reader finds nothing are left out."""
    measured = next(iter(results.values()))
    record = measured["record"]
    record["peaks"] = spec.peaks().get(record["devices"][0]["kind"], {})
    if traced and record.get("trace_dir"):
        import trace_reduce

        record["trace"] = trace_reduce.reduce_dir(record["trace_dir"])
    metrics = {}
    for m in cell["per_layer" if traced else "end_to_end"]:
        reader = spec.metric_reader(m["name"])
        try:
            value = reader.read(record) if reader else None
        except (KeyError, TypeError, ZeroDivisionError) as e:
            log(f"metric {m['name']}: the record lacks {e!r}")
            value = None
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for phase, r in results.items():
        log(f"{phase} checks: {r.get('checks')}")
    line = {"correct": all(r.get("correct", False)
                           for r in results.values()),
            "attempted": measured["attempted"],
            "failed": measured["failed"], "metrics": metrics,
            "device": device.block(_with_program_peaks(record))}
    trace = record.get("trace")
    if trace:
        line["device"]["busy_s"] = trace["busy_s"]
        line["device"]["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["top_ops"][:10],
                             "idle_gaps": trace["top_gaps"][:10]}
    return line


def _with_program_peaks(record: dict) -> list:
    """The peak on each chip. The backend's `peak_bytes_in_use` counts the
    buffers a process holds and leaves out what a running program needs
    beside its arguments (PR 21: 1.5 GB reported for a step whose
    `memory_analysis()` needs 14.7 GB), so the largest temporary size of
    the programs the window ran is added to it."""
    temps = [p["temp"] for p in (record.get("program_bytes") or {}).values()
             if "temp" in p]
    extra = max(temps, default=0)
    return [{**d, "memory_peak_bytes": (d.get("peak_bytes_in_use") or 0)
             + extra} for d in record["devices"]]
