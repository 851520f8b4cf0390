"""An `.xplane.pb` read whole by a protobuf wire decoder, no dependency.

`ProfileData` gives an event's name and its own statistics; what the
readers here need is kept with the event's *metadata*: for every XLA
operation the TPU's profiler records there where the operation came from
(`tf_op`, the JAX name stack: `jit(_step)/transpose(jvp(attn))/while/body/
.../dot_general:`, in which the program's `jax.named_scope`s appear) and
what the compiler estimated for it (`flops`, `bytes_accessed`). Two
programs on one device may hold operations of the same name (`%copy.3` of
`jit__step` and of `jit__chunk`), so an event is tied to its metadata by
the metadata's id, as the file ties it, never by name. One pass yields
both the events and the metadata.

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3, .event_metadata = 4 (map),
        .stat_metadata = 5 (map);  a map entry's .value = 2
    XLine.name = 2, .timestamp_ns = 3, .events = 4
    XEvent.metadata_id = 1, .offset_ps = 2, .duration_ps = 3
    XEventMetadata.id = 1, .name = 2, .stats = 5
    XStatMetadata.id = 1, .name = 2
    XStat.metadata_id = 1, .uint64 = 3, .int64 = 4, .str = 5, .ref = 7
"""

from __future__ import annotations

KEPT = ("tf_op", "flops", "bytes_accessed")


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _entry_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _metadata(buf, stat_names: dict) -> tuple:
    """(id, {"name": ..., and those of KEPT it carries}) of one
    XEventMetadata."""
    ident, out = 0, {"name": ""}
    for field, value in _fields(buf):
        if field == 1:
            ident = value
        elif field == 2:
            out["name"] = bytes(value).decode()
        elif field == 5:
            stat = dict(_fields(value))
            key = stat_names.get(stat.get(1))
            if key not in KEPT:
                continue
            if 7 in stat:                          # a reference to a name
                out[key] = stat_names.get(stat[7], "")
            elif 5 in stat:
                out[key] = bytes(stat[5]).decode()
            else:
                out[key] = stat.get(3, stat.get(4, 0))
    return ident, out


def _line(buf) -> tuple:
    """(name, [(start_ns, end_ns, metadata id)]) of one XLine."""
    name, t0, events = "", 0, []
    for field, value in _fields(buf):
        if field == 2:
            name = bytes(value).decode()
        elif field == 3:
            t0 = value
        elif field == 4:
            events.append(value)
    out = []
    for event in events:
        ident = offset = duration = 0
        for field, value in _fields(event):
            if field == 1:
                ident = value
            elif field == 2:
                offset = value
            elif field == 3:
                duration = value
        out.append((t0 + offset / 1e3, t0 + (offset + duration) / 1e3,
                    ident))
    return name, out


def _plane(buf) -> tuple:
    name, stat_names, metadata, lines = "", {}, [], []
    for field, value in _fields(buf):
        if field == 2:
            name = bytes(value).decode()
        elif field == 3:
            lines.append(value)
        elif field == 4:
            metadata.append(_entry_value(value))
        elif field == 5:
            stat = dict(_fields(_entry_value(value)))
            stat_names[stat.get(1)] = bytes(stat.get(2, b"")).decode()
    return name, {"meta": dict(_metadata(m, stat_names) for m in metadata),
                  "lines": [_line(ln) for ln in lines]}


def read(path: str) -> dict:
    """plane name -> {"meta": {metadata id: {"name", and `tf_op`, `flops`,
    `bytes_accessed` where the file has them}}, "lines": [(line name,
    [(start_ns, end_ns, metadata id), ...]), ...]}."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    return dict(_plane(v) for f, v in _fields(space) if f == 1)
