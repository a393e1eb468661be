"""What a profiler trace says ABOUT each device operation, beside when it ran.

``jax.profiler.ProfileData`` (what ``trace.py`` reads) hands out an event's
name, start, duration and the event's own stats. The chip's compiler writes
more, once per operation, onto the event's *metadata*: ``tf_op`` (the
operation's ``op_name``: the path of ``jax.named_scope``s and transforms it
was traced under, then ``:`` and an op type that JAX leaves empty),
``hlo_category``, ``flops`` and ``bytes_accessed``. This module reads those
from the ``.xplane.pb`` itself with a reader of the protobuf wire format for
the five messages of ``xplane.proto`` that are needed:

    XSpace         1: planes (XPlane, repeated)
    XPlane         2: name   4: event_metadata (map<int64, XEventMetadata>)
                   5: stat_metadata (map<int64, XStatMetadata>)
    XEventMetadata 1: id  2: name  5: stats (XStat, repeated)
    XStatMetadata  1: id  2: name
    XStat          1: metadata_id  2: double  3: uint64  4: int64  5: string
                   6: bytes  7: ref_value (the id of a XStatMetadata whose
                   name is the string)

(a map entry is a message with 1: key, 2: value). No TensorFlow, no protobuf
package: the machine with the chip need not have them.

Checked by ``tests/test_scopes.py`` on the traces recorded on the chip in
``testdata/``.
"""

from __future__ import annotations

import struct

from benchmarks.chipbench.trace import DEVICE_PLANE

KEPT = ("tf_op", "hlo_category", "flops", "bytes_accessed")


def _varint(buf, pos: int):
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def fields(buf):
    """Yields (field number, wire type, value) over one message: an int for a
    varint, a ``memoryview`` for a length-delimited field, the raw 8 or 4
    bytes for the fixed ones."""
    buf = memoryview(buf)
    pos, end = 0, len(buf)
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = buf[pos:pos + size], pos + size
        elif wire == 1:
            value, pos = buf[pos:pos + 8], pos + 8
        elif wire == 5:
            value, pos = buf[pos:pos + 4], pos + 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an xplane.proto message")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _map_entry(buf):
    key, value = 0, b""
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _named(buf):
    """(id, name, [raw XStat]) of a XEventMetadata or a XStatMetadata."""
    ident, name, stats = 0, "", []
    for number, wire, v in fields(buf):
        if number == 1 and wire == 0:
            ident = v
        elif number == 2 and wire == 2:
            name = _text(v)
        elif number == 5 and wire == 2:
            stats.append(v)
    return ident, name, stats


def _stat(buf, stat_names: dict):
    """(name, value) of one XStat."""
    name, value = None, None
    for number, wire, v in fields(buf):
        if number == 1:
            name = stat_names.get(v)
        elif number == 2:
            value = struct.unpack("<d", bytes(v))[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(v)
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def plane_metadata(plane_buf):
    """(plane name, {event name: {stat name: value}}) of one XPlane, with the
    stats named in ``KEPT``."""
    name, event_bufs, stat_names = "", [], {}
    for number, wire, v in fields(plane_buf):
        if number == 2 and wire == 2:
            name = _text(v)
        elif number == 4 and wire == 2:
            event_bufs.append(_map_entry(v)[1])
        elif number == 5 and wire == 2:
            key, value = _map_entry(v)
            ident, stat_name, _ = _named(value)
            stat_names[ident or key] = stat_name
    events = {}
    for buf in event_bufs:
        _, event_name, raw_stats = _named(buf)
        stats = dict(_stat(s, stat_names) for s in raw_stats)
        events[event_name] = {k: v for k, v in stats.items() if k in KEPT}
    return name, events


def read(path: str):
    """{event name: {"tf_op": ..., "hlo_category": ..., "flops": ...,
    "bytes_accessed": ...}} over the device planes of a ``.xplane.pb`` (the
    keys a metadata lacks are left out). An event's name is what
    ``trace.py`` keys ``op_seconds`` by: the whole HLO instruction."""
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for number, wire, v in fields(data):
        if number == 1 and wire == 2:
            name, events = plane_metadata(v)
            if DEVICE_PLANE.match(name):
                for event_name, stats in events.items():
                    out.setdefault(event_name, {}).update(stats)
    return out
