"""Write a small ``.xplane.pb`` by hand, for tests: the profiler's XSpace
protobuf needs only varints and length-prefixed fields, so no generated
module (tensorflow's ``xplane_pb2``, which ``make_synthetic_xplane.py``
used) has to be imported. Field numbers are xplane.proto's.

    write(path, {"/device:TPU:0": {"XLA Ops": [("%fusion.1 = ...", 1.0, 0.5)]},
                 "/host:CPU": {"main/1": [("capture_window", 0.5, 9.0,
                                           {"wall_s": 1e9})]}})

An event is ``(name, start_ms, duration_ms[, stats])``; a stat value is a
float (written as a double) or a string.
"""

from __future__ import annotations

import struct


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _bytes(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _stat(meta_id: int, value) -> bytes:
    if isinstance(value, str):
        return _int(1, meta_id) + _bytes(5, value.encode())
    return _int(1, meta_id) + _varint(2 << 3 | 1) + struct.pack("<d", float(value))


def _plane(plane_id: int, name: str, lines: dict) -> bytes:
    event_ids, stat_ids, body = {}, {}, b""
    for line_id, (line_name, events) in enumerate(lines.items()):
        line = _int(1, line_id) + _bytes(2, line_name.encode())
        for name_, start_ms, dur_ms, *stats in events:
            meta = event_ids.setdefault(name_, len(event_ids) + 1)
            ev = (_int(1, meta) + _int(2, round(start_ms * 1e9))
                  + _int(3, round(dur_ms * 1e9)))
            for key, value in (stats[0] if stats else {}).items():
                ev += _bytes(4, _stat(stat_ids.setdefault(key, len(stat_ids) + 1), value))
            line += _bytes(4, ev)
        body += _bytes(3, line)
    for field, ids in ((4, event_ids), (5, stat_ids)):  # map<int64, metadata>
        for name_, i in ids.items():
            entry = _int(1, i) + _bytes(2, name_.encode())
            body += _bytes(field, _int(1, i) + _bytes(2, entry))
    return _int(1, plane_id) + _bytes(2, name.encode()) + body


def write(path: str, planes: dict) -> str:
    """``planes``: plane name -> {line name -> [event, ...]}."""
    with open(path, "wb") as f:
        for i, (name, lines) in enumerate(planes.items()):
            f.write(_bytes(1, _plane(i, name, lines)))
    return path
