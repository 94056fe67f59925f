"""The `tf_op` of each device op in a profiler trace (`*.xplane.pb`).

`jax.profiler.ProfileData` gives an event's name (for a device op, its HLO
text) but not the stats of its metadata, where the op's path of named
scopes (`tf_op`, e.g. `jit(run)/dpc.doubling/while/body/gather:`) is kept.
No XPlane protobuf module is installed, so this walks the protobuf wire
format of the fields it needs:

    XSpace.planes 1; XPlane: name 2, event_metadata 4 (map), stat_metadata
    5 (map); map entry: key 1, value 2; XEventMetadata: name 2, stats 5;
    XStatMetadata: name 2; XStat: metadata_id 1, str_value 5, ref_value 7
    (the id of a stat metadata whose name is the string).
"""
from __future__ import annotations

from pathlib import Path

import devtrace

TF_OP = "tf_op"


def _varint(buf, i):
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf):
    """(field number, value) of one message: an int for a varint, bytes
    for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, v


def _map_values(plane, field):
    for f, entry in _fields(plane):
        if f == field:
            yield next((v for k, v in _fields(entry) if k == 2), b"")


def _plane_tf_ops(plane) -> dict:
    stat_names = {}
    for meta in _map_values(plane, 5):
        m = dict(_fields(meta))
        stat_names[m.get(1, 0)] = m.get(2, b"").decode()
    tf_id = next((i for i, n in stat_names.items() if n == TF_OP), None)
    out = {}
    for meta in _map_values(plane, 4):
        name, tf = "", None
        for f, v in _fields(meta):
            if f == 2:
                name = v.decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat.get(1) == tf_id:
                    tf = (stat[5].decode() if 5 in stat
                          else stat_names.get(stat.get(7), ""))
        if tf is not None:
            # one op text with two paths (two programs) names no layer
            out[name] = tf if out.get(name, tf) == tf else ""
    return out


def tf_ops(path) -> dict:
    """{device id: {op text: tf_op}} for each `/device:TPU:<id>` plane."""
    out = {}
    for f, plane in _fields(Path(path).read_bytes()):
        if f != 1:
            continue
        name = next((v.decode() for k, v in _fields(plane) if k == 2), "")
        m = devtrace.DEVICE_PLANE.match(name)
        if m:
            out[int(m.group(1))] = _plane_tf_ops(plane)
    return out
