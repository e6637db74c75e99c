"""The profiler's ``.xplane.pb`` as plain data, read from the file's wire
format.

The profiler's own reader (``jax.profiler.ProfileData``) leaves out the
statistics of an event's metadata, and a device event's op name (the
``tf_op`` statistic: the program's scopes, Flax module paths and kernel
names) is one of them.
"""

from __future__ import annotations

import struct

#: the statistic of a device event's metadata that holds its op name, as
#: "<op_name>:<op type>" (read off a chip trace, PR 26)
SCOPE_STAT = "tf_op"


def fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: varints
    as ints, fixed 64/32 and length-delimited as bytes."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        value, shift = 0, 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            val = varint()
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        elif wire == 2:
            size = varint()
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def signed(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def stat(buf: bytes, stat_names: dict):
    """(name, value) of one XStat; a ``ref_value`` is a stat name used as
    a string."""
    name, value = None, None
    for num, _wire, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = signed(val)
        elif num in (5, 6):
            value = val.decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, "")
    return name, value


def map_entry(buf: bytes):
    key, value = 0, b""
    for num, _wire, val in fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def load_planes(path: str) -> list:
    """The trace as plain data: planes of lines of events ``(start_ns,
    duration_ns, name, stats)``, an event's statistics merged over its
    metadata's. The profiler's own reader (``jax.profiler.ProfileData``)
    leaves the metadata's statistics out, and the op name is one."""
    with open(path, "rb") as f:
        space = f.read()
    planes = []
    for num, _wire, plane_buf in fields(space):
        if num != 1:
            continue
        name, lines, event_meta, stat_names = "", [], {}, {}
        for pnum, _w, val in fields(plane_buf):
            if pnum == 2:
                name = val.decode()
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                key, meta = map_entry(val)
                event_meta[key] = meta
            elif pnum == 5:
                key, meta = map_entry(val)
                stat_names[key] = next(
                    (v.decode() for n, _w2, v in fields(meta) if n == 2), "")
        metadata = {}
        for key, meta in event_meta.items():
            ev_name, ev_stats = "", {}
            for mnum, _w, val in fields(meta):
                if mnum == 2:
                    ev_name = val.decode("utf-8", "replace")
                elif mnum == 5:
                    k, v = stat(val, stat_names)
                    ev_stats[k] = v
            metadata[key] = (ev_name, ev_stats)
        out_lines = []
        for line_buf in lines:
            line_name, t0_ns, events = "", 0, []
            for lnum, _w, val in fields(line_buf):
                if lnum == 2:
                    line_name = val.decode()
                elif lnum == 3:
                    t0_ns = signed(val)
                elif lnum == 4:
                    events.append(val)
            out_events = []
            for ev_buf in events:
                meta_id, offset_ps, duration_ps, stats = 0, 0, 0, {}
                for enum_, _w, val in fields(ev_buf):
                    if enum_ == 1:
                        meta_id = val
                    elif enum_ == 2:
                        offset_ps = signed(val)
                    elif enum_ == 3:
                        duration_ps = signed(val)
                    elif enum_ == 4:
                        k, v = stat(val, stat_names)
                        stats[k] = v
                ev_name, meta_stats = metadata.get(meta_id, ("", {}))
                out_events.append((t0_ns + offset_ps // 1000,
                                   duration_ps // 1000, ev_name,
                                   dict(meta_stats, **stats)))
            out_lines.append({"name": line_name, "events": out_events})
        planes.append({"name": name, "lines": out_lines})
    return planes
