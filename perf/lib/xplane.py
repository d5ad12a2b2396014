"""A small reader of the profiler's ``.xplane.pb`` that keeps what
``jax.profiler.ProfileData`` does not hand out: the stats of an event's
METADATA (on a TPU plane the operation's ``op_name``, with the
``jax.named_scope`` it ran under, is one of them) beside the event's own
(a ``TraceAnnotation``'s keyword arguments). Plain protobuf wire format,
no schema module: XSpace{1: planes}; XPlane{2: name, 3: lines, 4:
event_metadata map, 5: stat_metadata map}; XLine{2: name, 3: timestamp_ns,
4: events, 11: display_name}; XEvent{1: metadata_id, 2: offset_ps, 3:
duration_ps, 4: stats}; XStat{1: metadata_id, 2: double, 3: uint64, 4:
int64, 5: str, 6: bytes, 7: ref}; XEventMetadata{1: id, 2: name, 4:
display_name, 5: stats}; XStatMetadata{1: id, 2: name}.
"""
import glob
import os
import struct


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message's top level."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield num, wire, val


def _stat(buf, stat_names):
    name = value = None
    for num, _wire, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num in (3, 4):
            value = val - (1 << 64) if num == 4 and val >> 63 else val
        elif num in (5, 6):
            value = bytes(val).decode("utf-8", "replace")
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key = value = None
    for num, _wire, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def _plane(buf):
    name, lines, meta_bufs, stat_names = "", [], {}, {}
    for num, _wire, val in _fields(buf):
        if num == 2:
            name = bytes(val).decode()
        elif num == 3:
            lines.append(val)
        elif num == 4:
            key, value = _map_entry(val)
            meta_bufs[key] = value
        elif num == 5:
            key, value = _map_entry(val)
            for n2, _w2, v2 in _fields(value):
                if n2 == 2:
                    stat_names[key] = bytes(v2).decode()
    metadata = {}
    for key, mbuf in meta_bufs.items():
        mname, stats = "", {}
        for n2, _w2, v2 in _fields(mbuf):
            if n2 == 2:
                mname = bytes(v2).decode("utf-8", "replace")
            elif n2 == 5:
                k, v = _stat(v2, stat_names)
                stats[k] = v
        metadata[key] = (mname, stats)
    out_lines = []
    for lbuf in lines:
        lname, t0, events = "", 0, []
        for n2, _w2, v2 in _fields(lbuf):
            if n2 == 2:
                lname = bytes(v2).decode()
            elif n2 == 3:
                t0 = v2
            elif n2 == 4:
                events.append(v2)
        parsed = []
        for ebuf in events:
            mid = off = dur = 0
            stats = {}
            for n3, _w3, v3 in _fields(ebuf):
                if n3 == 1:
                    mid = v3
                elif n3 == 2:
                    off = v3
                elif n3 == 3:
                    dur = v3
                elif n3 == 4:
                    k, v = _stat(v3, stat_names)
                    stats[k] = v
            mname, mstats = metadata.get(mid, ("", {}))
            parsed.append({"name": mname, "start_ns": t0 + off // 1000,
                           "dur_ns": dur // 1000,
                           "stats": dict(mstats, **stats)})
        out_lines.append({"name": lname, "events": parsed})
    return {"name": name, "lines": out_lines}


def read(path):
    """The planes of one ``.xplane.pb``: ``[{name, lines: [{name, events:
    [{name, start_ns, dur_ns, stats}]}]}]``, an event's stats being its
    metadata's overlaid by its own."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane(val) for num, _wire, val in _fields(buf) if num == 1]


def newest(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]
