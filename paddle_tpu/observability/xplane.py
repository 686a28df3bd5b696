"""Stdlib-only XSpace (.xplane.pb) decoder: the device half of a capture.

`jax.profiler.trace` writes the device timeline as an XSpace protobuf
(tensorflow/tsl `xplane.proto`) — planes of lines of events, with names
and per-event stats interned through metadata tables. jax has a typed
reader (`jax.profiler.ProfileData`, see `_jax_compat.profile_data`), but
the offline tools must be able to read a capture from a process that must
not import jax at all (a chip belongs to one process).

This module is a minimal protobuf *wire-format* decoder for exactly the
XSpace fields the deviceprof parser needs. The wire format is stable by
protobuf's own compatibility rules, unknown fields are skipped, and the
whole thing is stdlib-only — importable standalone (importlib by file
path) like flight_recorder.py, which is how tools/xplane_summary.py reads
a capture without touching the backend.

Decoded model (duck-typed to match jax.profiler.ProfileData's shape so
the parser accepts either):

  XSpace.planes -> XPlane(name, lines, stats)
  XPlane.lines  -> XLine(name, events)
  XLine.events  -> XEvent(name, duration_ns, offset_ns, occurrences,
                          stats: {stat_name: value, refs resolved})
"""
import struct

__all__ = ["XSpace", "XPlane", "XLine", "XEvent", "DecodeError"]


class DecodeError(ValueError):
    """The bytes are not a parseable XSpace protobuf."""


def _varint(buf, i):
    shift = 0
    val = 0
    while True:
        try:
            b = buf[i]
        except IndexError:
            raise DecodeError(f"truncated varint at offset {i}") from None
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7
        if shift > 70:
            raise DecodeError(f"varint overflow at offset {i}")


def _signed64(v):
    return v - (1 << 64) if v >= (1 << 63) else v


def _fields(buf):
    """Yield (field_number, wire_type, raw_value) over one message's bytes.
    Varints come out as ints; length-delimited as bytes; fixed as bytes."""
    i = 0
    n = len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fn, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = buf[i:i + 8]
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            if len(v) != ln:
                raise DecodeError(f"truncated field {fn} at offset {i}")
            i += ln
        elif wt == 5:
            v = buf[i:i + 4]
            i += 4
        else:
            raise DecodeError(f"unsupported wire type {wt} (field {fn})")
        yield fn, wt, v


def _map_entry(buf):
    """protobuf map<int64, Msg> entry -> (key, value_bytes)."""
    key, val = None, b""
    for fn, _, v in _fields(buf):
        if fn == 1:
            key = v
        elif fn == 2:
            val = v
    return key, val


def _stat_value(fn, wt, v):
    """XStat oneof value by field number (2=double 3=uint64 4=int64
    5=str 6=bytes 7=ref)."""
    if fn == 2:
        return struct.unpack("<d", v)[0] if wt == 1 else float(v)
    if fn == 3:
        return int(v)
    if fn == 4:
        return _signed64(int(v))
    if fn == 5:
        return v.decode("utf-8", "replace")
    if fn == 6:
        return v
    if fn == 7:
        return ("__ref__", int(v))
    return None


def _decode_stat(buf):
    mid, value = None, None
    for fn, wt, v in _fields(buf):
        if fn == 1:
            mid = int(v)
        else:
            sv = _stat_value(fn, wt, v)
            if sv is not None:
                value = sv
    return mid, value


class XEvent:
    __slots__ = ("name", "duration_ns", "offset_ns", "occurrences", "stats")

    def __init__(self, name, duration_ns, offset_ns, occurrences, stats):
        self.name = name
        self.duration_ns = duration_ns
        self.offset_ns = offset_ns
        self.occurrences = occurrences
        self.stats = stats

    def __repr__(self):
        return (f"XEvent({self.name!r}, dur_ns={self.duration_ns}, "
                f"stats={self.stats})")


class XLine:
    __slots__ = ("name", "events")

    def __init__(self, name, events):
        self.name = name
        self.events = events

    def __repr__(self):
        return f"XLine({self.name!r}, {len(self.events)} events)"


class XPlane:
    __slots__ = ("name", "lines", "stats")

    def __init__(self, name, lines, stats):
        self.name = name
        self.lines = lines
        self.stats = stats

    def __repr__(self):
        return f"XPlane({self.name!r}, {len(self.lines)} lines)"


def _decode_meta_name(buf):
    """XEventMetadata / XStatMetadata -> name (field 2, display_name 4
    as fallback for events)."""
    name, display = "", ""
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = v.decode("utf-8", "replace")
        elif fn == 4 and isinstance(v, bytes):
            display = v.decode("utf-8", "replace")
    return name or display


def _resolve(value, stat_names):
    if isinstance(value, tuple) and len(value) == 2 and value[0] == "__ref__":
        return stat_names.get(value[1], value[1])
    return value


def _decode_event(buf, event_names, stat_names):
    mid = None
    dur_ps = 0
    off_ps = 0
    occ = 1
    stats = {}
    for fn, _, v in _fields(buf):
        if fn == 1:
            mid = int(v)
        elif fn == 2:
            off_ps = _signed64(int(v))
        elif fn == 3:
            dur_ps = _signed64(int(v))
        elif fn == 5:
            occ = int(v)
        elif fn == 4:
            smid, sval = _decode_stat(v)
            sname = stat_names.get(smid, smid)
            stats[sname] = _resolve(sval, stat_names)
    return XEvent(event_names.get(mid, str(mid)), dur_ps // 1000,
                  off_ps // 1000, occ, stats)


def _decode_line(buf, event_names, stat_names):
    name, display = "", ""
    raw_events = []
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = v.decode("utf-8", "replace")
        elif fn == 11:
            display = v.decode("utf-8", "replace")
        elif fn == 4:
            raw_events.append(v)
    events = [_decode_event(e, event_names, stat_names) for e in raw_events]
    return XLine(name or display, events)


def _decode_plane(buf):
    name = ""
    raw_lines = []
    event_names = {}
    stat_names = {}
    raw_stats = []
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = v.decode("utf-8", "replace")
        elif fn == 3:
            raw_lines.append(v)
        elif fn == 4:
            k, m = _map_entry(v)
            event_names[k] = _decode_meta_name(m)
        elif fn == 5:
            k, m = _map_entry(v)
            stat_names[k] = _decode_meta_name(m)
        elif fn == 6:
            raw_stats.append(v)
    stats = {}
    for s in raw_stats:
        smid, sval = _decode_stat(s)
        stats[stat_names.get(smid, smid)] = _resolve(sval, stat_names)
    lines = [_decode_line(ln, event_names, stat_names) for ln in raw_lines]
    return XPlane(name, lines, stats)


class XSpace:
    __slots__ = ("planes",)

    def __init__(self, planes):
        self.planes = planes

    @classmethod
    def from_bytes(cls, data):
        if not data:
            raise DecodeError("empty XSpace buffer")
        planes = []
        for fn, _, v in _fields(data):
            if fn == 1:
                planes.append(_decode_plane(v))
        return cls(planes)

    @classmethod
    def from_file(cls, path):
        with open(path, "rb") as f:
            data = f.read()
        try:
            return cls.from_bytes(data)
        except DecodeError:
            raise
        except Exception as e:                               # noqa: BLE001
            raise DecodeError(f"{path}: {type(e).__name__}: {e}") from None

    def __repr__(self):
        return f"XSpace({[p.name for p in self.planes]})"
