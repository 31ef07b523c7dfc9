"""Read a profiler trace (``.xplane.pb``) with each event's metadata stats.

``jax.profiler.ProfileData`` gives an event's name and its own stats, but
not the stats of its event metadata; on a TPU that is where an operation's
``tf_op`` lives: the HLO ``op_name`` of the instruction, with the
``jax.named_scope`` names it was traced under
(``jit(chunk)/while/body/.../replay_insert/scatter``).  This module decodes
the few XPlane messages it needs straight from the protobuf wire format:

    XSpace          planes = 1
    XPlane          name = 2, lines = 3, event_metadata = 4 (map),
                    stat_metadata = 5 (map)
    XLine           name = 2, timestamp_ns = 3, events = 4
    XEvent          metadata_id = 1, offset_ps = 2, duration_ps = 3,
                    stats = 4
    XEventMetadata  id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata   id = 1, name = 2
    XStat           metadata_id = 1, double = 2, uint64 = 3, int64 = 4,
                    str = 5, bytes = 6, ref = 7 (a stat metadata's name)

``read`` returns planes, lines and events with the attributes
``ProfileData`` gives (``name``, ``lines``, ``events``, ``start_ns``,
``end_ns``, ``duration_ns``, ``stats``), so ``trace_reduce`` runs on it
unchanged, and each event also carries ``metadata_stats``.
"""
from __future__ import annotations

import glob
import os
import re
import struct
from typing import Dict, Iterator, List, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an int for a
    varint, bytes for a length-delimited field, raw bytes for the fixed
    widths."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} is not used by XPlane")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


class Event:
    """One event: whole ns from the line's timestamp and the event's
    offset and duration in ps, rounded down as ``ProfileData`` rounds
    them."""
    __slots__ = ("name", "start_ns", "end_ns", "duration_ns", "stats",
                 "metadata_stats")

    def __init__(self, name, start_ns, duration_ns, stats, metadata_stats):
        self.name = name
        self.start_ns = start_ns
        self.duration_ns = duration_ns
        self.end_ns = start_ns + duration_ns
        self.stats = stats
        self.metadata_stats = metadata_stats


class Line:
    __slots__ = ("name", "events")

    def __init__(self, name: str, events: List[Event]):
        self.name, self.events = name, events


class Plane:
    __slots__ = ("name", "lines")

    def __init__(self, name: str, lines: List[Line]):
        self.name, self.lines = name, lines


class Space:
    __slots__ = ("planes",)

    def __init__(self, planes: List[Plane]):
        self.planes = planes


def _stat(buf: bytes, stat_names: Dict[int, str]):
    mid, value = 0, None
    for num, wt, v in fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num == 3:
            value = v
        elif num == 4:
            value = _signed(v)
        elif num == 5:
            value = v.decode("utf-8", "replace")
        elif num == 6:
            value = v
        elif num == 7:
            value = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for num, _, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            value = v
    return key, value


def _plane(buf: bytes) -> Plane:
    name, lines, ev_meta, stat_names = "", [], [], {}
    for num, _, v in fields(buf):
        if num == 2:
            name = v.decode("utf-8", "replace")
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_meta.append(_map_entry(v)[1])
        elif num == 5:
            _, sm = _map_entry(v)
            sid, sname = 0, ""
            for n2, _, v2 in fields(sm):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = v2.decode("utf-8", "replace")
            stat_names[sid] = sname
    meta: Dict[int, Tuple[str, list]] = {}
    for em in ev_meta:
        mid, mname, display, mstats = 0, "", "", []
        for num, _, v in fields(em):
            if num == 1:
                mid = v
            elif num == 2:
                mname = v.decode("utf-8", "replace")
            elif num == 4:
                display = v.decode("utf-8", "replace")
            elif num == 5:
                mstats.append(_stat(v, stat_names))
        meta[mid] = (mname or display, mstats)
    out = []
    for lb in lines:
        lname, ts_ns, events = "", 0, []
        for num, _, v in fields(lb):
            if num == 2:
                lname = v.decode("utf-8", "replace")
            elif num == 3:
                ts_ns = _signed(v)
            elif num == 4:
                events.append(v)
        evs = []
        for eb in events:
            mid = off_ps = dur_ps = 0
            stats = []
            for num, _, v in fields(eb):
                if num == 1:
                    mid = v
                elif num == 2:
                    off_ps = _signed(v)
                elif num == 3:
                    dur_ps = _signed(v)
                elif num == 4:
                    stats.append(_stat(v, stat_names))
            ename, mstats = meta.get(mid, ("", []))
            evs.append(Event(ename, ts_ns + off_ps // 1000, dur_ps // 1000,
                             stats, mstats))
        out.append(Line(lname, evs))
    return Plane(name, out)


def find(path: str) -> str:
    """The one ``.xplane.pb`` under ``path`` (a file or a profiler log
    directory); the newest name where there are several."""
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read(path: str) -> Space:
    """The XSpace of the trace under ``path``."""
    with open(find(path), "rb") as f:
        buf = f.read()
    return Space([_plane(v) for num, _, v in fields(buf) if num == 1])


def tf_op(event) -> str:
    """The HLO ``op_name`` of a device event (its ``tf_op`` stat), or
    ``""``."""
    for k, v in getattr(event, "metadata_stats", ()):
        if k == "tf_op":
            return v if isinstance(v, str) else ""
    for k, v in event.stats:
        if k == "tf_op":
            return v if isinstance(v, str) else ""
    return ""


def _varint_bytes(v: int) -> bytes:
    out = bytearray()
    v &= (1 << 64) - 1
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _emit(num: int, wt: int, v) -> bytes:
    tag = _varint_bytes(num << 3 | wt)
    if wt == 0:
        return tag + _varint_bytes(v)
    if wt == 2:
        return tag + _varint_bytes(len(v)) + v
    return tag + v


_OPCODE = re.compile(rb"[a-z][a-z0-9_-]*\(")


def _short_name(name: bytes) -> bytes:
    """An HLO instruction's text up to its opcode's parenthesis: its name,
    shape and opcode, without the operands."""
    head, sep, rest = name.partition(b" = ")
    m = _OPCODE.search(rest)
    return head + sep + rest[:m.end()] if m else name


def _trim_event_metadata(buf: bytes, keep_ids) -> bytes:
    out = []
    for num, wt, v in fields(buf):
        if num == 5 and _stat_id(v) not in keep_ids:
            continue
        if num == 2:
            v = _short_name(v)
        if num in (1, 2, 4, 5):
            out.append(_emit(num, wt, v))
    return b"".join(out)


def _stat_id(buf: bytes) -> int:
    for num, _, v in fields(buf):
        if num == 1:
            return v
    return 0


def _trim_line(buf: bytes) -> bytes:
    """An XLine whose events keep their times and lose their own stats."""
    out = []
    for num, wt, v in fields(buf):
        if num == 4:
            v = b"".join(_emit(n, w, x) for n, w, x in fields(v) if n != 4)
        out.append(_emit(num, wt, v))
    return b"".join(out)


def _line_name(buf: bytes) -> str:
    for num, _, v in fields(buf):
        if num == 2:
            return v.decode("utf-8", "replace")
    return ""


def trim(path: str, out: str, planes=("/device:TPU:", "/host:CPU"),
         stats=("tf_op",)):
    """Write a copy of a trace that keeps only the planes whose names
    start with ``planes``, of a device plane only its ``XLA Ops`` line,
    and of each event only its times, its name up to its opcode and the
    metadata ``stats`` named: what ``trace_reduce`` and ``phases`` read, in
    a file small enough to keep as a test fixture."""
    with open(find(path), "rb") as f:
        buf = f.read()
    space = []
    for num, wt, v in fields(buf):
        if num != 1:
            space.append(_emit(num, wt, v))
            continue
        name, stat_names, body = "", {}, []
        for n2, w2, v2 in fields(v):
            body.append((n2, w2, v2))
            if n2 == 2:
                name = v2.decode("utf-8", "replace")
            elif n2 == 5:
                sm = _map_entry(v2)[1]
                sid = _stat_id(sm)
                for n3, _, v3 in fields(sm):
                    if n3 == 2:
                        stat_names[sid] = v3.decode("utf-8", "replace")
        if not name.startswith(planes):
            continue
        keep_ids = {i for i, s in stat_names.items() if s in stats}
        device = name.startswith(planes[0])
        plane = []
        for n2, w2, v2 in body:
            if n2 == 3 and device:
                if _line_name(v2) != "XLA Ops":
                    continue
                v2 = _trim_line(v2)
            elif n2 == 4:
                key, meta = _map_entry(v2)
                v2 = (_emit(1, 0, key) + _emit(
                    2, 2, _trim_event_metadata(meta, keep_ids)))
            plane.append(_emit(n2, w2, v2))
        space.append(_emit(1, 2, b"".join(plane)))
    with open(out, "wb") as f:
        f.write(b"".join(space))
