"""A reader of the raw profiler trace for what ``ProfileData`` leaves out.

``jax.profiler.ProfileData`` gives each device op's name, start and
duration, but not the stats of the op's event metadata: ``tf_op`` (the
op's ``op_name``, where ``jax.named_scope`` names appear),
``hlo_category``, ``program_id``.  This
module reads them from the ``.xplane.pb`` file itself, in protobuf's wire
format, so it needs no generated ``xplane_pb2`` module.  The messages it
walks (``tsl/profiler/protobuf/xplane.proto``):

    XSpace          planes = 1 (XPlane)
    XPlane          name = 2, event_metadata = 4 (map<int64,
                    XEventMetadata>), stat_metadata = 5 (map<int64,
                    XStatMetadata>)
    XEventMetadata  name = 2, display_name = 4, stats = 5 (XStat)
    XStatMetadata   name = 2
    XStat           metadata_id = 1; the value: double = 2 (fixed64),
                    uint64 = 3, int64 = 4, str = 5, bytes = 6, or a
                    reference to an XStatMetadata whose name is the value,
                    ref = 7

A plane's lines, which hold its events, are skipped whole, so reading
costs the metadata's size, not the trace's.
"""
from __future__ import annotations

import dataclasses
import struct
from typing import Dict, Iterator, Optional, Tuple

Span = Tuple[int, int]  # [start, end) byte offsets of a length-delimited field


@dataclasses.dataclass(frozen=True)
class OpMeta:
    """What a device plane's event metadata says of one op."""

    name: str               # the instruction's text (ProfileData's name)
    display_name: str       # the instruction's name: ``fusion.12``
    tf_op: Optional[str]    # ``jit(f)/while/body/<scopes>/<primitive>:``
    hlo_category: Optional[str]
    program_id: Optional[int]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of each field in ``buf[lo:hi]``;
    a length-delimited value is its byte span, a fixed one its bytes."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield field, wire, value


def _text(buf: bytes, span: Span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", errors="replace")


def _map_entry(buf: bytes, span: Span) -> Tuple[int, Span]:
    key, value = 0, (span[0], span[0])
    for field, _, v in _fields(buf, *span):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _name(buf: bytes, span: Span) -> str:
    for field, _, v in _fields(buf, *span):
        if field == 2:
            return _text(buf, v)
    return ""


def _stat(buf: bytes, span: Span, stat_names: Dict[int, str]):
    """(name, value) of one XStat."""
    name, value = None, None
    for field, _, v in _fields(buf, *span):
        if field == 1:
            name = stat_names.get(v)
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v if field == 3 or v < 1 << 63 else v - (1 << 64)
        elif field in (5, 6):
            value = _text(buf, v)
        elif field == 7:
            value = stat_names.get(v)
    return name, value


def _plane_ops(buf: bytes, span: Span) -> Dict[int, OpMeta]:
    events, stats = [], []
    for field, _, v in _fields(buf, *span):
        if field == 4:
            events.append(_map_entry(buf, v))
        elif field == 5:
            stats.append(_map_entry(buf, v))
    stat_names = {k: _name(buf, v) for k, v in stats}
    ops = {}
    for key, ev in events:
        text = display = ""
        st: Dict[str, object] = {}
        for field, _, v in _fields(buf, *ev):
            if field == 2:
                text = _text(buf, v)
            elif field == 4:
                display = _text(buf, v)
            elif field == 5:
                k, val = _stat(buf, v, stat_names)
                st[k] = val
        ops[key] = OpMeta(name=text, display_name=display,
                          tf_op=st.get("tf_op"),
                          hlo_category=st.get("hlo_category"),
                          program_id=st.get("program_id"))
    return ops


def device_op_metadata(path: str, plane_prefix: str = "/device:"
                       ) -> Dict[str, Dict[Tuple[Optional[int], str], OpMeta]]:
    """For each plane whose name starts with ``plane_prefix``: its ops'
    metadata keyed by (program id, op text).  An op's text starts with its
    instruction name, which is unique within its program, so the key is
    unique; ``ProfileData`` names each device event by that text and the
    plane's ``XLA Modules`` line names the program running it
    (``jit_f(<program id>)``)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, _, v in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name = ""
        for pf, _, pv in _fields(buf, *v):
            if pf == 2:
                name = _text(buf, pv)
                break
        if not name.startswith(plane_prefix):
            continue
        out[name] = {(m.program_id, m.name): m
                     for m in _plane_ops(buf, v).values()}
    return out
