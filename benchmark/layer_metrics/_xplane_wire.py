"""What ``jax.profiler.ProfileData`` does not hand out, read from the
``.xplane.pb`` bytes themselves: a plane's *event metadata* (the
per-operation record a TPU trace keeps its statistics on, not on each
event) and the HLO modules the profile embeds in ``/host:metadata``.

Protocol-buffer wire format, decoded by hand so that no run imports
TensorFlow for its generated classes (25 s). Field numbers are
``tsl/profiler/protobuf/xplane.proto``'s and ``xla/service/hlo.proto``'s:

    XSpace.planes=1
    XPlane.name=2 .lines=3 .event_metadata=4 (map) .stat_metadata=5 (map) .stats=6
    XLine.name=2 .events=4
    XEvent.metadata_id=1 .stats=4
    XEventMetadata.id=1 .name=2 .display_name=4 .stats=5
    XStatMetadata.id=1 .name=2
    XStat.metadata_id=1 .double=2 .uint64=3 .int64=4 .str=5 .bytes=6 .ref=7
    HloProto.hlo_module=1; HloModuleProto.name=1 .computations=3
    HloComputationProto.instructions=2
    HloInstructionProto.name=1 .metadata=7; OpMetadata.op_name=2

Only length-delimited fields are descended into, and a plane's lines
are skipped by their length, so reading the metadata of a 300k-event
trace costs what its few thousand metadata records cost.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, List, Tuple

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` over one message: an int
    for varints and fixed-width values (raw bits), a memoryview for
    length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == VARINT:
            val, i = _varint(buf, i)
        elif wt == BYTES:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wt == FIXED64:
            val = struct.unpack_from("<Q", buf, i)[0]
            i += 8
        elif wt == FIXED32:
            val = struct.unpack_from("<I", buf, i)[0]
            i += 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}: not an xplane")
        yield num, wt, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf, stat_names: Dict[int, str]):
    """One XStat as ``(name, value)``; a ``ref`` value is the name of
    the stat metadata it points at (how strings are shared)."""
    name, value = None, None
    for num, wt, val in fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", struct.pack("<Q", val))[0]
        elif num in (3, 4):
            value = val
        elif num == 5:
            value = _text(val)
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = stat_names.get(val, "")
    return name, value


def planes(space) -> Iterator[memoryview]:
    for num, wt, val in fields(space):
        if num == 1 and wt == BYTES:
            yield val


def plane_name(plane) -> str:
    for num, wt, val in fields(plane):
        if num == 2 and wt == BYTES:
            return _text(val)
    return ""


def _map_value(entry):
    for num, wt, val in fields(entry):
        if num == 2 and wt == BYTES:
            return val
    return None


def plane_metadata(plane) -> Dict[str, object]:
    """``{"name", "stat_names": {id: name}, "events": {id: {"name",
    "display_name", "stats": {name: value}}}}`` of one plane; its lines
    are not read."""
    name, raw_events, stat_names = "", [], {}
    for num, wt, val in fields(plane):
        if wt != BYTES:
            continue
        if num == 2:
            name = _text(val)
        elif num == 4:
            raw_events.append(val)
        elif num == 5:
            meta = _map_value(val)
            if meta is not None:
                sid, sname = 0, ""
                for n2, w2, v2 in fields(meta):
                    if n2 == 1:
                        sid = v2
                    elif n2 == 2 and w2 == BYTES:
                        sname = _text(v2)
                stat_names[sid] = sname
    events = {}
    for entry in raw_events:
        meta = _map_value(entry)
        if meta is None:
            continue
        rec = {"name": "", "display_name": "", "stats": {}}
        eid = 0
        for n2, w2, v2 in fields(meta):
            if n2 == 1:
                eid = v2
            elif n2 == 2 and w2 == BYTES:
                rec["name"] = _text(v2)
            elif n2 == 4 and w2 == BYTES:
                rec["display_name"] = _text(v2)
            elif n2 == 5 and w2 == BYTES:
                k, v = _stat(v2, stat_names)
                rec["stats"][k] = v
        events[eid] = rec
    return {"name": name, "stat_names": stat_names, "events": events}


def hlo_op_names(hlo_proto) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction name: op_name})`` of one serialized
    ``HloProto``, over every computation (a fused or a loop body's
    instruction too: the trace names those as it names the rest)."""
    module, out = "", {}
    for num, wt, val in fields(hlo_proto):
        if num != 1 or wt != BYTES:
            continue
        for n2, w2, v2 in fields(val):
            if n2 == 1 and w2 == BYTES:
                module = _text(v2)
            elif n2 == 3 and w2 == BYTES:
                for n3, w3, v3 in fields(v2):
                    if n3 != 2 or w3 != BYTES:
                        continue
                    iname, op_name = "", ""
                    for n4, w4, v4 in fields(v3):
                        if n4 == 1 and w4 == BYTES:
                            iname = _text(v4)
                        elif n4 == 7 and w4 == BYTES:
                            for n5, w5, v5 in fields(v4):
                                if n5 == 2 and w5 == BYTES:
                                    op_name = _text(v5)
                    if iname:
                        out[iname] = op_name
    return module, out


def embedded_hlo(space) -> List[Tuple[str, Dict[str, str]]]:
    """The HLO modules a profile embeds: every bytes-valued statistic
    of ``/host:metadata``'s event metadata that parses as an
    ``HloProto`` with instructions."""
    out = []
    for plane in planes(space):
        if plane_name(plane) != "/host:metadata":
            continue
        for rec in plane_metadata(plane)["events"].values():
            for value in rec["stats"].values():
                if not isinstance(value, bytes) or len(value) < 16:
                    continue
                try:
                    module, names = hlo_op_names(value)
                except (ValueError, IndexError, struct.error):
                    continue
                if names:
                    # the record's name carries the program id, as the
                    # XLA Modules events' do; the module's own does not
                    out.append((rec["name"] or module, names))
    return out
