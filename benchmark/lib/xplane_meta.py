"""What ``jax.profiler.ProfileData`` does not show of an ``.xplane.pb``:
the statistics kept on each event's *metadata*.

The TPU runtime writes an HLO instruction's properties (its framework
``op_name``, its category, FLOPs, bytes accessed) once, on the
``XEventMetadata`` that all of the instruction's events share, and
``ProfileData`` lists only the statistics of the events themselves. This
module reads those metadata statistics straight from the protobuf wire
format, so it needs no generated ``xplane_pb2`` (the only one installed
is tensorflow's, which this repository does not depend on). Events,
starts and durations keep coming from ``ProfileData``
(``trace_reduce.load_xplane``); the two join on the event's name.

The fields read (tsl/profiler/protobuf/xplane.proto)::

    XSpace          1 planes*
    XPlane          2 name, 4 event_metadata (map), 5 stat_metadata (map)
    map entry       1 key, 2 value
    XEventMetadata  1 id, 2 name, 4 display_name, 5 stats*
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
                    6 bytes, 7 ref (the id of a stat metadata whose name
                    is the value)

:func:`hlo_op_names` walks a serialized ``HloProto`` with the same
reader: the fallback for a trace whose device planes carry no
``op_name`` but whose ``/host:metadata`` plane keeps each program.
"""

from __future__ import annotations

import struct
from typing import Dict, Iterator, Tuple

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, at: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """``(field number, wire type, value)`` of one message: an ``int``
    for a varint, the raw bytes for the other three wire types."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value, at = _varint(buf, at)
        elif wire == BYTES:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif wire == FIXED64:
            value, at = buf[at:at + 8], at + 8
        elif wire == FIXED32:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not an "
                             "xplane.pb, or groups, which it never has")
        yield number, wire, value


def _signed(value: int) -> int:
    """An ``int64`` field's varint is its two's complement."""
    return value - (1 << 64) if value >> 63 else value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for number, _, v in fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf: bytes, stat_names: Dict[int, str]):
    """``(name, value)`` of one ``XStat``."""
    name, value = None, None
    for number, _, v in fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 6:
            value = bytes(v)
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf: bytes) -> Tuple[str, Dict[str, Dict[str, object]]]:
    name, events, stat_names = "", [], {}
    for number, _, v in fields(buf):
        if number == 2:
            name = v.decode()
        elif number == 4:
            events.append(_map_entry(v)[1])
        elif number == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next(
                (s.decode() for n, _, s in fields(meta) if n == 2), str(key))
    out: Dict[str, Dict[str, object]] = {}
    for meta in events:
        event_name, stats = "", {}
        for number, _, v in fields(meta):
            if number == 2:
                event_name = v.decode("utf-8", "replace")
            elif number == 5:
                key, value = _stat(v, stat_names)
                stats[key] = value
        out[event_name] = stats
    return name, out


def read_metadata(data: bytes) -> Dict[str, Dict[str, Dict[str, object]]]:
    """``{plane name: {event name: {stat name: value}}}`` of a
    serialized ``XSpace``: every plane, every event metadata (those
    without statistics too, with ``{}``), keyed by the name that
    ``ProfileData`` gives the metadata's events."""
    out: Dict[str, Dict[str, Dict[str, object]]] = {}
    for number, _, v in fields(data):
        if number == 1:
            name, events = _plane(v)
            out[name] = events
    return out


def read_metadata_file(path: str) -> Dict[str, Dict[str, Dict[str, object]]]:
    with open(path, "rb") as f:
        return read_metadata(f.read())


# ------------------------------------------------------------------ #
# the fallback: op names out of a program's HloProto                 #
# ------------------------------------------------------------------ #
def hlo_op_names(hlo_proto: bytes) -> Dict[str, str]:
    """``{instruction name: metadata.op_name}`` of a serialized
    ``HloProto`` (xla/service/hlo.proto: ``HloProto.hlo_module`` 1,
    ``HloModuleProto.computations`` 3, ``HloComputationProto
    .instructions`` 2, ``HloInstructionProto.name`` 1 and ``.metadata``
    7, ``OpMetadata.op_name`` 2). Instructions without one are left
    out."""
    out: Dict[str, str] = {}
    for n_module, _, module in fields(hlo_proto):
        if n_module != 1:
            continue
        for n_comp, _, computation in fields(module):
            if n_comp != 3:
                continue
            for n_instr, _, instruction in fields(computation):
                if n_instr != 2:
                    continue
                name, op_name = "", ""
                for number, _, v in fields(instruction):
                    if number == 1:
                        name = v.decode()
                    elif number == 7:
                        op_name = next((s.decode("utf-8", "replace")
                                        for n, _, s in fields(v) if n == 2),
                                       "")
                if name and op_name:
                    out[name] = op_name
    return out
