"""The per-operation metadata of a profiler trace (``.xplane.pb``).

``jax.profiler.ProfileData`` gives each device event its name and times
but not its metadata's stats, where the TPU profiler keeps the scope path
of the operation (``tf_op``: ``jit(solve)/while/body/amg_l0_presmooth/...``)
and its HLO category.  This module reads just those from the serialized
``XSpace`` with a minimal protobuf wire-format decoder (field numbers from
``tsl/profiler/protobuf/xplane.proto``), skipping the event lines, which
``ProfileData`` reads far faster.
"""

from __future__ import annotations

# tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_MAP_KEY, _MAP_VALUE = 1, 2
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_METADATA_ID, _STAT_STR, _STAT_REF = 1, 5, 7
_STAT_INTS = (3, 4)  # uint64_value, int64_value


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` pairs of one message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields skipped."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
            yield num, val
        elif wire == 2:
            size, i = _varint(buf, i)
            yield num, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _text(mv) -> str:
    return bytes(mv).decode("utf-8", "replace")


def op_metadata(data: bytes, plane_prefix: str = "/device:",
                keys=("tf_op", "hlo_category")) -> dict:
    """``{plane name: {event name: {stat: str}}}`` for the planes whose name
    starts with ``plane_prefix``; only the string stats named in ``keys``."""
    out = {}
    for num, plane in fields(data):
        if num != _SPACE_PLANES:
            continue
        name, events, stat_names = None, [], {}
        for pnum, val in fields(plane):
            if pnum == _PLANE_NAME:
                name = _text(val)
            elif pnum == _PLANE_EVENT_METADATA:
                events.append(val)
            elif pnum == _PLANE_STAT_METADATA:
                for mnum, md in fields(val):
                    if mnum != _MAP_VALUE:
                        continue
                    sid = sname = None
                    for snum, sval in fields(md):
                        if snum == _STAT_MD_ID:
                            sid = sval
                        elif snum == _STAT_MD_NAME:
                            sname = _text(sval)
                    stat_names[sid] = sname
        if name is None or not name.startswith(plane_prefix):
            continue
        table = out.setdefault(name, {})
        for entry in events:
            for mnum, md in fields(entry):
                if mnum != _MAP_VALUE:
                    continue
                ev_name, stats = None, {}
                for enum, ev in fields(md):
                    if enum == _EVENT_MD_NAME:
                        ev_name = _text(ev)
                    elif enum == _EVENT_MD_STATS:
                        key = value = None
                        for snum, sval in fields(ev):
                            if snum == _STAT_METADATA_ID:
                                key = stat_names.get(sval)
                            elif snum == _STAT_STR:
                                value = _text(sval)
                            elif snum == _STAT_REF:
                                value = stat_names.get(sval)
                        if key in keys and value is not None:
                            stats[key] = value
                if ev_name is not None:
                    table.setdefault(ev_name, stats)
    return out
