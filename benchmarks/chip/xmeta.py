"""Read each device op's JAX name stack from a profiler trace.

``jax.profiler.ProfileData`` gives an op's name and times but not the
stats of its event metadata, where the TPU trace keeps the op's JAX name
stack (the stat ``tf_op``, e.g. ``jit(body)/vmap()/while/body/repro.server/
repro.key_version/scatter-add``).  This module decodes just enough of the
``XSpace`` protobuf wire format to read them:

    XSpace.planes = 1                      XPlane.name = 2, .lines = 3,
    XPlane.event_metadata = 4 (map)        XPlane.stat_metadata = 5 (map)
    XLine.name = 2, .events = 4            XEvent.metadata_id = 1
    XEventMetadata.stats = 5               XStat.metadata_id = 1,
    XStatMetadata.name = 2                   .str_value = 5, .ref_value = 7

Map entries are messages with the key in field 1 and the value in field 2.
Nothing here imports JAX.
"""
from __future__ import annotations

TF_OP = "tf_op"


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """Yield ``(field number, value)`` of one message: an int for varint
    and fixed-width fields, a ``memoryview`` for length-delimited ones."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _map_entry(buf) -> tuple[int, memoryview]:
    key, val = 0, memoryview(b"")
    for num, v in fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _stat_names(plane: list) -> dict[int, str]:
    names = {}
    for num, v in plane:
        if num == 5:
            sid, meta = _map_entry(v)
            names[sid] = next((bytes(x).decode() for n, x in fields(meta) if n == 2), "")
    return names


def _tf_ops(plane: list, stat_names: dict[int, str]) -> dict[int, str]:
    """Event metadata id -> its ``tf_op`` string (ops without one are left out)."""
    out = {}
    for num, v in plane:
        if num != 4:
            continue
        mid, meta = _map_entry(v)
        for n, stat in fields(meta):
            if n != 5:
                continue
            sid, value = 0, None
            for sn, sv in fields(stat):
                if sn == 1:
                    sid = sv
                elif sn == 5:
                    value = bytes(sv).decode()
                elif sn == 7:
                    value = stat_names.get(sv)
            if stat_names.get(sid) == TF_OP and value is not None:
                out[mid] = value
    return out


def device_tf_ops(data: bytes, prefix: str) -> dict[str, list[tuple[str, list[str]]]]:
    """Per plane whose name starts with ``prefix``: per line (in file
    order), its name and the ``tf_op`` of each event (in file order),
    ``""`` where the event's metadata has none."""
    out = {}
    buf = memoryview(data)
    for num, plane_buf in fields(buf):
        if num != 1:
            continue
        plane = list(fields(plane_buf))
        name = next((bytes(v).decode() for n, v in plane if n == 2), "")
        if not name.startswith(prefix):
            continue
        tf_ops = _tf_ops(plane, _stat_names(plane))
        lines = []
        for n, line in plane:
            if n != 3:
                continue
            line_name, ops = "", []
            for ln, v in fields(line):
                if ln == 2:
                    line_name = bytes(v).decode()
                elif ln == 4:
                    mid = next((x for en, x in fields(v) if en == 1), 0)
                    ops.append(tf_ops.get(mid, ""))
            lines.append((line_name, ops))
        out[name] = lines
    return out
