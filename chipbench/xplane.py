"""Read a profiler trace (``.xplane.pb[.gz]``) with everything in it.

``trace_reduce.load`` reads a trace through ``jax.profiler.ProfileData``,
which shows an event's own stats and not the stats of the event's
**metadata**. On a TPU's device planes that is where the compiler's account
of an operation is kept: ``tf_op`` (jax's name stack, the named scopes among
it: ``jit(step)/transpose(jvp(attention))/attention_core/mul``),
``hlo_category``, ``flops``, ``bytes_accessed``, ``source``. This module reads
the file itself and hands every event both sets of stats, the event's own over
its metadata's.

It is a plain reader of the protobuf wire format, so it needs no package the
machine with the chip may lack. The seven messages (tsl's ``xplane.proto``;
the numbers are the fields'):

    XSpace          planes 1
    XPlane          name 2, lines 3, event_metadata 4 and stat_metadata 5
                    (maps: entries of key 1, value 2), stats 6
    XLine           name 2, timestamp_ns 3, events 4, display_name 11
    XEvent          metadata_id 1, offset_ps 2, duration_ps 3, stats 4
    XEventMetadata  id 1, name 2, stats 5
    XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                    bytes 6, ref 7 (the id of a stat metadata whose name is
                    the value)
    XStatMetadata   id 1, name 2

A length-delimited field is sliced out by its length and never walked, so what
is not read (display names, descriptions, child ids) costs nothing.
``tests/test_xplane.py`` checks the reader against ``trace_reduce.load`` on the
recorded traces, and against tensorflow's ``xplane_pb2`` where that imports.
"""

import gzip
import struct

from chipbench.trace_reduce import Event

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def fields(buf, at, end):
    """The fields of the message in ``buf[at:end]``: (number, wire type,
    value). A varint's value is the unsigned number, a length-delimited
    field's the (start, end) of its payload in ``buf``, a fixed field's
    its offset. The varint loop is written out three times: a call for each
    varint would cost more than the decoding, and a trace has millions."""
    while at < end:
        key = shift = 0
        while True:
            byte = buf[at]
            at += 1
            key |= (byte & 0x7F) << shift
            if byte < 0x80:
                break
            shift += 7
        number, wire = key >> 3, key & 7
        if wire == VARINT:
            value = shift = 0
            while True:
                byte = buf[at]
                at += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            yield number, wire, value
        elif wire == BYTES:
            size = shift = 0
            while True:
                byte = buf[at]
                at += 1
                size |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            yield number, wire, (at, at + size)
            at += size
        elif wire == FIXED64:
            yield number, wire, at
            at += 8
        elif wire == FIXED32:
            yield number, wire, at
            at += 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}: not a "
                             f"protobuf message this reader knows")


def signed(value):
    """An int64 field's value from its unsigned varint."""
    return value - (1 << 64) if value >= 1 << 63 else value


def text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def stat(buf, at, end, stat_names):
    """(name, value) of one XStat."""
    name = value = None
    for number, wire, v in fields(buf, at, end):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack_from("<d", buf, v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = signed(v)
        elif number == 5:
            value = text(buf, v)
        elif number == 6:
            value = bytes(buf[v[0]:v[1]])
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def map_entry(buf, at, end):
    """(key, (start, end) of the value) of one entry of a proto map."""
    key = value = None
    for number, wire, v in fields(buf, at, end):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def plane(buf, at, end):
    """(name, {line name: [Event]}) of one XPlane."""
    name, lines, metadata_at, stat_names = "", [], [], {}
    for number, wire, v in fields(buf, at, end):
        if number == 2:
            name = text(buf, v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            metadata_at.append(v)
        elif number == 5:
            _, where = map_entry(buf, *v)
            sid, sname = 0, ""
            for n, w, x in fields(buf, *where):
                if n == 1:
                    sid = x
                elif n == 2:
                    sname = text(buf, x)
            stat_names[sid] = sname
    # event metadata: the name and the stats every event of it shares
    metadata = {}
    for span in metadata_at:
        key, where = map_entry(buf, *span)
        ename, estats = "", {}
        for n, w, x in fields(buf, *where):
            if n == 2:
                ename = text(buf, x)
            elif n == 5:
                k, value = stat(buf, x[0], x[1], stat_names)
                estats[k] = value
        metadata[key] = (ename, estats)
    out = {}
    for span in lines:
        lname, display, t0_ns, events_at = "", "", 0, []
        for n, w, x in fields(buf, *span):
            if n == 2:
                lname = text(buf, x)
            elif n == 11:
                display = text(buf, x)
            elif n == 3:
                t0_ns = signed(x)
            elif n == 4:
                events_at.append(x)
        events = out.setdefault(lname or display, [])
        for e_at, e_end in events_at:
            mid = offset_ps = duration_ps = 0
            own = None
            for n, w, x in fields(buf, e_at, e_end):
                if n == 1:
                    mid = x
                elif n == 2:
                    offset_ps = signed(x)
                elif n == 3:
                    duration_ps = signed(x)
                elif n == 4:
                    if own is None:
                        own = {}
                    k, value = stat(buf, x[0], x[1], stat_names)
                    own[k] = value
            ename, shared = metadata.get(mid, ("", {}))
            # whole nanoseconds, offset and duration each cut as
            # ProfileData cuts them, so both readers give the same times;
            # an event with no stats of its own shares its metadata's dict
            # (read it, do not write to it)
            start = t0_ns + offset_ps // 1000
            events.append(Event(
                ename, start, start + duration_ps // 1000,
                {**shared, **own} if own else shared))
    return name, out


def parse(data):
    """{plane name: {line name: [Event]}} of a serialized XSpace. An event's
    ``stats`` are its metadata's with its own laid over them."""
    buf = memoryview(data)
    planes = {}
    for number, wire, v in fields(buf, 0, len(buf)):
        if number == 1:
            name, lines = plane(buf, *v)
            merged = planes.setdefault(name, {})
            for lname, events in lines.items():
                merged.setdefault(lname, []).extend(events)
    return planes


def load(path):
    """``parse`` of an ``.xplane.pb`` or ``.xplane.pb.gz`` file."""
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        return parse(f.read())
