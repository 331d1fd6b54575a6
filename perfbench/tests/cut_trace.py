"""Cut a recorded `.xplane.pb` down to a test fixture:

    python3 -m perfbench.tests.cut_trace <in.xplane.pb> <out.xplane.pb.gz> [steps]

keeps the chips' planes with the lines the reducer reads and the host
plane's `perfbench.*` spans, only the events of the first `steps`
(default 2) `perfbench.step` spans, drops the events' statistics and
every name no kept event uses, and gzips the result. A filter on the
protobuf wire format (XSpace > XPlane > XLine > XEvent), so it needs no
schema; whatever it does not cut is copied byte for byte.
"""

import gzip
import sys

from perfbench import trace_reduce as tr

KEPT_LINES = {tr.OPS_LINE, tr.ASYNC_LINE, "Steps", "XLA Modules"}


def varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def put_varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def fields(buf):
    """[(field number, wire type, value, the field's raw bytes)]"""
    out, i = [], 0
    while i < len(buf):
        at = i
        tag, i = varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 2:
            size, i = varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            size = {1: 8, 5: 4}[wire]
            value, i = buf[i:i + size], i + size
        out.append((number, wire, value, buf[at:i]))
    return out


def message(number, body):
    return put_varint(number << 3 | 2) + put_varint(len(body)) + body


def first(parsed, number, default=0):
    return next((v for n, _, v, _ in parsed if n == number), default)


def cut_plane(plane, window_ps, host):
    parsed = fields(plane)
    names = {}                              # metadata id -> name
    for number, _, value, _ in parsed:
        if number == 4:                     # map entry: key 1, value 2
            meta = fields(first(fields(value), 2, b""))
            names[first(meta, 1)] = first(meta, 2, b"").decode()
    used, out = set(), bytearray()
    for number, _, value, raw in parsed:
        if number != 3:
            continue
        line = fields(value)
        if not host and first(line, 2, b"").decode() not in KEPT_LINES:
            continue
        base_ps = first(line, 3) * 1000
        body = bytearray()
        for n, _, v, r in line:
            if n != 4:
                body += r
                continue
            event = fields(v)
            meta_id, start = first(event, 1), base_ps + first(event, 2)
            name = names.get(meta_id, "")
            if host and not name.startswith(tr.HOST_SPAN):
                continue
            if not window_ps[0] <= start < window_ps[1]:
                continue
            used.add(meta_id)
            body += message(4, b"".join(
                r2 for n2, _, _, r2 in event if n2 != 4))
        out += message(3, bytes(body))
    head = bytearray()
    for number, _, value, raw in parsed:
        if number == 3:
            continue
        if number == 4 and first(fields(value), 1) not in used:
            continue
        head += raw
    return bytes(head + out)


def main(src, dst, steps=2):
    planes = tr.read_events(src)
    spans = sorted((s, e) for lines in planes.values()
                   for events in lines.values() for name, s, e in events
                   if name == tr.STEP_SPAN)[:steps]
    window_ps = (spans[0][0], spans[-1][1] + 1)
    with open(src, "rb") as f:
        space = fields(f.read())
    out = bytearray()
    for number, _, value, raw in space:
        if number != 1:
            continue
        name = first(fields(value), 2, b"").decode()
        if tr.DEVICE_PLANE.match(name) or name == "/host:CPU":
            out += message(1, cut_plane(value, window_ps,
                                        host=name == "/host:CPU"))
    with gzip.GzipFile(dst, "wb", mtime=0) as f:
        f.write(bytes(out))
    print(f"{dst}: {len(out)} bytes before gzip")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(int, sys.argv[3:]))
