"""Cut a recorded `.xplane.pb` down to a fixture for `scope_reduce`:

    python3 -m perfbench.tests.cut_scopes <in.xplane.pb> <out.xplane.pb.gz> [steps]

`cut_trace.py`'s cut (the first `steps` `perfbench.step` spans, the
lines the reducers read, no event statistics), then only the first
chip's plane and the host's, and of the statistics that hang on the
kept events' metadata only `scope_reduce.OP_NAME_STAT`: the source
stacks beside it are most of a trace's bytes.
"""

import gzip
import os
import sys
import tempfile

from perfbench import scope_reduce as sr
from perfbench import trace_reduce as tr
from perfbench.tests.cut_trace import fields, first, main as cut, message


def slim_plane(plane: bytes) -> bytes:
    parsed = fields(plane)
    wanted = set()
    for number, _, value, _ in parsed:
        if number == 5:
            meta = fields(first(fields(value), 2, b""))
            if first(meta, 2, b"").decode() == sr.OP_NAME_STAT:
                wanted.add(first(meta, 1))
    out = bytearray()
    for number, _, value, raw in parsed:
        if number == 5 and first(fields(first(
                fields(value), 2, b"")), 1) not in wanted:
            continue
        if number == 4:
            entry = fields(value)
            meta = b"".join(
                r for n, _, v, r in fields(first(entry, 2, b""))
                if n != 5 or first(fields(v), 1) in wanted)
            raw = message(4, b"".join(
                message(2, meta) if n == 2 else r
                for n, _, _, r in entry))
        out += raw
    return bytes(out)


def main(src, dst, steps=2):
    with tempfile.TemporaryDirectory() as tmp:
        whole = os.path.join(tmp, "cut.xplane.pb.gz")
        cut(src, whole, steps)
        with gzip.open(whole) as f:
            space = fields(f.read())
    planes = {first(fields(v), 2, b"").decode(): v
              for n, _, v, _ in space if n == 1}
    chip = min((name for name in planes if tr.DEVICE_PLANE.match(name)),
               key=lambda name: int(tr.DEVICE_PLANE.match(name).group(1)))
    out = message(1, slim_plane(planes[chip])) + \
        message(1, planes["/host:CPU"])
    with gzip.GzipFile(dst, "wb", mtime=0) as f:
        f.write(out)
    print(f"{dst}: {len(out)} bytes before gzip")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], *map(int, sys.argv[3:]))
