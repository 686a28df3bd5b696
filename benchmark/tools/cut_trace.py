"""Cut a small slice out of a kept trace, as text, for a test to keep.

    python3 benchmark/tools/cut_trace.py <.xplane.pb> <out.textproto> \
        --start-ms <a> --end-ms <b> [--name-chars 1500] [--note "..."]

Keeps, of the first device plane, the `XLA Ops` and `XLA Modules` events
that overlap [a, b) ms after the plane's first operation (clipped to it,
times shifted to start near 0, names cut to --name-chars), and of
`/host:CPU` the `bench:` and `serving::` spans that overlap it. Everything
else (statistics, other lines and planes) is dropped: what is left is what
`harness/trace_reduce.py` reads, in a file a test can hold (PR 25's
`recorded_trace_train_slice.textproto` was cut the same way by hand).
Run it where tensorflow's `xplane_pb2` can be imported (the sandbox), on a
file brought back from the chip with `--keep-trace chiprun_out/<dir>`.
"""
import argparse

KEPT_LINES = ("XLA Ops", "XLA Modules")
HOST_PREFIXES = ("bench:", "serving::")


def cut(space, start_ms, end_ms, name_chars):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    device = next(p for p in space.planes
                  if p.name.startswith("/device:TPU:"))
    ops = next(l for l in device.lines if l.name == "XLA Ops")
    origin = ops.timestamp_ns * 1000 + min(e.offset_ps for e in ops.events)
    lo, hi = origin + int(start_ms * 1e9), origin + int(end_ms * 1e9)
    out = xplane_pb2.XSpace()

    def copy(plane, keep_line, keep_event):
        new = out.planes.add(id=plane.id, name=plane.name)
        ids = {}
        for line in plane.lines:
            if not keep_line(line):
                continue
            base = line.timestamp_ns * 1000
            events = []
            for e in line.events:
                a, b = base + e.offset_ps, base + e.offset_ps + e.duration_ps
                name = plane.event_metadata[e.metadata_id].name
                if b <= lo or a >= hi or not keep_event(name):
                    continue
                a, b = max(a, lo), min(b, hi)
                key = ids.setdefault(name[:name_chars], len(ids) + 1)
                events.append((a - lo, b - a, key))
            if not events:
                continue
            nl = new.lines.add(id=line.id, name=line.name, timestamp_ns=0)
            for off, dur, key in events:
                nl.events.add(metadata_id=key, offset_ps=off,
                              duration_ps=dur)
        for name, key in ids.items():
            new.event_metadata[key].id = key
            new.event_metadata[key].name = name

    copy(device, lambda l: l.name in KEPT_LINES, lambda n: True)
    for plane in space.planes:
        if plane.name.startswith("/host:CPU"):
            copy(plane, lambda l: True,
                 lambda n: n.startswith(HOST_PREFIXES))
    return out


def main():
    from google.protobuf import text_format
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--start-ms", type=float, required=True)
    ap.add_argument("--end-ms", type=float, required=True)
    ap.add_argument("--name-chars", type=int, default=1500)
    ap.add_argument("--note", default="")
    args = ap.parse_args()
    space = xplane_pb2.XSpace()
    with open(args.xplane, "rb") as f:
        space.ParseFromString(f.read())
    text = text_format.MessageToString(
        cut(space, args.start_ms, args.end_ms, args.name_chars))
    with open(args.out, "w") as f:
        for line in args.note.splitlines():
            f.write(f"# {line}\n")
        f.write(text)
    print(f"{args.out}: {len(text)} bytes")


if __name__ == "__main__":
    main()
