"""How the recorded traces under tests/data were cut from chip runs.

    python3 chipbench/tests/cut_trace.py <in.xplane.pb> <out.xplane.pb> \\
        <start_ms> <length_ms>      # start as ProfileData's start_ns / 1e6

Keeps, of every ``/device:TPU:<n>`` plane, the ``XLA Ops`` and ``XLA Modules``
lines and, of the host, the harness's ``cb/`` annotations, all clipped to the
slice; drops every stat, and shortens each operation's HLO text to its name
and result type (all the reduction reads).  Times are untouched.  Needs the
xplane protobuf schema, which here comes with tensorflow; the benchmark
itself reads traces with jax.profiler.ProfileData only.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench.trace_reduce import label  # noqa: E402


def main(src, dst, start_ms, length_ms):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    lo = float(start_ms) * 1e9           # ps, on the scale ProfileData shows
    hi = lo + float(length_ms) * 1e9
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not (device or plane.name == "/host:CPU"):
            continue
        new = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            base = line.timestamp_ns * 1000
            kept = []
            for e in line.events:
                name = plane.event_metadata[e.metadata_id].name
                s, t = base + e.offset_ps, base + e.offset_ps + e.duration_ps
                if t <= lo or s >= hi or not (device or name.startswith("cb/")):
                    continue
                s, t = max(s, lo), min(t, hi)
                kept.append((e.metadata_id, name, s - base, t - s))
            if not kept:
                continue
            nl = new.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
            for mid, name, off, dur in kept:
                nl.events.add(metadata_id=mid, offset_ps=int(off),
                              duration_ps=int(dur))
                short = name
                if device:      # "%copy.3 = bf16[8,4] cut()": same label
                    op, _, result = label(name).partition(" ")
                    short = f"%{op} = {result} cut()"
                new.event_metadata[mid].id = mid
                new.event_metadata[mid].name = short
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {len(out.SerializeToString())} bytes")


if __name__ == "__main__":
    main(*sys.argv[1:5])
