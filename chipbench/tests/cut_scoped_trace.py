"""How tests/data/chat_scoped_slice.xplane.pb was cut from a chip run.

    python3 chipbench/tests/cut_scoped_trace.py <in.xplane.pb> <out.xplane.pb> \
        <start_ms> <length_ms>      # start as ProfileData's start_ns / 1e6

As cut_trace.py (whose two slices are not recut), but it keeps what
chipbench.scope_reduce reads as well: of the host, the program's ``td/``
annotations beside the harness's ``cb/``, each on its own thread's line; of
every device operation's event metadata, the ``tf_op`` and ``program_id``
stats.  Every other stat goes, and each operation's HLO text is shortened to
its name and result type.  Times are untouched.  Needs the xplane protobuf
schema, which here comes with tensorflow; the benchmark itself reads traces
without it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from chipbench.trace_reduce import label  # noqa: E402

KEPT_STATS = ("tf_op", "program_id")
KEPT_SPANS = ("cb/", "td/")


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
        stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            base = line.timestamp_ns * 1000
            kept = []
            for e in line.events:
                name = plane.event_metadata[e.metadata_id].name
                s, t = base + e.offset_ps, base + e.offset_ps + e.duration_ps
                if t <= lo or s >= hi or not (
                        device or name.startswith(KEPT_SPANS)):
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
                if mid in new.event_metadata:
                    continue
                md = new.event_metadata[mid]
                md.id, md.name = mid, name
                if not device:
                    continue
                op, _, result = label(name).partition(" ")
                md.name = f"%{op} = {result} cut()"   # the same label
                for st in plane.event_metadata[mid].stats:
                    if stat_name.get(st.metadata_id) in KEPT_STATS:
                        md.stats.add().CopyFrom(st)
                        sm = new.stat_metadata[st.metadata_id]
                        sm.id = st.metadata_id
                        sm.name = stat_name[st.metadata_id]
                        if st.WhichOneof("value") == "ref_value":
                            ref = new.stat_metadata[st.ref_value]
                            ref.id = st.ref_value
                            ref.name = stat_name[st.ref_value]
    with open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{dst}: {len(out.SerializeToString())} bytes")


if __name__ == "__main__":
    main(*sys.argv[1:5])
