"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  Two steps, so the
arithmetic can be checked without a profile: ``load`` turns the file into
plain rows, ``reduce`` turns rows into metrics.

What is read:

- device planes ``/device:TPU:<n>``, their ``XLA Ops`` line: one event per
  HLO operation the core executed, start and duration on the device's clock.
  An event is named by the operation's whole HLO text, ``%fusion.5 =
  bf16[8,1024]{...} fusion(...)``; its label here is the instruction's own
  name and the type of its result, ``fusion.5 bf16[8,1024]``.  A Pallas
  kernel's instruction carries the name its ``pallas_call`` was given
  (``%jvp_flash_fwd_.3``), so kernels are found by that name.  (The ``Async
  XLA Ops`` line repeats asynchronous copies from start to done; the core's
  own wait shows as the ``-done`` operation on ``XLA Ops``.)
- host planes: the harness's ``cb/<name>`` TraceAnnotations (chipbench.spans),
  which the profiler records on the same time base.

What comes out, for the slice inside ``cb/trace_window``:

- busy seconds per device (the union of its operations' intervals), the
  window's length, and so the idle share;
- seconds and calls per kind of operation (the name without its instance
  number, with the result's type: the 24 per-layer copies of one fusion are
  one kind), and per kernel name;
- collective seconds (all-reduce, reduce-scatter, all-gather, all-to-all,
  collective-permute; an asynchronous ``-start``/``-done`` pair counts from
  the start's beginning to the done's end) and the part of them during which
  no other operation runs on that device: the exposed part;
- device 0's idle time, every instant of it named by the harness span that
  began last among those open just then on the host (``no span`` where none
  was), and summed by that name.

    python3 -m chipbench.trace_reduce <file.xplane.pb>    # describe a trace
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import sys

from .spans import PREFIX, WINDOW

OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
               "collective-permute")
_INSTANCE = re.compile(r"(\.\d+)?(\.remat\d*)?$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT = re.compile(r"^((?:\([^)]*\))|\S+)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """{"devices": {ordinal: [(label, start_ns, end_ns)]},
        "spans": [(name, start_ns, end_ns)]}"""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                rows = devices.setdefault(int(m.group(1)), [])
                rows.extend((label(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events)
            elif not m:
                spans.extend((e.name[len(PREFIX):], e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(PREFIX))
    return {"devices": devices, "spans": spans}


# -- interval arithmetic (lists of (start, end), in any unit) ----------------

def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a_merged, b_merged) -> list:
    """The parts of ``a`` that no interval of ``b`` covers."""
    out, j = [], 0
    for s, e in a_merged:
        while j < len(b_merged) and b_merged[j][1] <= s:
            j += 1
        k = j
        while k < len(b_merged) and b_merged[k][0] < e:
            if b_merged[k][0] > s:
                out.append([s, b_merged[k][0]])
            s = max(s, b_merged[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def innermost(spans, lo, hi) -> list:
    """[lo, hi] cut at every span boundary into (start, end, name) pieces,
    each named by the span that began last among those open in it."""
    points = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e)
                               if lo < t < hi)})
    pieces = []
    for a, b in zip(points, points[1:]):
        open_ = [(s, n) for n, s, e in spans if s <= a and b <= e]
        pieces.append((a, b, max(open_)[1] if open_ else "no span"))
    return pieces


def clip(rows, lo, hi) -> list:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in rows
            if e > lo and s < hi]


# -- reduction ----------------------------------------------------------------

def label(hlo: str) -> str:
    """``%copy.3 = bf16[32,1024]{1,0:T(8,128)} copy(...)`` ->
    ``copy.3 bf16[32,1024]``: the instruction's name and its result's type,
    without layouts."""
    name, _, rest = hlo.partition(" = ")
    result = _RESULT.match(_LAYOUT.sub("", rest))
    return (name.lstrip("%") + " " + (result.group(1) if result else "")).strip()


def op_kind(label: str) -> str:
    """``fusion.123 bf16[8,1024]`` -> ``fusion bf16[8,1024]``."""
    name, _, result = label.partition(" ")
    return (_INSTANCE.sub("", name) + " " + result[:80]).strip()


def is_collective(label: str) -> bool:
    return label.startswith(COLLECTIVES)


def _collective_intervals(rows) -> list:
    """Synchronous collectives as they are; ``X-start`` paired with the next
    ``X-done`` of the same kind into one interval."""
    out, open_starts = [], collections.defaultdict(list)
    for name, s, e in sorted(rows, key=lambda r: r[1]):
        head = name.split(" ", 1)[0]
        kind = next(c for c in COLLECTIVES if head.startswith(c))
        rest = head[len(kind):]
        if rest.startswith("-start"):
            open_starts[kind].append(s)
        elif rest.startswith("-done") and open_starts[kind]:
            out.append((open_starts[kind].pop(0), e))
        else:
            out.append((s, e))
    return out


def reduce(table: dict) -> dict:
    """Rows -> metrics; seconds throughout.  Empty when no device ran."""
    ns = 1e-9
    window = [(s, e) for n, s, e in table["spans"] if n == WINDOW]
    every = [r for rows in table["devices"].values() for r in rows]
    if not every:
        return {}
    lo, hi = window[0] if window else (min(r[1] for r in every),
                                       max(r[2] for r in every))
    per_device, ops = {}, collections.Counter()
    for ordinal, rows in sorted(table["devices"].items()):
        rows = clip(rows, lo, hi)
        busy = merge((s, e) for _, s, e in rows)
        coll = [r for r in rows if is_collective(r[0])]
        coll_iv = merge(_collective_intervals(coll))
        other = merge((s, e) for n, s, e in rows if not is_collective(n))
        per_device[ordinal] = {
            "busy_s": total(busy) * ns,
            "collective_s": total(coll_iv) * ns,
            "collective_exposed_s": total(subtract(coll_iv, other)) * ns,
            "busy": busy, "rows": rows}
    first = per_device[min(per_device)]
    for name, s, e in first["rows"]:
        ops[op_kind(name)] += (e - s) * ns
    pieces = innermost([r for r in clip(table["spans"], lo, hi)
                        if r[0] != WINDOW], lo, hi)
    starts = [a for a, _, _ in pieces]
    idle_by_span = collections.Counter()
    gaps = subtract([[lo, hi]], first["busy"])
    for s, e in gaps:
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(pieces) and pieces[i][0] < e:
            a, b, name = pieces[i]
            idle_by_span[name] += (min(b, e) - max(a, s)) * ns
            i += 1
    n_dev = len(per_device)
    mean = lambda key: sum(d[key] for d in per_device.values()) / n_dev
    return {"window_s": (hi - lo) * ns, "busy_s": mean("busy_s"),
            "busy0_s": first["busy_s"], "devices": n_dev,
            "collective_s": mean("collective_s"),
            "collective_exposed_s": mean("collective_exposed_s"),
            "op_seconds": dict(ops), "rows0": first["rows"],
            "idle_by_span": dict(idle_by_span)}


def kernel(reduced: dict, names) -> tuple:
    """(seconds, calls) on device 0 of the operations whose instruction name
    holds one of ``names`` (the names ops/*.py give their pallas_calls)."""
    hits = [(e - s) for name, s, e in reduced.get("rows0", ())
            if any(n in name.split(" ", 1)[0] for n in names)]
    return sum(hits) * 1e-9, len(hits)


def breakdown(reduced: dict, top: int = 10) -> dict:
    rank = lambda d: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(reduced["op_seconds"]),
            "idle_gaps": rank(reduced["idle_by_span"])}


def describe(path: str) -> None:
    """Print what a trace holds: look at one by hand before trusting code."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:3]:
                print(f"    {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {dict(e.stats)}")
    table = load(path)
    for ordinal, rows in sorted(table["devices"].items())[:1]:
        by_kind = collections.Counter()
        for name, s, e in rows:
            by_kind[op_kind(name)] += e - s
        print(f"device {ordinal}: the 40 kinds of operation with most time (ns)")
        for kind, t in by_kind.most_common(40):
            print(f"  {t:12.0f}  {kind}")
    print("spans:", collections.Counter(n for n, _, _ in table["spans"]))
    r = reduce(table)
    print({k: v for k, v in r.items() if k != "rows0"})


if __name__ == "__main__":
    describe(sys.argv[1])
