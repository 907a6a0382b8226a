"""From a KEPT profiler trace to what the program's own scopes and spans say:
seconds on device 0 per scope path, and device 0's idle time named by the
program's phase.

    python3 -m chipbench.run --workload <cell> ... --trace 1 --keep-trace DIR
    python3 -m chipbench.scope_reduce DIR          # or the .xplane.pb itself

Beside ``trace_reduce`` and not inside it, because run.py deletes the trace
before the per-layer readers run and hands them ``trace_reduce``'s rows
alone; until run.py calls this too (PERF.md, section 7), these numbers are a
builder's, from a run with ``--keep-trace``.

What is read, and from where:

- the scope path of a device operation is the ``tf_op`` stat of its *event
  metadata* (``jit(local_step)/transpose(jvp(block3))/attn/dot_general:``:
  the HLO ``op_name``, which holds every ``jax.named_scope`` open at trace
  time, wrapped by ``jvp(..)`` going forward and ``transpose(jvp(..))`` going
  back).  libtpu 0.0.34 writes it there and nowhere else: the event's name is
  the HLO text without its metadata, and ``jax.profiler.ProfileData`` shows
  only an event's own stats.  So the file is read as protobuf wire format
  (xplane.proto: five messages, read below; nothing to install).
- a fusion carries ONE op_name, that of the operation XLA fused around (a
  matmul draws its elementwise neighbours in): a weight-gradient matmul with
  the optimizer's update fused into its output reads as backward, not as
  optimizer.  Shares by scope are shares by fusion root.
- an operation the compiler put in itself (a relayout ``copy``, the
  ``-start``/``-done`` halves of an asynchronous copy) has no op_name, or
  that of the argument it copies (``cache['block7.attn']['k']``): no scope
  of the program either way.  ``scope_seconds`` leaves it under what it has;
  ``filled_seconds`` counts it with the next operation of the same program
  that has a program scope, its likeliest consumer.  Both are printed; where
  they differ much, say which one a number is.
- host annotations: the harness's ``cb/<name>`` and the program's
  ``td/<name>`` (tpu_dist.obs.spans), with the host thread each ran on.  Idle
  time is named from the spans of the threads that dispatch (those holding a
  ``*.dispatch`` or ``cb/train_step`` span): ``td/stage.put`` runs on the
  staging thread and must not claim a gap it did not cause.  Program phases
  keep their ``td/`` here; they begin later than the harness span around
  them, so they win.
"""

from __future__ import annotations

import collections
import json
import os
import re
import struct
import sys

from . import trace_reduce as tr
from .spans import PREFIX, WINDOW

PROGRAM = "td/"      # tpu_dist.obs.spans.PREFIX; read, never imported
_OUTER = re.compile(r"^(jit\([^)]*\)/|shard_map/|pjit/)+")
_WRAP = re.compile(r"(transpose|jvp|vmap|checkpoint|remat|custom_vjp|"
                   r"custom_jvp)\(")
NORMS = ("ln1", "ln2", "ln_f")


# -- protobuf wire format (xplane.proto) --------------------------------------

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, the raw
    bytes for fixed-width ones, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v = buf[i:i + size]
            i += size
        elif kind in (1, 5):
            width = 8 if kind == 1 else 4
            v = bytes(buf[i:i + width])
            i += width
        else:
            raise ValueError(f"wire type {kind} is not in xplane.proto")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """XStat -> (name, value); a ``ref_value`` is the name it refers to."""
    name = value = None
    for num, v in _fields(buf):
        if num == 1:
            name = stat_names.get(v)
        elif num == 2:
            value = struct.unpack("<d", v)[0]
        elif num in (3, 4):
            value = v
        elif num == 5:
            value = _text(v)
        elif num == 7:
            value = stat_names.get(v, v)
    return name, value


def _plane(buf) -> dict:
    """XPlane -> {"name", "lines": [(line name, [(metadata id, start_ps,
    end_ps)])], "events": {metadata id: (name, {stat: value})}}."""
    name, raw_lines, raw_events, stat_names = "", [], [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            raw_events.append(v)
        elif num == 5:                       # map<int64, XStatMetadata>
            entry = dict(_fields(v))[2]
            meta = dict(_fields(entry))
            stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
    events = {}
    for v in raw_events:                     # map<int64, XEventMetadata>
        mid, ename, stats = 0, "", []
        for num, x in _fields(dict(_fields(v))[2]):
            if num == 1:
                mid = x
            elif num == 2:
                ename = _text(x)
            elif num == 5:
                stats.append(x)
        events[mid] = (ename, dict(_stat(s, stat_names) for s in stats))
    lines = []
    for v in raw_lines:
        lname, t0_ns, rows = "", 0, []
        for num, x in _fields(v):
            if num == 2:
                lname = _text(x)
            elif num == 3:
                t0_ns = x
            elif num == 4:                   # XEvent; fields 1-3 are varints
                ev = dict(_fields(x))
                start = t0_ns * 1000 + ev.get(2, 0)
                rows.append((ev.get(1, 0), start, start + ev.get(3, 0)))
        lines.append((lname, rows))
    return {"name": name, "lines": lines, "events": events}


def find(path: str) -> str:
    return path if os.path.isfile(path) else tr.find_xplane(path)


def load(path: str) -> dict:
    """{"devices": {ordinal: [(label, start_ns, end_ns, scope, program)]},
        "spans": [(name, start_ns, end_ns, thread)]}

    ``label`` as trace_reduce's; ``scope`` is the operation's op_name without
    the outermost ``jit(..)/`` and the trailing colon, "" where the trace
    gives none; ``program`` is its executable's id.  A span's name is ``engine.step`` for the harness's and
    ``td/decode.emit`` for the program's; its thread numbers the host line."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    devices, spans, thread = {}, [], 0
    for num, buf in _fields(space):
        if num != 1:
            continue
        plane = _plane(buf)
        m = re.fullmatch(r"/device:TPU:(\d+)", plane["name"])
        for lname, events in plane["lines"]:
            if m and lname == tr.OPS_LINE:
                rows = devices.setdefault(int(m.group(1)), [])
                for mid, s, e in events:
                    name, stats = plane["events"][mid]
                    scope = _OUTER.sub("", str(stats.get("tf_op") or "")
                                       ).rstrip(":")
                    rows.append((tr.label(name), s / 1e3, e / 1e3, scope,
                                 stats.get("program_id")))
            elif plane["name"].startswith("/host:"):
                thread += 1
                for mid, s, e in events:
                    name = plane["events"][mid][0]
                    if name.startswith(PREFIX):
                        name = name[len(PREFIX):]
                    elif not name.startswith(PROGRAM):
                        continue
                    spans.append((name, s / 1e3, e / 1e3, thread))
    return {"devices": devices, "spans": spans}


# -- scope paths ---------------------------------------------------------------

def module_path(scope: str) -> list:
    """``transpose(jvp(block3))/attn/dot_general`` -> ``["block3", "attn",
    "dot_general"]``: the path without the wrappers autodiff puts around its
    outermost scope.  An argument's name (``cache['block3.attn']['k']``) is
    one component."""
    if "[" in scope:
        return [scope]
    return [p for p in _WRAP.sub("", scope).replace(")", "").split("/") if p]


def direction(scope: str) -> str:
    if "transpose(" in scope:
        return "bwd"
    return "fwd" if "jvp(" in scope else ""


def has_program_scope(scope: str) -> bool:
    """More than the primitive's own name: some ``jax.named_scope`` of the
    program (a module, ``optimizer``, ``decode``, ...) was open."""
    return len(module_path(scope)) > 1


# -- reduction -----------------------------------------------------------------

def reduce(table: dict) -> dict:
    """Seconds throughout, device 0, inside ``cb/trace_window``:
    ``scope_seconds`` per scope path ("" for operations with none) and
    ``filled_seconds`` (those counted with the next scoped operation of their
    program), ``busy0_s``, ``window_s``, ``idle_by_span`` from the
    dispatching threads' spans, and ``dispatch_threads``."""
    if not table["devices"]:
        return {}
    rows = table["devices"][min(table["devices"])]
    dispatching = {t for n, _, _, t in table["spans"]
                   if n.endswith(".dispatch") or n == "train_step"}
    # window, busy seconds and the naming of idle time are trace_reduce's,
    # given device 0 and the dispatching threads' spans alone
    base = tr.reduce({"devices": {0: [r[:3] for r in rows]},
                      "spans": [(n, s, e) for n, s, e, t in table["spans"]
                                if t in dispatching or n == WINDOW]})
    window = [(s, e) for n, s, e, _ in table["spans"] if n == WINDOW]
    lo, hi = window[0] if window else (min(r[1] for r in rows),
                                       max(r[2] for r in rows))
    scope_seconds, filled = collections.Counter(), collections.Counter()
    following = {}                  # program -> scope of its next scoped row
    for _, s, e, scope, program in sorted(rows, key=lambda r: -r[1]):
        if has_program_scope(scope):
            following[program] = scope
        if e > lo and s < hi:
            t = (min(e, hi) - max(s, lo)) * 1e-9
            scope_seconds[scope] += t
            filled[following.get(program, scope)] += t
    return {"window_s": base["window_s"], "busy0_s": base["busy0_s"],
            "scope_seconds": dict(scope_seconds),
            "filled_seconds": dict(filled),
            "idle_by_span": base["idle_by_span"],
            "dispatch_threads": len(dispatching)}


def shares(reduced: dict, key: str = "scope_seconds") -> dict:
    """Percent of device 0's busy seconds, by fusion root: the splits ISSUE
    23 names, over ``scope_seconds`` or ``filled_seconds``.  A split that
    finds nothing is left out."""
    under = lambda *head: lambda s: module_path(s)[:len(head)] == list(head)
    inside = lambda *names: lambda s: any(
        p in names for p in module_path(s)[:-1])
    both = lambda f, g: lambda s: f(s) and g(s)
    splits = {
        "train.fwd_share": lambda s: direction(s) == "fwd",
        "train.bwd_share": lambda s: direction(s) == "bwd",
        "train.optimizer_share": under("optimizer"),
        "train.grad_reduce_share": under("grad_reduce"),
        "model.attention_share": both(inside("attn"), direction),
        "model.mlp_share": both(inside("mlp"), direction),
        "model.norm_share": both(inside(*NORMS), direction),
        "serve.decode_share_of_device": under("decode"),
        "serve.decode_attn_share": both(under("decode"), inside("attn")),
        "serve.decode_attend_share": both(under("decode"),
                                          inside("attend")),
        "serve.decode_cache_update_share": both(under("decode"),
                                                inside("cache_update")),
        "serve.prefill_device_share": under("prefill"),
        "no_program_scope_share": lambda s: not has_program_scope(s),
    }
    busy = reduced["busy0_s"]
    out = {name: 100.0 * sum(t for scope, t in reduced[key].items()
                             if keep(scope)) / busy
           for name, keep in splits.items()}
    return {k: v for k, v in out.items() if v > 0}


def by_module(reduced: dict, key: str = "scope_seconds", depth: int = 2,
              top: int = 12) -> list:
    """[[direction + module path cut to ``depth`` with block numbers
    folded, seconds]], largest first."""
    c = collections.Counter()
    for scope, t in reduced[key].items():
        path = module_path(scope)
        parts = [re.sub(r"block\d+", "blockN", p)
                 for p in (path[:-1][:depth] if len(path) > 1 else path)]
        c[" ".join(filter(None, [direction(scope), "/".join(parts)]))
          or "(no op_name)"] += t
    return [[k, v] for k, v in c.most_common(top)]


def describe(path: str) -> dict:
    r = reduce(load(find(path)))
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])]
    return {"window_s": r["window_s"], "busy0_s": r["busy0_s"],
            "dispatch_threads": r["dispatch_threads"],
            "shares": shares(r), "by_module": by_module(r),
            "shares_filled": shares(r, "filled_seconds"),
            "by_module_filled": by_module(r, "filled_seconds", depth=3),
            "idle_by_span": rank(r["idle_by_span"])}


if __name__ == "__main__":
    print(json.dumps(describe(sys.argv[1]), indent=1))
