"""The compile ledger: which seconds of a start went into tracing, lowering
and compiling (or loading from the persistent cache), by program, and which
loop iteration a recompile fell into (docs/observability.md, "The compile
ledger").

JAX publishes, through ``jax.monitoring``, one duration event a stage of
every program it builds — ``jaxpr_trace_duration`` (``fun_name="step"``),
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``
(``fun_name="jit(step)"``) — and, on the same thread just before the last,
whether the persistent cache was asked and whether it held the program.  A
second call of a compiled function fires nothing, so a warmed loop pays
nothing.  Each stage's START is published too, as a scalar.
:func:`install` (called by
``tpu_dist.utils.compile_cache.ensure_compile_cache``, never at import)
registers one listener for each of the three — durations, events, scalars —
and they join what they hear, per thread and as a stack of the stages open
on it, into ONE record a program:

``name``
    the program's own name, the ``jit(...)`` wrapper stripped so the three
    stages meet;
``at``
    the instant the backend stage ended, on ``time.monotonic()``;
``trace_s``, ``lower_s``, ``backend_s``
    seconds of each stage; ``backend_s`` is the compilation or the load from
    the persistent cache.  A program lowered from a jaxpr that was never
    traced through ``jax.jit`` (a backward pass) has ``trace_s`` 0;
``cache``
    ``"hit"``, ``"miss"`` (asked, and not held), or ``"off"`` (the
    persistent cache was not asked), with ``retrieval_s`` and ``saved_s``
    (what JAX says the hit saved) on a hit and ``kept`` 1 on a miss the
    cache then stored — it stores what took
    ``jax_persistent_cache_min_compile_time_secs`` (1 s by default) to
    compile, so a one-operation program misses at every start;
``inner_trace_s``
    seconds of the traces that began and ended INSIDE this program's trace
    on the same thread (inner ``jax.jit`` functions: ``matmul`` inside
    ``step``).  They are part of ``trace_s`` and of no total: totals count
    wall seconds once.  A trace inside a LOWERING (a lowering rule that
    traces a Python function) is part of ``lower_s`` alone.  A whole program
    built inside another's stage (an eager operation under
    ``jax.ensure_compile_time_eval``) is a record of its own, and its
    seconds are taken OUT of the stage around it;
``span``
    the innermost ``td/`` span open on that thread (``prefill.dispatch``,
    ``setup.place_params``; empty where none);
``step``
    on a thread that owns a :class:`~tpu_dist.obs.spans.LoopClock`, the step
    of the iteration the record closed in, once that iteration is ticked.

Totals are exact for the life of the process.  Records are bounded:
the newest :data:`KEPT_RECORDS` (4096; a set-up holds some hundreds) are
kept, the oldest dropped first, and a reading over an interval that held
dropped records says ``truncated``.  There is no switch.
"""

from __future__ import annotations

import collections
import heapq
import os
import threading
import time
from typing import Optional

from .spans import _local, _mu

__all__ = ["install", "compiles", "totals", "totals_since", "longest",
           "KEPT_RECORDS", "FIELDS", "TOTALS"]

#: Records kept, the newest; the oldest are dropped first.
KEPT_RECORDS = 4096
# stages a thread has seen and no backend stage has claimed (a trace under
# ``jax.eval_shape``, a lowering never compiled): the oldest are forgotten
_PENDING = 64

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_STAGES = (_TRACE, _LOWER, _BACKEND)
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_KEPT = "/jax/compilation_cache/cache_misses"   # fired when a miss is stored
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

#: A record's fields, in the order it is kept.
FIELDS = ("name", "at", "trace_s", "lower_s", "backend_s", "cache",
          "retrieval_s", "saved_s", "inner_trace_s", "span", "step", "kept")
#: The totals' keys: programs by what the persistent cache said, then
#: seconds by stage.
TOTALS = ("programs", "hits", "misses", "kept", "off", "trace_s", "lower_s",
          "backend_s", "retrieval_s", "saved_s")



def _zeros() -> dict:
    return dict.fromkeys(TOTALS[:5], 0) | dict.fromkeys(TOTALS[5:], 0.0)


_totals = _zeros()
_records: collections.deque = collections.deque()   # in the order of ``at``
_dropped = [0, 0.0, 0.0]    # how many, the first one's ``at``, the last's
_installed = False


class _Joining:
    """One thread's stages not yet joined into a record."""

    __slots__ = ("stack", "traces", "lowered", "asked", "hit", "kept",
                 "retrieval", "saved")

    def __init__(self):
        # the stages open on this thread, outermost first: [event, fun_name,
        # seconds of the traces directly inside, seconds of the whole
        # programs built inside]
        self.stack: list = []
        # ended, and not yet claimed by a backend stage (a trace under
        # ``jax.eval_shape``, a lowering never compiled, stay so)
        self.traces: dict = {}      # name -> (seconds, inner seconds)
        self.lowered: dict = {}     # name -> seconds
        self.asked = self.hit = self.kept = False
        self.retrieval = self.saved = 0.0


def _joining() -> _Joining:
    j = _local.joining
    if j is None:
        j = _local.joining = _Joining()
    return j


def _bare(fun_name: str) -> str:
    """``jit(step)`` -> ``step``."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _keep(pending: dict, name: str, value) -> None:
    pending.pop(name, None)         # the newest of a name, and last in line
    pending[name] = value
    if len(pending) > _PENDING:
        del pending[next(iter(pending))]


def _on_event(event: str, **_) -> None:
    if event == _ASKED:
        _joining().asked = True
    elif event == _HIT:
        _joining().hit = True
    elif event == _KEPT:
        _joining().kept = True


def _on_scalar(event: str, value, fun_name: str = "", **_) -> None:
    """A stage begins (JAX publishes its start time as a scalar)."""
    if event in _STAGES:
        _joining().stack.append([event, fun_name, 0.0, 0.0])


def _on_duration(event: str, duration: float, fun_name: str = "",
                 **_) -> None:
    if event in _STAGES:
        j = _joining()
        stack = j.stack
        inner = built = 0.0
        # the frame this stage opened (with whatever a stage that never
        # ended left above it)
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] == event and stack[i][1] == fun_name:
                inner, built = stack[i][2:]
                del stack[i:]
                break
        around = stack[-1] if stack else None
        # whole programs built inside are records of their own
        seconds = max(0.0, duration - built)
        if event == _TRACE:
            # inside a lowering it is part of that lowering, and of nothing
            if around is None or around[0] == _TRACE:
                if around is not None:
                    around[2] += seconds
                _keep(j.traces, fun_name, (seconds, inner))
        elif event == _LOWER:
            _keep(j.lowered, _bare(fun_name), seconds)
        else:
            built += _close(j, _bare(fun_name), seconds, around)
        if around is not None:
            around[3] += built
    elif event == _RETRIEVAL:
        _joining().retrieval = duration
    elif event == _SAVED:
        _joining().saved = duration


def _close(j: _Joining, name: str, backend_s: float, around) -> float:
    """The backend stage ended: one record, from what this thread saw.
    Returns its seconds."""
    now = time.monotonic()
    trace_s, inner = j.traces.pop(name, (0.0, 0.0))
    lower_s = j.lowered.pop(name, 0.0)
    if around is not None and around[0] == _TRACE:
        around[2] -= trace_s        # a program of its own, not an inner trace
    cache = "hit" if j.hit else "miss" if j.asked else "off"
    seconds = trace_s + lower_s + backend_s
    clock = _local.clock
    # on a loop thread: a cell its clock writes the iteration's step to
    step = (clock._compiled_one(name, seconds, cache)
            if clock is not None and clock._thread == threading.get_ident()
            else None)
    names = getattr(_local, "open", None)       # the spans open on the thread
    record = (name, now, trace_s, lower_s, backend_s, cache, j.retrieval,
              j.saved, inner, names[-1] if names else "", step, int(j.kept))
    j.asked = j.hit = j.kept = False
    j.retrieval = j.saved = 0.0
    with _mu:
        _add(_totals, record)
        _records.append(record)
        if len(_records) > KEPT_RECORDS:
            at = _records.popleft()[1]
            if not _dropped[0]:
                _dropped[1] = at
            _dropped[0] += 1
            _dropped[2] = at
    return seconds


def _add(t: dict, r: tuple) -> None:
    (_, _, trace_s, lower_s, backend_s, cache, retrieval_s, saved_s, _, _, _,
     kept) = r
    t["programs"] += 1
    t["hits" if cache == "hit" else "misses" if cache == "miss"
      else "off"] += 1
    t["kept"] += kept
    t["trace_s"] += trace_s
    t["lower_s"] += lower_s
    t["backend_s"] += backend_s
    t["retrieval_s"] += retrieval_s
    t["saved_s"] += saved_s


def _seconds(r: tuple) -> float:
    return r[2] + r[3] + r[4]       # trace, lower, backend


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once a process
    (idempotent; ``ensure_compile_cache()`` calls it before anything
    compiles): one for the stages' durations, one for the cache's events,
    one for the scalars in which JAX publishes a stage's START.  Nothing
    takes them out again."""
    global _installed
    if _installed:
        return
    with _mu:
        if _installed:
            return
        _installed = True
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_scalar)


def _as_dict(r: tuple) -> dict:
    d = dict(zip(FIELDS, r))
    step = d.pop("step")
    if step is not None and step[0] is not None:
        d["step"] = step[0]
    return d


def totals() -> dict:
    """The process's totals (:data:`TOTALS`), exact whatever was dropped."""
    with _mu:
        return dict(_totals)


def totals_since(base: dict) -> dict:
    """The totals since an earlier reading ``base`` of :func:`totals`."""
    now = totals()
    return {k: now[k] - base[k] for k in TOTALS}


def _within(lo: Optional[float], hi: Optional[float]) -> tuple:
    """The kept records whose backend stage ended in ``[lo, hi)``, and
    whether a dropped one did."""
    with _mu:
        kept = [r for r in _records
                if (lo is None or r[1] >= lo) and (hi is None or r[1] < hi)]
        truncated = bool(_dropped[0]
                         and (lo is None or lo <= _dropped[2])
                         and (hi is None or hi > _dropped[1]))
    return kept, truncated


def longest(top: int, since: Optional[float] = None,
            until: Optional[float] = None) -> list:
    """The ``top`` longest kept records of ``[since, until)`` as dicts, by
    the sum of their three stages, longest first."""
    kept, _ = _within(since, until)
    return [_as_dict(r) for r in heapq.nlargest(top, kept, key=_seconds)]


def compiles(since: Optional[float] = None,
             until: Optional[float] = None) -> dict:
    """Totals (:data:`TOTALS`) and ``records`` (longest first) of the
    programs whose backend stage ended in ``[since, until)`` on
    ``time.monotonic()``, the whole life of the process by default; plain
    ints, floats and strings.  The totals are sums over the kept records
    and ``truncated`` says where a record of the interval was dropped
    (over the whole life they are the exact ones regardless).
    ``cache_dir`` is where the persistent cache lives (empty where it is
    off), ``cache_bytes`` and ``cache_entries`` what the directory holds,
    read now, and ``cache_max_bytes`` JAX's cap on it (-1: none)."""
    import jax
    kept, truncated = _within(since, until)
    if since is None and until is None:
        out = totals()
    else:
        out = _zeros()
        for r in kept:
            _add(out, r)
    kept.sort(key=_seconds, reverse=True)
    out["truncated"] = truncated
    out["records"] = [_as_dict(r) for r in kept]
    path = jax.config.jax_compilation_cache_dir or ""
    size = entries = 0
    if path and os.path.isdir(path):
        with os.scandir(path) as it:
            for e in it:
                if e.is_file():
                    size += e.stat().st_size
                    entries += e.name.endswith("-cache")
    out.update(cache_dir=path, cache_bytes=size, cache_entries=entries,
               cache_max_bytes=int(jax.config.jax_compilation_cache_max_size))
    return out
