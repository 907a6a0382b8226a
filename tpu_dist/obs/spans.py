"""Spans on the compiled path: where the host's time goes between the
device's operations (docs/observability.md, "Spans on the compiled path").

``with span("decode.dispatch", step=n):`` does two things, always — there is
no switch:

- it enters a ``jax.profiler.TraceAnnotation("td/decode.dispatch", step=n)``.
  With no profiler session active that is a flag test; with one
  (``tpu_dist.utils.trace``), the span lands in the profiler's own trace, on
  the clock of the device's operations, so an idle gap on the chip can be
  named by the host phase that was open across it.  The fields carry the
  identifiers that tie spans together: ``req`` on every span of one request
  (the same number the flight recorder's ``serve`` span carries), ``step`` on
  the spans of one decode iteration or training step, ``slot``, ``bucket``,
  ``active``.  Nesting gives the cause.
- it adds its duration (``time.perf_counter``) to a ``LatencyHistogram`` named
  after the span, in one process-wide table that :func:`phase_times` reads.

A phase says how long it took, never why.  A :class:`LoopClock`, owned by one
loop thread (the serving loop's: ``SlotEngine.stats()["loop"]``), closes the
books on every iteration of that thread over the same spans: seconds by
phase and under no span, the thread's CPU time, the other threads', the
garbage collector's pauses (``td/gc``, on the profiler's clock too) and the
time the thread stood off the CPU; the longest iterations are kept whole and
one past :data:`STALL_S` is logged.  No switch either.

The flight recorder (:mod:`.recorder`) answers "which host collective hangs";
this answers "who holds the chip back".  They share nothing but ``req``.
"""

from __future__ import annotations

import gc
import heapq
import threading
import time
from typing import Dict, Iterable, Optional, Sequence

from ..utils.metrics import LatencyHistogram

__all__ = ["span", "phase_times", "reset_phases", "PREFIX", "LoopClock",
           "STALL_S", "STALL_LOG_EVERY_S", "KEPT"]

PREFIX = "td/"

_mu = threading.Lock()
_table: Dict[str, LatencyHistogram] = {}


def _hist(name: str) -> LatencyHistogram:
    h = _table.get(name)
    if h is None:
        with _mu:
            h = _table.setdefault(name, LatencyHistogram())
    return h


class _Local(threading.local):
    clock = None    # the LoopClock this thread owns (a class default: a
    #                 thread that owns none reads it without an exception)
    joining = None  # the compile ledger's (.compiles) stages not yet
    #                 joined, of this thread
    # ``open``: the names of the spans open on this thread, outermost
    # first (what the ledger files a record under); made by the thread's
    # first span, so it has no class default


_local = _Local()


class span:
    """One host phase: a profiler annotation plus a sample in the phase
    table.  A context manager; an exception inside it still closes both.
    On a thread that owns a :class:`LoopClock` the span's seconds also go
    to the clock's open iteration; any other thread pays one thread-local
    lookup for that."""

    __slots__ = ("name", "_ann", "_hist", "_t0", "_clock", "_covered",
                 "_open")

    def __init__(self, name: str, **fields):
        import jax
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **fields)

    def __enter__(self) -> "span":
        self._hist = _hist(self.name)
        try:
            names = self._open = _local.open
        except AttributeError:
            names = self._open = _local.open = []
        clock = self._clock = _local.clock
        if clock is not None:
            if clock._thread != threading.get_ident():  # taken over since
                clock = self._clock = _local.clock = None
            else:
                self._covered = clock._covered
        self._ann.__enter__()
        names.append(self.name)     # open from here: ``__exit__`` will run
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._hist.observe(dt)
        self._open.pop()
        clock = self._clock
        if clock is not None:
            by_phase = clock._by_phase
            by_phase[self.name] = by_phase.get(self.name, 0.0) + dt
            # what its nested spans added since it opened is inside dt:
            # counted once
            clock._covered = self._covered + dt


def phase_times(names: Optional[Iterable[str]] = None) -> Dict[str, dict]:
    """``{name: LatencyHistogram.summary()}`` (seconds; ``mean * count`` is
    the exact sum) of every phase seen so far, or of ``names`` alone — a
    phase of ``names`` that never ran reads as the empty summary."""
    if names is None:
        with _mu:
            names = list(_table)
    return {n: _hist(n).summary() for n in names}


def reset_phases(names: Optional[Iterable[str]] = None) -> None:
    """Zero the named phases (all of them by default).  A span open across
    the reset adds its sample to the histogram that was replaced, so a
    phase's count after a reset holds only spans that began after it.
    :meth:`LoopClock.reset` keeps the same rule for a whole iteration."""
    with _mu:
        for n in (list(_table) if names is None else names):
            _table[n] = LatencyHistogram()


# -- the collector as a span ---------------------------------------------------

# Process-wide, written under the interpreter lock by whichever thread set a
# collection off (collections do not nest): seconds of the collections that
# have ended, and how many of each generation.
_gc_seconds = 0.0
_gc_counts = [0, 0, 0]
_gc_open = None
_gc_annotation = None       # jax.profiler.TraceAnnotation once watched


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open, _gc_seconds
    if phase == "start":
        ann = _gc_annotation(PREFIX + "gc", gen=info["generation"])
        ann.__enter__()
        _gc_open = (ann, time.perf_counter())
    elif _gc_open is not None:
        ann, t0 = _gc_open
        _gc_open = None
        dt = time.perf_counter() - t0
        ann.__exit__(None, None, None)
        _gc_seconds += dt
        _gc_counts[info["generation"]] += 1


def _watch_collector() -> None:
    """Put the interpreter's garbage collector on the profiler's clock as
    ``td/gc`` (``gen``) and into the process-wide totals a clock's tick
    reads.  Idempotent; called when a :class:`LoopClock` is made and never
    at import, so a process that serves nothing has no callback."""
    global _gc_annotation
    with _mu:
        if _on_gc not in gc.callbacks:
            import jax
            _gc_annotation = jax.profiler.TraceAnnotation
            gc.callbacks.append(_on_gc)


# -- a clock on one loop thread --------------------------------------------------

#: An iteration whose wall time less the loop's own sleep passes this many
#: seconds is logged in one line (``[tpu_dist] serve loop stalled ...``).
STALL_S = 0.5
#: ... at most once in this many seconds.
STALL_LOG_EVERY_S = 10.0
#: Iterations kept, the longest of each kind since the last reset.
KEPT = 8

try:
    import resource as _resource
    _RUSAGE_THREAD = _resource.RUSAGE_THREAD
except (ImportError, AttributeError):       # no per-thread usage here
    _resource = None


def _counts_switches() -> bool:
    """Whether this platform keeps a thread's context-switch counts at all:
    a thread that got as far as building an engine has been switched out
    somewhere (a sandboxed kernel that reports zeros for ever charges a
    tick one more system call for nothing)."""
    if _resource is None:
        return False
    ru = _resource.getrusage(_RUSAGE_THREAD)
    return ru.ru_nvcsw + ru.ru_nivcsw > 0


class _Books:
    """What a clock has closed since its last reset."""

    __slots__ = ("t0", "n", "wall", "covered", "wait", "cpu", "proc", "gc",
                 "gc_n", "compile", "compiles", "hist", "longest", "seq")

    def __init__(self, kinds: Sequence[str]):
        self.t0 = time.perf_counter()
        self.n = dict.fromkeys(kinds, 0)
        self.wall = self.covered = self.wait = self.cpu = 0.0
        self.proc = self.gc = self.compile = 0.0
        self.gc_n = [0, 0, 0]
        self.compiles = 0
        self.hist = {k: LatencyHistogram() for k in kinds}
        self.longest = {k: [] for k in kinds}   # heaps of (busy, seq, record)
        self.seq = 0


class LoopClock:
    """Closes the books on every iteration of ONE loop thread.

    The thread that calls :meth:`tick` at its iteration boundary owns the
    clock (a tick from another thread takes it over and drops the open
    iteration).  Between two ticks every :class:`span` that thread closes
    adds its seconds to the open iteration by name, and to ``covered`` at
    nesting depth 0 only; spans of other threads add nothing.  A tick reads
    the wall clock, this thread's and the process's CPU time, the
    collector's running total and (where the platform keeps the counts) the
    thread's ``getrusage``, and closes the iteration that just ended:

    - ``wall``; ``by_phase`` (seconds a span name); ``unnamed = wall -
      covered``: the thread's time that no span covers;
    - ``wait``: the spans named in ``waits`` (the waits the design intends:
      for the device, for work);
    - ``cpu`` (this thread's) and ``cpu_others`` (the process's less this
      thread's: what the other threads burnt meanwhile);
    - ``offcpu = max(0, wall - wait - cpu)``: the thread wanted to run and
      did not (the interpreter lock, the OS scheduler, a blocking send).  A
      lower bound: CPU spent inside the waits is not subtracted.  The sum
      ``offcpu_s`` is taken over the sums, not over the iterations;
    - ``gc``: seconds of the collections that ENDED in the iteration, on any
      thread (a collection holds the interpreter lock whoever set it off);
    - ``by_phase["compile"]``: seconds of the programs this thread traced,
      lowered and compiled or loaded in the iteration (the compile ledger,
      :mod:`.compiles`), with their names under ``compiled``.  Never added
      to ``covered``: the span they fell in already holds those seconds.

    Sums go to counters, ``wall`` less the ``sleep`` span to one
    ``LatencyHistogram`` a kind, and the :data:`KEPT` longest iterations of
    each kind (by that same length) are kept whole, with the phase that
    took most of each.  One past :data:`STALL_S` is logged in one line
    (``tpu_dist.utils.logging.log_event``), at most once every
    :data:`STALL_LOG_EVERY_S`.  There is no switch; a tick costs a few
    microseconds.  Tick with no span of the thread open.
    """

    __slots__ = ("name", "_kinds", "_waits", "_sleep", "_books", "_open",
                 "_by_phase", "_covered", "_thread", "_last", "_gc_n",
                 "_logged", "_switches", "_compiled", "_step")

    def __init__(self, name: str, kinds: Sequence[str],
                 waits: Sequence[str], sleep: str):
        _watch_collector()
        self.name = name
        self._kinds = tuple(kinds)
        self._waits = tuple(waits)
        self._sleep = sleep
        self._books = _Books(self._kinds)
        self._open = None           # the books the open iteration began under
        self._by_phase: Dict[str, float] = {}
        self._covered = 0.0
        # the compile ledger's records of the open iteration (seconds,
        # name, what the cache said) and the cell they read its step from
        self._compiled: list = []
        self._step: list = [None]
        self._thread = None
        self._switches = _counts_switches()
        # the last tick's readings: wall, this thread's CPU, the
        # process's, the collector's total, the thread's rusage
        self._last = (0.0, 0.0, 0.0, 0.0, None)
        self._gc_n = [0, 0, 0]
        self._logged = float("-inf")

    def reset(self) -> None:
        """Zero everything :meth:`stats` reports (any thread may call).  An
        iteration open across the reset is DROPPED, not split — as a span
        open across :func:`reset_phases` lands in the replaced histogram —
        so a reset at a window's first instant puts nothing from before it
        into ``wall_s``."""
        self._books = _Books(self._kinds)

    def tick(self, kind: str, step: int = 0) -> None:
        """The iteration boundary: close the iteration that just ended as
        one of ``kind`` (its ``step`` for the record) and open the next."""
        now = time.perf_counter()
        cpu = time.thread_time()
        proc = time.process_time()
        gc_s = _gc_seconds
        ru = (_resource.getrusage(_RUSAGE_THREAD) if self._switches
              else None)
        if _local.clock is not self or self._thread != threading.get_ident():
            self._own()
        books = self._books
        by_phase, covered = self._by_phase, self._covered
        self._by_phase, self._covered = {}, 0.0
        compiled = self._compiled
        if compiled:
            cell = self._step
            self._compiled, self._step = [], [None]
        if self._open is books:
            t0, cpu0, proc0, gc0, ru0 = self._last
            wall = now - t0
            cpu_d = cpu - cpu0
            sleep = by_phase.get(self._sleep, 0.0)
            wait = 0.0
            for name in self._waits:
                wait += by_phase.get(name, 0.0)
            proc_d = proc - proc0
            gc_d = gc_s - gc0
            books.wall += wall
            books.covered += covered
            books.wait += wait
            books.cpu += cpu_d
            books.proc += proc_d
            books.n[kind] += 1
            if gc_d:
                books.gc += gc_d
                counts, before = list(_gc_counts), self._gc_n
                self._gc_n = counts
                for g in range(3):
                    books.gc_n[g] += counts[g] - before[g]
            if compiled:
                books.compile += by_phase["compile"]
                books.compiles += len(compiled)
                cell[0] = int(step)     # the ledger's records read it
            busy = wall - sleep
            books.hist[kind].observe(busy)
            heap = books.longest[kind]
            if len(heap) < KEPT or busy > heap[0][0] or busy > STALL_S:
                books.seq += 1
                record = {
                    "step": int(step), "at": t0 - books.t0,
                    "wall": wall, "by_phase": by_phase,
                    "unnamed": wall - covered, "wait": wait, "cpu": cpu_d,
                    "cpu_others": max(0.0, proc_d - cpu_d),
                    "offcpu": max(0.0, wall - wait - cpu_d), "gc": gc_d}
                if ru is not None:
                    record.update(
                        switches=ru.ru_nivcsw - ru0.ru_nivcsw,
                        voluntary_switches=ru.ru_nvcsw - ru0.ru_nvcsw,
                        major_faults=ru.ru_majflt - ru0.ru_majflt)
                named = dict(by_phase, unnamed=record["unnamed"])
                named.pop(self._sleep, None)
                if compiled:
                    named.pop("compile")    # inside the span it fell in
                    record["compiled"] = [c[1] for c in compiled]
                record["phase"] = max(named, key=named.get)
                entry = (busy, books.seq, record)
                if len(heap) < KEPT:
                    heapq.heappush(heap, entry)
                elif busy > heap[0][0]:
                    heapq.heapreplace(heap, entry)
                if busy > STALL_S and now - self._logged >= STALL_LOG_EVERY_S:
                    self._logged = now
                    self._log_stall(busy, record, compiled)
        else:
            self._gc_n = list(_gc_counts)
        self._open = books
        self._last = (now, cpu, proc, gc_s, ru)

    def _own(self) -> None:
        """This thread takes the clock: the open iteration (another
        thread's, or none) is dropped."""
        _local.clock = self
        self._thread = threading.get_ident()
        self._open = None
        self._by_phase, self._covered = {}, 0.0
        self._compiled, self._step = [], [None]

    def _compiled_one(self, name: str, seconds: float, cache: str) -> list:
        """The compile ledger closed a record on this, the owning, thread:
        its ``seconds`` go to the open iteration under ``compile``.
        Returns the cell the iteration's step is written to when it is
        ticked (None until then, and for ever if it is dropped)."""
        by_phase = self._by_phase
        by_phase["compile"] = by_phase.get("compile", 0.0) + seconds
        self._compiled.append((seconds, name, cache))
        return self._step

    def _log_stall(self, busy: float, r: dict, compiled: list) -> None:
        from ..utils.logging import log_event
        why = ""
        if compiled:
            seconds, name, cache = max(compiled)
            why = (f", compiling {name} {seconds:.3g} s (cache {cache})"
                   + (f" and {len(compiled) - 1} more"
                      if len(compiled) > 1 else ""))
        log_event(
            f"{self.name} stalled {busy:.2f} s at step {r['step']} in "
            f"{r['phase']}: cpu {r['cpu']:.3g} s, off-cpu {r['offcpu']:.3g} "
            f"s, gc {r['gc']:.3g} s, others' cpu {r['cpu_others']:.3g} s"
            + (f", {r['switches']} involuntary switches"
               if "switches" in r else "") + why)

    def stats(self) -> dict:
        """Everything closed since :meth:`reset`, plain ints, floats and
        strings (it rides the wire ``stats`` frame): iterations a kind, the
        sums in seconds (``covered_s + unnamed_s == wall_s``), collections
        a generation, ``iteration`` (a ``LatencyHistogram.summary()`` a
        kind, of ``wall`` less the sleep) and ``longest`` (the kept records
        of each kind, longest first)."""
        b = self._books
        return {
            "iterations": dict(b.n),
            "wall_s": b.wall, "covered_s": b.covered,
            "unnamed_s": b.wall - b.covered, "wait_s": b.wait,
            # the two differences are clamped at the sums, not an iteration:
            # where the CPU clocks tick in steps of 10 ms (the chip's host)
            # an iteration's clamp would count every short one that read 0
            # as off the CPU
            "cpu_s": b.cpu, "cpu_others_s": max(0.0, b.proc - b.cpu),
            "offcpu_s": max(0.0, b.wall - b.wait - b.cpu), "gc_s": b.gc,
            "gc_collections": list(b.gc_n),
            "compile_s": b.compile, "compiles": b.compiles,
            "iteration": {k: h.summary() for k, h in b.hist.items()},
            "longest": {k: [dict(r, by_phase=dict(r["by_phase"]))
                            for _, _, r in sorted(list(heap), reverse=True,
                                                  key=lambda e: e[:2])]
                        for k, heap in b.longest.items()},
        }
