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

The flight recorder (:mod:`.recorder`) answers "which host collective hangs";
this answers "who holds the chip back".  They share nothing but ``req``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Iterable, Optional

from ..utils.metrics import LatencyHistogram

__all__ = ["span", "phase_times", "reset_phases", "PREFIX"]

PREFIX = "td/"

_mu = threading.Lock()
_table: Dict[str, LatencyHistogram] = {}


def _hist(name: str) -> LatencyHistogram:
    h = _table.get(name)
    if h is None:
        with _mu:
            h = _table.setdefault(name, LatencyHistogram())
    return h


class span:
    """One host phase: a profiler annotation plus a sample in the phase
    table.  A context manager; an exception inside it still closes both."""

    __slots__ = ("name", "_ann", "_hist", "_t0")

    def __init__(self, name: str, **fields):
        import jax
        self.name = name
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name, **fields)

    def __enter__(self) -> "span":
        self._hist = _hist(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._hist.observe(dt)


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
    phase's count after a reset holds only spans that began after it."""
    with _mu:
        for n in (list(_table) if names is None else names):
            _table[n] = LatencyHistogram()
