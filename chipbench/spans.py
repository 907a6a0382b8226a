"""The harness's own host spans, and the traced slice.

A span is written twice: into ``Spans.rows`` on chipbench.clock (what the
``program_span`` metrics read) and, as a ``jax.profiler.TraceAnnotation``
named ``cb/<name>``, into the profiler's trace, where it shares a clock with
the device's operations (what names an idle gap).  Spans come from the
benchmark's files only; the program is not edited.
"""

from __future__ import annotations

import contextlib
import functools

from .clock import now

PREFIX = "cb/"
WINDOW = "trace_window"


class Spans:
    def __init__(self):
        self.rows = []    # (name, start, end); list.append is atomic

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = now()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.rows.append((name, t0, now()))

    def wrap(self, name: str, fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def durations(self, name: str, t0: float, t1: float) -> list:
        """Seconds of every span of that name that began inside [t0, t1)."""
        return [e - s for n, s, e in self.rows if n == name and t0 <= s < t1]


@contextlib.contextmanager
def traced_slice(trace_dir: str):
    """Profile what runs inside: device operations and host TraceAnnotations,
    without the Python function tracer (it slows the host it measures).  The
    ``cb/trace_window`` annotation brackets the slice so the reduction can
    leave the profiler's own start and stop out."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(PREFIX + WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
