"""What the loop clock costs (ISSUE 34): a ``span`` on a thread with no
clock, a ``span`` on the thread that owns one, and a ``tick``, each in a loop
of 10^5 on the host's CPU (best of 15; the empty loop subtracted); then the
system calls a tick makes, each alone, and the step of the thread's CPU clock;
and (ISSUE 49) what the compile ledger's listeners do for one program and
what ``ensure_compile_cache()`` costs when called again.

    JAX_PLATFORMS=cpu python3 benchmarks/bench_loop_clock.py

Host numbers only: nothing here touches a device.
"""

from __future__ import annotations

import importlib
import json
import os
import resource
import sys
import threading
import time
import timeit

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N = 100_000


def _best(fn, repeat: int = 15) -> float:
    return min(timeit.repeat(fn, number=1, repeat=repeat))


def measure(n: int = N) -> dict:
    """Nanoseconds each, ``tick_ns`` None on a tree from before the clock."""
    import jax  # noqa: F401  (span imports it on first use)
    from tpu_dist.obs import spans

    def empty():
        for i in range(n):
            pass

    def spanned():
        for i in range(n):
            with spans.span("bench.cost", step=i):
                pass

    base = _best(empty)
    out = {"n": n, "span_ns": (_best(spanned) - base) / n * 1e9,
           "span_on_clock_ns": None, "tick_ns": None}
    # what a tick is made of on this host: its system calls, each alone, and
    # the step in which the thread's CPU clock advances
    for name, call in (("thread_time", time.thread_time),
                       ("process_time", time.process_time),
                       ("getrusage_thread", lambda: resource.getrusage(
                           resource.RUSAGE_THREAD))):
        def called():
            for i in range(n):
                call()
        out[name + "_ns"] = (_best(called, 5) - base) / n * 1e9
    seen, end = set(), time.perf_counter() + 0.25
    while time.perf_counter() < end:
        seen.add(time.thread_time())
    steps = sorted(seen)
    out["thread_time_step_s"] = min(b - a for a, b in zip(steps, steps[1:]))
    if not hasattr(spans, "LoopClock"):
        return out
    got = {}

    def owner():
        clock = spans.LoopClock("bench loop", ("decode", "idle"),
                                ("bench.wait",), sleep="bench.wait")

        def ticked():
            for i in range(n):
                clock.tick("decode", i)

        clock.tick("idle")      # this thread owns the clock from here
        got["tick"] = (_best(ticked) - base) / n * 1e9
        got["span"] = (_best(spanned) - base) / n * 1e9

    t = threading.Thread(target=owner)
    t.start()
    t.join()
    out["tick_ns"], out["span_on_clock_ns"] = got["tick"], got["span"]
    return out


def measure_ledger(n: int = 2000) -> dict:
    """What the compile ledger costs (ISSUE 49), nanoseconds each: the
    listeners' work for ONE program (three starts, three durations and the
    cache's two events, as JAX fires them once a compilation and never in a
    warmed loop) and ``ensure_compile_cache()``'s second call.  None on a
    tree from before the ledger.  The ``n`` records land in this process's
    ledger under the name ``bench.cost``."""
    from tpu_dist.utils import ensure_compile_cache
    ensure_compile_cache()

    def again():
        for i in range(n):
            ensure_compile_cache()

    def empty():
        for i in range(n):
            pass

    base = _best(empty, 5)
    out = {"n": n, "ensure_compile_cache_again_ns":
           (_best(again, 5) - base) / n * 1e9, "program_ns": None}
    try:    # by name: ``tpu_dist.obs.compiles`` the attribute is a function
        ledger = importlib.import_module("tpu_dist.obs.compiles")
    except ImportError:
        return out
    stages = ((ledger._TRACE, "bench.cost"), (ledger._LOWER, "jit(bench.cost)"),
              (ledger._BACKEND, "jit(bench.cost)"))

    def programs():
        for i in range(n):
            for event, name in stages:
                if event == ledger._BACKEND:
                    ledger._on_event(ledger._ASKED)
                    ledger._on_event(ledger._KEPT)
                ledger._on_scalar(event, 0.0, fun_name=name)
                ledger._on_duration(event, 1e-9, fun_name=name)

    out["program_ns"] = (_best(programs, 5) - base) / n * 1e9
    return out


if __name__ == "__main__":
    print(json.dumps(dict(measure(), ledger=measure_ledger())))
