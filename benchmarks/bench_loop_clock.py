"""What the loop clock costs (ISSUE 34): a ``span`` on a thread with no
clock, a ``span`` on the thread that owns one, and a ``tick``, each in a loop
of 10^5 on the host's CPU (best of 15; the empty loop subtracted); then the
system calls a tick makes, each alone, and the step of the thread's CPU clock.

    JAX_PLATFORMS=cpu python3 benchmarks/bench_loop_clock.py

Host numbers only: nothing here touches a device.
"""

from __future__ import annotations

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


if __name__ == "__main__":
    print(json.dumps(measure()))
