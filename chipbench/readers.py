"""Arithmetic the per-layer readers share.  A reader that finds nothing to
read returns None, and the harness leaves its metric out of the line."""

from __future__ import annotations

import statistics

from . import flops, trace_reduce


def span_median_ms(run, name: str):
    d = run.spans.durations(name, *run.window)
    return 1e3 * statistics.median(d) if d else None


def engine_hist(run, name: str):
    """One of the engine's latency summaries (seconds; ``LatencyHistogram``:
    mean and max exact, percentiles on a 2% grid), None when empty."""
    h = run.counters.get("engine", {}).get(name)
    return h if h and h["count"] else None


def engine_time_share(run, name: str):
    """Share of the window the serving loop spent in those engine calls."""
    h = engine_hist(run, name)
    t0, t1 = run.window
    return 100.0 * h["mean"] * h["count"] / (t1 - t0) if h else None


def trace_share(run, key: str):
    """``key`` seconds of the reduced trace over its window, in percent."""
    tr = run.trace
    return 100.0 * tr[key] / tr["window_s"] if tr and tr.get(key) else None


def idle_share(run):
    tr = run.trace
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"]) if tr else None


def kernel_share(run, names):
    """Those kernels' seconds over device 0's busy seconds, in percent."""
    if not run.trace:
        return None
    seconds, calls = trace_reduce.kernel(run.trace, names)
    return 100.0 * seconds / run.trace["busy0_s"] if calls else None


def kernel_roofline(run, passes: dict, need: dict):
    """Least seconds the chip could take for the calls the trace holds, over
    the seconds they took, in percent.  ``passes`` maps "fwd"/"bwd" to the
    kernel names of that pass; the first name of each counts the calls."""
    if not run.trace:
        return None
    least = took = 0.0
    for which, names in passes.items():
        seconds, _ = trace_reduce.kernel(run.trace, names)
        _, calls = trace_reduce.kernel(run.trace, names[:1])
        t, bound = flops.roofline(need[which + "_flops"],
                                  need[which + "_bytes"], run.peak)
        print(f"[chipbench]     {'+'.join(names)}: {calls} calls, "
              f"{seconds * 1e3:.3f} ms, least {t * calls * 1e3:.3f} ms "
              f"({bound} bound)", flush=True)
        least, took = least + t * calls, took + seconds
    return 100.0 * least / took if took else None
