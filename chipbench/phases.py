"""The program's own host phases (tpu_dist.obs.spans) as a per-layer reader
sees them.  A program from before the spans has none: every function here
then gives None, and the harness leaves the metric out of the line."""

from __future__ import annotations


def process(names) -> dict | None:
    """``tpu_dist.obs.phase_times(names)``: the whole process's table, warm-up
    included (nothing resets it in a training run)."""
    try:
        from tpu_dist.obs import phase_times
    except ImportError:
        return None
    return phase_times(names)


def engine(run) -> dict | None:
    """``SlotEngine.stats()["phases"]`` as read at the window's end; the
    driver zeroes them with the other counters at its start."""
    return run.counters.get("engine", {}).get("phases")


def seconds(phases: dict, names) -> float:
    """Exact: a LatencyHistogram keeps its sum (``mean * count``)."""
    return sum(phases[n]["mean"] * phases[n]["count"] for n in names)
