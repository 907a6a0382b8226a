"""Share of the window's decode steps that were launched while an earlier
program's result was still uncollected (``SlotEngine.stats()["pipeline"]``:
decode launches made ahead over decode launches): how often the chip had its
next step enqueued while the host read back and sent the previous one.  A
program without the counter, as the parent of PR 29 is, reports nothing."""


def read(run):
    p = run.counters.get("engine", {}).get("pipeline")
    if not p or not p["launches"]["decode"]:
        return None
    return 100.0 * p["launched_ahead"]["decode"] / p["launches"]["decode"]
