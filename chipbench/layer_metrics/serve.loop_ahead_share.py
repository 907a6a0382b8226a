"""Share of the window's pool programs, prefills and decode steps alike, that
were launched while an earlier program's result was still uncollected
(``SlotEngine.stats()["pipeline"]``): how often the loaded loop kept the chip
one program ahead of the host.  A program without the counter, as the parent
of PR 29 is, reports nothing."""


def read(run):
    p = run.counters.get("engine", {}).get("pipeline")
    if not p or not sum(p["launches"].values()):
        return None
    return (100.0 * sum(p["launched_ahead"].values())
            / sum(p["launches"].values()))
