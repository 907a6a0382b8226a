"""Routed rows of the busiest expert over the mean expert's, over the window
and all layers (``SlotEngine.stats()["moe"]``; requests' rows only).  1 is a
perfectly even router; the busiest expert's segment sets how many row blocks
a grouped matmul sweeps."""


def read(run):
    moe = run.counters.get("engine", {}).get("moe")
    if not moe or not moe["rows"]:
        return None
    per = moe["rows_per_expert"]
    return max(per) / (sum(per) / len(per))
