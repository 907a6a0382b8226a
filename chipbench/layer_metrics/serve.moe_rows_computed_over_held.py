"""Rows the grouped matmuls computed over the requests' rows that fell on an
expert the model holds, over the window, both pool programs and all layers
(``SlotEngine.stats()["moe"]``: ``computed_rows`` / ``held_rows``).  1.0 when
picks of absent experts, free slots, bucket padding and block alignment cost
nothing; 8 if every pick of a model that holds an eighth were computed.  A
program without the counters, as the parent of PR 30 is, reports nothing."""


def read(run):
    moe = run.counters.get("engine", {}).get("moe")
    if not moe or not moe.get("held_rows") or "computed_rows" not in moe:
        return None
    return moe["computed_rows"] / moe["held_rows"]
