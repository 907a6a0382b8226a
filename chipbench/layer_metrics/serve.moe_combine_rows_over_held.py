"""Rows the expert layers' combine gathered over the requests' rows that fell
on an expert the model holds, over the window, both pool programs and all
layers (``SlotEngine.stats()["moe"]``: ``combined_rows`` / ``held_rows``).  A
combine that gathers a row for every pick of a call reads the picks over the
held ones (about 11 for a model that holds an eighth of its experts behind
bucket padding, 1.1-1.4 for one that holds them all); one that walks its row
buffer reads the buffer's rows over them, or half of those where the call's
held picks fit the half.  A program without the counter, as the parent of
PR 46 is, reports nothing."""


def read(run):
    moe = run.counters.get("engine", {}).get("moe")
    if not moe or not moe.get("held_rows") or "combined_rows" not in moe:
        return None
    return moe["combined_rows"] / moe["held_rows"]
