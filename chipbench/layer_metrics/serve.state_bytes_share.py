"""Of the cache bytes the window's decode steps had to touch for their busy
slots (``SlotEngine.stats()["state"]``: a recurrent layer's whole state, read
and written once a step; the K/V columns a slot holds), the share that is
recurrent state: which of the two caches sets a step's pace.  0 for a model of
attention layers alone.  A program without the counter, as the parent of PR
30 is, reports nothing."""


def read(run):
    st = run.counters.get("engine", {}).get("state")
    if not st or not (st["state_bytes"] + st["kv_bytes"]):
        return None
    return 100.0 * st["state_bytes"] / (st["state_bytes"] + st["kv_bytes"])
