"""Prompts a prefill program carried, the window's mean: the requests launched
in prefill programs over the programs (``SlotEngine.stats()["pipeline"]``:
``prefill_prompts / launches["prefill"]``).  A program of a bucket takes as
many prompts as the pool's largest bucket holds of it (``prefill_width``), and
reads every layer's weights once for all of them; 1.0 where the bucket is the
largest, and where no request ever found company.  A program without the
counter, as the parent of PR 48 is, reports nothing."""


def read(run):
    p = run.counters.get("engine", {}).get("pipeline")
    if not p or "prefill_prompts" not in p or not p["launches"]["prefill"]:
        return None
    return p["prefill_prompts"] / p["launches"]["prefill"]
