"""Mean share of the pool's slots that held a request, per decode step
(``SlotEngine.occupancy()``)."""


def read(run):
    eng = run.counters.get("engine")
    return 100.0 * eng["occupancy"] if eng and eng["decode_steps"] else None
