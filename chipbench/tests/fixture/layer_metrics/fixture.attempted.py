"""A per-layer metric added as a new file: what the window attempted."""


def read(run):
    c = run.counters
    return c.get("steps", c.get("engine", {}).get("completed"))
