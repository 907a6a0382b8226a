"""What the decode steps of a serving window needed of the chip, and the
share of it that their time was.

The program counts the need itself (``SlotEngine.stats()["decode_need"]``,
summed over the window's decode steps; tpu_dist/serve/engine.py): the bytes of
every parameter a step reads once (all but the gathered token table and the
routed experts, plus one expert's matrices for each held expert a request's
row reached), the bytes of the cache columns (and whole state) the busy slots
held, and the operations: 2 x the parameters a busy row uses + the
attention's operations a resident position.  Rows of free slots, padding, a
dense branch's reading of the whole pool and anything a kernel recomputes are
nobody's need.  Here: the least time the chip could take for that, by its two
peaks, and what the steps' own time makes of it.
"""

from __future__ import annotations

from . import flops


def least_seconds(need: dict, peak: dict) -> tuple:
    """(least seconds for the window's decode steps, which bound sets it):
    the larger of all the bytes at the memory's peak and all the operations
    at the MXU's."""
    return flops.roofline(need["flops"],
                          need["weight_bytes"] + need["cache_bytes"], peak)


def roofline_share(need: dict, seconds: float, peak: dict):
    """Least seconds over the ``seconds`` the decode steps were charged, in
    percent; None where there was no step."""
    if not need or not need.get("steps") or not seconds:
        return None
    return 100.0 * least_seconds(need, peak)[0] / seconds


def latent_read_share(need: dict):
    """Of the bytes the decode steps had to read, the share that is cache
    (resident columns and whole state) and not weights, in percent."""
    total = need["cache_bytes"] + need["weight_bytes"] if need else 0
    return 100.0 * need["cache_bytes"] / total if total else None
