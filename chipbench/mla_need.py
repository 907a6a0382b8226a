"""What the latent decode-attention kernel needs, and its share of the
roofline from a reduced trace.

``tpu_dist/ops/decode_attention.py`` names its latent form's ``pallas_call``
``latent_decode_attention``: one call a latent-attention layer a decode step,
every head's query row of every busy slot against that slot's resident
columns of a ``(slots, C, Tmax)`` pool, the values the first ``R`` rows of the
same columns.  What a call needs, by the mathematics: the resident columns of
the busy slots read ONCE (the new one is written, not read), ``C`` numbers
each; the new column written; each busy slot's ``H x C`` queries read and its
``H x R`` weighted latent written; ``2 H (C + R)`` operations a resident
column.  Columns past a slot's length, free slots, the block a copy rounds up
to and the slab the write rounds up to are nobody's need.  The program's
counter (``SlotEngine.stats()["decode_need"]``: ``steps``, ``rows``,
``positions``) gives the busy rows and the resident columns of a step as
means over the window.
"""

from __future__ import annotations

from . import flops, trace_reduce

KERNEL = "latent_decode_attention"


def call(rows: float, positions: float, heads: int, latent: int, values: int,
         itemsize: int = 2) -> dict:
    """One call over ``rows`` busy slots that hold ``positions`` columns in
    all (the ones written included), as operations and HBM bytes."""
    return {"flops": 2.0 * heads * (latent + values) * positions,
            "bytes": (positions * latent
                      + rows * heads * (latent + values)) * itemsize}


def roofline_share(reduced: dict, need: dict, heads: int, latent: int,
                   values: int, peak: dict, itemsize: int = 2):
    """Least seconds the chip could take for the traced calls over the
    seconds they took, in percent; None where the trace holds no such call
    or the counter no step."""
    seconds, calls = trace_reduce.kernel(reduced, (KERNEL,))
    if not calls or not need or not need.get("steps"):
        return None
    one = call(need["rows"] / need["steps"],
               need["positions"] / need["steps"], heads, latent, values,
               itemsize)
    return (100.0 * calls * flops.roofline(one["flops"], one["bytes"],
                                           peak)[0] / seconds)
