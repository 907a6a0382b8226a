"""What the one-token state-update kernel needs, and its share of the
roofline from a reduced trace.

``tpu_dist/ops/delta_step.py`` names its ``pallas_call`` ``delta_step``: one
call a recurrent layer a decode step, every busy slot's ``heads`` float32
``(Dk, Dv)`` states advanced by one token.  What a call needs, by the
mathematics: each busy slot's state read ONCE and written ONCE (the
convolution tails are the convolution's and are not counted here, unlike
``state_need.slot_state_bytes``); the rows' ``q``, ``k`` (``Dk`` each), ``v``
(``Dv``), ``g`` (``Dk`` for a decay a channel, 1 for a decay a head) and
``beta`` read and ``o`` (``Dv``) written, float32; 7 operations a number of
state (two contractions, the decay, the rank-one update).  A free slot's
tile, which the kernel moves as a no-op, and the transposed copies its
wrapper makes of the small operands are nobody's need.  The program's
counter (``SlotEngine.stats()["decode_need"]``: ``steps``, ``rows``) gives
the busy rows of a step as a mean over the window.  The need is memory
bound by a wide margin, so the share cannot pass 100% unless the kernel
moves less than the state.
"""

from __future__ import annotations

from . import flops, trace_reduce

KERNEL = "delta_step"


def call(rows: float, heads: int, k_dim: int, v_dim: int,
         per_channel: bool = True) -> dict:
    """One call over ``rows`` busy slots, as operations and HBM bytes."""
    state = rows * heads * k_dim * v_dim
    small = rows * heads * (2 * k_dim + 2 * v_dim + 1
                            + (k_dim if per_channel else 1))
    return {"flops": 7.0 * state, "bytes": 4.0 * (2 * state + small)}


def roofline_share(reduced: dict, need: dict, heads: int, k_dim: int,
                   v_dim: int, peak: dict):
    """Least seconds the chip could take for the traced calls over the
    seconds they took, in percent; None where the trace holds no such call
    or the counter no step."""
    seconds, calls = trace_reduce.kernel(reduced, (KERNEL,))
    if not calls or not need or not need.get("steps"):
        return None
    one = call(need["rows"] / need["steps"], heads, k_dim, v_dim)
    return (100.0 * calls * flops.roofline(one["flops"], one["bytes"],
                                           peak)[0] / seconds)
