"""What the grouped-matmul kernels need where the expert layer holds ONE
CHIP'S SHARE of the experts it routes over, and their share of the roofline
from a reduced trace.

``chipbench.gmm_need`` charges a call with every pick of a request and takes
the expert's width from ``intermediate_size``; a layer told to hold 64 of the
router's 512 experts (``tpu_dist/nn/moe.py``, ``experts_held``) is sent all
the picks and needs only those that fall on an expert it holds.  What a call
needs, by the mathematics: the requests' rows that fell on a HELD expert, each
through one (d_in, d_out) matrix; the matrices of the held experts such a row
reached, read once; each such row read and written once.  Picks of absent
experts, rows of free slots and of bucket padding, and the kernels' block
alignment are nobody's need.  The program's counters
(``SlotEngine.stats()["moe"]["by_phase"]``: ``held_rows``, ``experts_hit``,
``calls``) give, per pool program, the held rows and the experts reached of
a call as means over the window.
"""

from __future__ import annotations

from . import flops
from .gmm_need import calls, grouped_matmul


def phase_means(moe: dict) -> dict:
    """{pool program -> (requests' rows on held experts a call, held experts
    a call reached)}; None for a program that made no call or for counters
    that do not tell held rows apart (a program from before them)."""
    def means(c):
        if not c.get("calls") or "held_rows" not in c:
            return None
        return c["held_rows"] / c["calls"], c["experts_hit"] / c["calls"]
    return {phase: means(c) for phase, c in moe["by_phase"].items()}


def roofline_share(reduced: dict, moe: dict, decode_rows: int, d_model: int,
                   d_expert: int, peak: dict, itemsize: int = 2):
    """Least seconds the chip could take for the traced ``gmm_r<R>`` calls
    over the seconds they took, in percent; None where the trace holds no
    such call or the counters no held rows.  ``decode_rows`` is the picks a
    decode step routes (R of its calls); a call of any other size is a
    prefill bucket's.  Gate, up (d_model -> d_expert) and down (the reverse)
    need the same operations and bytes."""
    means = phase_means(moe)
    least = took = 0.0
    for routed, seconds in calls(reduced):
        m = means.get("decode" if routed == decode_rows else "prefill")
        if m is None:
            continue
        need = grouped_matmul(*m, d_model, d_expert, itemsize)
        least += flops.roofline(need["flops"], need["bytes"], peak)[0]
        took += seconds
    return 100.0 * least / took if took else None
