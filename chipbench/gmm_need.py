"""What the grouped-matmul kernels of a served expert layer need, and their
share of the roofline from a reduced trace.

``tpu_dist/nn/moe.py`` names each ``pallas_call`` of its dropless path
``gmm_r<R>``, R the rows the call routes (tokens x experts per token: 8192 in
a 1024-token prefill, 256 in a 32-slot decode step), so a trace tells the
two pool programs' calls apart.  What a call needs, by the mathematics: the
rows that belong to a request, each through one (d_in, d_out) matrix; the
matrices of the experts such a row reached, read once; each such row read
and written once.  Rows of free slots, of bucket padding and of the
kernels' block alignment are work the program does and nobody needs.  The
program's counters (``SlotEngine.stats()["moe"]["by_phase"]``) say, per pool
program, which share of the routed rows were a request's and how many experts
a call reached, as means over the window.
"""

from __future__ import annotations

import re

from . import flops

_CALL = re.compile(r"gmm_r(\d+)")


def grouped_matmul(rows: float, experts_hit: float, d_in: int, d_out: int,
                   itemsize: int = 2) -> dict:
    """One grouped matmul of ``rows`` routed rows over ``experts_hit``
    experts' (d_in, d_out) matrices, as operations and HBM bytes."""
    return {"flops": 2.0 * rows * d_in * d_out,
            "bytes": (experts_hit * d_in * d_out
                      + rows * (d_in + d_out)) * itemsize}


def calls(reduced: dict) -> list:
    """[(routed rows of the call, seconds)] of device 0's gmm kernels."""
    out = []
    for name, s, e in reduced.get("rows0", ()):
        m = _CALL.search(name.split(" ", 1)[0])
        if m:
            out.append((int(m.group(1)), (e - s) * 1e-9))
    return out


def phase_means(moe: dict) -> dict:
    """{pool program -> (share of its routed rows that are a request's,
    experts a call reached)} from the engine's counters; None for a program
    that made no call."""
    def means(c):
        routed = c["rows"] + c["pad_rows"]
        return ((c["rows"] / routed, c["experts_hit"] / c["calls"])
                if routed and c["calls"] else None)
    return {phase: means(c) for phase, c in moe["by_phase"].items()}


def roofline_share(reduced: dict, moe: dict, decode_rows: int, d_model: int,
                   d_expert: int, peak: dict, itemsize: int = 2):
    """Least seconds the chip could take for the traced gmm calls over the
    seconds they took, in percent; None where the trace holds no such call.
    ``decode_rows`` is what a decode step routes; a call of any other size
    is a prefill bucket's.  Every matmul of the gated expert (gate, up:
    d_model -> d_expert; down: the reverse) needs the same operations and
    bytes, so a call's direction does not matter."""
    means = phase_means(moe)
    least = took = 0.0
    for routed, seconds in calls(reduced):
        m = means["decode" if routed == decode_rows else "prefill"]
        if m is None:
            continue
        real, hit = m
        need = grouped_matmul(routed * real, hit, d_model, d_expert, itemsize)
        least += flops.roofline(need["flops"], need["bytes"], peak)[0]
        took += seconds
    return 100.0 * least / took if took else None
