"""What the gated short convolution layers of a serving window needed of the
chip, and the share of the window's model time that need is at the chip's
peaks.

A gated short convolution layer (tpu_dist/nn/shortconv.py; LFM2's ``conv``
layers) is two projections around a depthwise convolution of ``K`` taps:
``[B | C | u] = x W_in`` (d -> 3 d), ``B * u`` through the taps, times ``C``,
``W_out`` (d -> d).  What ONE call of a pool program needs of one such layer,
by the mathematics: its three matrices read once (``d (4 d + K)``
parameters); for each of the requests' rows ``x`` read, ``[B | C | u]``, the
gated ``y`` and the output moved once (``6 d`` numbers); for each slot the
call serves its tail of ``K - 1`` gated inputs read once and written once;
and ``2 x rows x d (4 d + K)`` operations.  Rows of free slots and of bucket
padding, a second pass over ``[B | C | u]`` and a tail gathered through a
window are nobody's need.

The program counts the calls and the requests' rows itself
(``SlotEngine.stats()["conv"]``: a prefill's true prompt tokens into ONE slot,
a decode step's busy slots, one row each), and this file prices a program's
MEAN call, as chipbench.gmm_ep_need does: a roofline is the larger of two
sums, so the mean call's is a floor under the calls' mean.  What a kept
trace's ``blockN/attn/{in_proj,conv,out_proj}`` scopes take over this share
is the mixer's distance from its roofline (PERF.md section 5).
"""

from __future__ import annotations

from . import flops


def layer_params(width: int, taps: int) -> int:
    """Parameters of one layer: ``W_in`` (d, 3 d), the taps (d, K), ``W_out``
    (d, d)."""
    return width * (4 * width + taps)


def layer_call(rows: float, slots: float, width: int, taps: int,
               itemsize: int = 2) -> dict:
    """Operations and bytes ONE layer needs for one call that carries
    ``rows`` rows of requests for ``slots`` slots."""
    params = layer_params(width, taps)
    moved = 6 * width * rows + 2 * (taps - 1) * width * slots
    return {"flops": 2.0 * rows * params,
            "bytes": (params + moved) * itemsize}


def least_seconds(conv: dict, width: int, taps: int, peak: dict,
                  itemsize: int = 2) -> float:
    """Seconds the chip needs at its peaks for the convolution layers of the
    window's calls, both pool programs: every call priced as its program's
    mean call.  A prefill serves one slot, a decode step a slot a row."""
    total = 0.0
    for kind in ("prefill", "decode"):
        calls = conv[kind]["calls"]
        if not calls:
            continue
        rows = conv[kind]["rows"] / calls
        need = layer_call(rows, 1 if kind == "prefill" else rows, width,
                          taps, itemsize)
        total += conv["layers"] * calls * flops.roofline(
            need["flops"], need["bytes"], peak)[0]
    return total


def need_share(conv: dict, width: int, taps: int, seconds: float,
               peak: dict, itemsize: int = 2):
    """Least seconds over the ``seconds`` the serving loop charged its
    prefills and decode steps, in percent; None where the program has no
    such counter, the model no such layer or nothing ran."""
    if not conv or not conv.get("layers") or not seconds:
        return None
    least = least_seconds(conv, width, taps, peak, itemsize)
    return 100.0 * least / seconds if least else None
