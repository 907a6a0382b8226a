"""What the residual mixes of a serving window needed of the chip, and the
share of the window's model time that need is at the memory's peak.

A residual of ``n`` streams (manifold-constrained hyper-connections;
tpu_dist/nn/hyper.py) makes every sublayer move, for each row, the streams
in and its own input out, then the streams and its output in and the streams
out: ``3 n + 2`` times the model's width, in the type the streams are kept
in.  The coefficients (a norm, three thin projections, a Sinkhorn of 4 x 4
a row) read the streams too; reading them again is an implementation's
choice, not the mathematics' need, and is not counted.  No operation count:
the mixes are a few multiply-adds a number moved, memory bound by a wide
margin on any chip.

The program counts the rows itself (``SlotEngine.stats()["residual"]``: a
prefill's true prompt tokens, a decode step's busy slots; padding and free
slots beside them) and multiplies by what its model says a row costs; this
file has the same arithmetic from the configuration's shapes, for the tests
to hold the two together, and the share.
"""

from __future__ import annotations


def numbers_per_row(streams: int, width: int) -> int:
    """Numbers ONE sublayer moves a row: read X (n C), write u (C); read X
    (n C) and y (C), write X' (n C)."""
    return (3 * streams + 2) * width


def bytes_moved(rows: int, sublayers: int, streams: int, width: int,
                itemsize: int = 2) -> int:
    """Bytes ``rows`` rows move through the mixes of ``sublayers``."""
    return rows * sublayers * numbers_per_row(streams, width) * itemsize


def least_seconds(residual: dict, peak: dict) -> float:
    """Seconds the chip's memory needs for the bytes both pool programs'
    rows had to move."""
    moved = sum(residual[kind]["bytes"] for kind in ("prefill", "decode"))
    return moved / peak["hbm_bytes_per_s"]


def need_share(residual: dict, seconds: float, peak: dict):
    """Least seconds over the ``seconds`` the serving loop charged its
    prefills and decode steps, in percent; None where the program has no
    such counter, the residual is one stream (no bytes) or nothing ran."""
    if not residual or not seconds:
        return None
    least = least_seconds(residual, peak)
    return 100.0 * least / seconds if least else None
