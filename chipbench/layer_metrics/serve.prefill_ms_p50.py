"""Median time of one whole-prompt prefill: dispatch to the first token's
blocking readback (``SlotEngine.hist_prefill``).  ``_admit`` runs prefills one
at a time between decode steps, so this also lengthens the gaps between the
tokens of requests already decoding."""

from chipbench.readers import engine_hist


def read(run):
    h = engine_hist(run, "prefill")
    return 1e3 * h["p50"] if h else None
