"""Median time of one decode step over the slot pool: dispatch to the blocking
readback of the sampled tokens (``SlotEngine.hist_token``); the host's
bookkeeping and token sends after it are not in it."""

from chipbench.readers import engine_hist


def read(run):
    h = engine_hist(run, "decode_step")
    return 1e3 * h["p50"] if h else None
