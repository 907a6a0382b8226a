"""The least time the window's decode steps could take at the chip's peaks
(chipbench.decode_need: every byte of weights and resident cache they had to
read at the memory's peak, or every operation at the MXU's, whichever is
larger, from ``SlotEngine.stats()["decode_need"]``) over the time the serving
loop charged them (the sum of ``SlotEngine.hist_token``, collection to
collection).  A program without the counter, as the parent of PR 32 is, and a
run with no chip's peaks report nothing."""

from chipbench import decode_need
from chipbench.readers import engine_hist


def read(run):
    need = run.counters.get("engine", {}).get("decode_need")
    h = engine_hist(run, "decode_step")
    if not need or not h or run.peak is None:
        return None
    return decode_need.roofline_share(need, h["mean"] * h["count"], run.peak)
