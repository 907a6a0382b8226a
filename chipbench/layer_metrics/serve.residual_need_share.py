"""The share of the window's model time that the residual mixes would take at
the chip's memory peak: the bytes ``SlotEngine.stats()["residual"]`` says the
requests' rows had to move through every sublayer's two mixes, prefills and
decode steps together (chipbench.residual_need: ``3 n + 2`` widths a row a
sublayer), at 819 GB/s, over the time the serving loop charged the two pool
programs (the sums of ``hist_prefill`` and ``hist_token``, collection to
collection).  What a kept trace's ``hc_*`` scopes take over this share is the
mix's distance from its roofline (PERF.md section 5).  A program without the
counter, as the parent of PR 38 is, a model whose residual is one stream and
a run with no chip's peaks report nothing."""

from chipbench import residual_need
from chipbench.readers import engine_hist


def read(run):
    residual = run.counters.get("engine", {}).get("residual")
    if not residual or run.peak is None:
        return None
    charged = [h["mean"] * h["count"]
               for h in (engine_hist(run, "prefill"),
                         engine_hist(run, "decode_step")) if h]
    return residual_need.need_share(residual, sum(charged), run.peak)
