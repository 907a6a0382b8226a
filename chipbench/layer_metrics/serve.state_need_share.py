"""The share of the window's model time that the recurrent state would take
at the chip's memory peak: the bytes ``SlotEngine.stats()["state"]`` says the
decode steps had to move for their busy slots' whole state (each read once
and written once a step; chipbench.state_need), at 819 GB/s, over the time
the serving loop charged the two pool programs (the sums of ``hist_prefill``
and ``hist_token``, collection to collection).  The floor under what a
one-token-update kernel could win: what a kept trace's ``state_update``
scopes take over this share is the update's distance from its roofline
(PERF.md section 5).  A program without the counter, as the parent of PR 30
is, a model of attention layers alone and a run with no chip's peaks report
nothing."""

from chipbench import state_need
from chipbench.readers import engine_hist


def read(run):
    state = run.counters.get("engine", {}).get("state")
    if not state or run.peak is None:
        return None
    charged = [h["mean"] * h["count"]
               for h in (engine_hist(run, "prefill"),
                         engine_hist(run, "decode_step")) if h]
    return state_need.need_share(state, sum(charged), run.peak)
