"""Share of the window the serving loop spent inside prefill calls (the sum of
``SlotEngine.hist_prefill``).  With serve.decode_share it splits the loop's
time; the rest is the host's."""

from chipbench.readers import engine_time_share


def read(run):
    return engine_time_share(run, "prefill")
