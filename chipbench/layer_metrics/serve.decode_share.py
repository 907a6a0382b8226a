"""Share of the window the serving loop spent inside decode-step calls (the
sum of ``SlotEngine.hist_token``)."""

from chipbench.readers import engine_time_share


def read(run):
    return engine_time_share(run, "decode_step")
