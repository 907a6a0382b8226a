"""Seconds in all-reduce / reduce-scatter / all-gather operations over the
traced slice, mean over the chips.  Absent where no collective ran."""

from chipbench.readers import trace_share


def read(run):
    return trace_share(run, "collective_s")
