"""The part of collective time during which no other operation ran on that
chip, over the traced slice: communication that compute does not hide."""

from chipbench.readers import trace_share


def read(run):
    return trace_share(run, "collective_exposed_s")
