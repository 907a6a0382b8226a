"""Programs built in set-up: the compile ledger's records
(tpu_dist.obs.compiles) whose backend stage ended before the window's first
instant, one-operation eager programs included: each is a trace, a lowering
and a cache lookup of its own."""

from chipbench import compiles


def read(run):
    ledger = compiles.setup(run)
    return ledger["programs"] if ledger else None
