"""Seconds of set-up the program spent tracing and lowering: ``trace_s +
lower_s`` of the compile ledger's records (tpu_dist.obs.compiles) whose
backend stage ended before the window's first instant.  Host Python, on the
thread that first calls each program, paid at every start whether or not the
persistent cache holds the program.  Prints the ledger: the totals, the
``setup.*`` phases, the cache directory and the eight longest programs."""

from chipbench import compiles


def read(run):
    ledger = compiles.setup(run)
    if not ledger:
        return None
    compiles.say(ledger)
    return ledger["trace_s"] + ledger["lower_s"]
