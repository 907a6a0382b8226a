"""Seconds of set-up in the backend stage: ``backend_s`` of the compile
ledger's records (tpu_dist.obs.compiles) whose backend stage ended before the
window's first instant: XLA's compilation of a program, or its load from the
persistent cache (what the harness calls "compiled or loaded")."""

from chipbench import compiles


def read(run):
    ledger = compiles.setup(run)
    return ledger["backend_s"] if ledger else None
