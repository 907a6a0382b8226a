"""Share of the window in which the serving loop's own thread held the chip
back: every phase of that thread except its waits on the device
(``*.readback``) and for work (``sched.wait``).  ``stage.put`` is the staging
thread's (inline, it lies inside ``prefill.prepare`` already)."""

from chipbench import phases

HOST = ("sweep", "prefill.prepare", "prefill.dispatch", "prefill.emit",
        "decode.dispatch", "decode.emit")


def read(run):
    p = phases.engine(run)
    if not p:
        return None
    t0, t1 = run.window
    return 100.0 * phases.seconds(p, HOST) / (t1 - t0)
