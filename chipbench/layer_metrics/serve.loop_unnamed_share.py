"""Share of the window the serving loop's thread spent under NO ``td/`` span
(``stats()["loop"]["unnamed_s"]``: an iteration's wall time less what its
depth-0 spans cover).  Near 0, the phase table is the thread's whole
account; the scheduler's own lines between the spans are what is left."""

from chipbench import loop_clock


def read(run):
    return loop_clock.share(run, "unnamed_s")
