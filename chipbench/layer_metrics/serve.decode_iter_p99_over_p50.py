"""p99 over p50 of the serving loop's decode-only iterations
(``stats()["loop"]["iteration"]["decode"]``: wall time less the loop's sleep
of every iteration that launched a decode step and no prefill, percentiles on
a 2% grid): the tail a client's p95 gap is cut from.  Prints the window's
sums and the three longest iterations of each kind."""

from chipbench import loop_clock


def read(run):
    loop = loop_clock.engine(run)
    if not loop:
        return None
    loop_clock.say(loop)
    h = loop["iteration"]["decode"]
    return h["p99"] / h["p50"] if h["count"] and h["p50"] else None
