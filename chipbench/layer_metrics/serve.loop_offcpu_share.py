"""Share of the window in which the serving loop's thread wanted to run and
did not (``stats()["loop"]["offcpu_s"]``: every iteration's wall time less
its two designed waits, ``*.readback`` and ``sched.wait``, less the thread's
own CPU time): the interpreter lock, the OS scheduler, a blocking send.  A
lower bound.  Prints the window's sums and the three longest iterations of
each kind with their records, which the driver's own lines leave out."""

from chipbench import loop_clock


def read(run):
    loop = loop_clock.engine(run)
    if not loop:
        return None
    loop_clock.say(loop)
    return loop_clock.share(run, "offcpu_s")
