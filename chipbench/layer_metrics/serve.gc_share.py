"""Share of the window the interpreter's garbage collector ran, on any
thread (``stats()["loop"]["gc_s"]``: the ``td/gc`` pauses that ended inside
the loop thread's iterations; a collection holds the interpreter lock
whichever thread set it off)."""

from chipbench import loop_clock


def read(run):
    return loop_clock.share(run, "gc_s")
