"""One clock for the server process and the load generator.

CLOCK_MONOTONIC is the same counter in every process of a machine, so an
instant fixed by one process (the window's start) means the same thing in
the other.
"""

import time


def now() -> float:
    return time.clock_gettime(time.CLOCK_MONOTONIC)
