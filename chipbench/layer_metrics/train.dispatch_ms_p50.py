"""Median of the program's ``train.dispatch`` phase
(``DistributedDataParallel.train_step``): the host's call into the jitted
step, argument handling and enqueue, not the step.  Over the whole process,
so the first call's trace-and-compile is one sample far above the median."""

from chipbench import phases


def read(run):
    table = phases.process(["train.dispatch"])
    h = table and table["train.dispatch"]
    return 1e3 * h["p50"] if h and h["count"] else None
