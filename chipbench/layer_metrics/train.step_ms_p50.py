"""Median of the harness's spans around ``ddp.train_step``; the traced run
blocks on every step's loss, so a span is dispatch to completion."""

from chipbench.readers import span_median_ms


def read(run):
    return span_median_ms(run, "train_step")
