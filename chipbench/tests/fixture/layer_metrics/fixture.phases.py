"""A reader of the program's own phase table: how many dispatches (training
steps, or decode iterations) ``tpu_dist.obs.phase_times()`` has counted."""


def read(run):
    from tpu_dist.obs import phase_times
    table = phase_times(["train.dispatch", "decode.dispatch"])
    return sum(h["count"] for h in table.values())
