"""Times a causal flash-attention call's backward pass computes the
sub-tiles of scores a pass executes: 1.0 where one kernel makes a sub-tile's
scores and probabilities once for dQ, dK and dV, 2.0 where a dQ kernel and a
dK/dV kernel each make them.  Host arithmetic on the cell's shapes by the
program's own ``tpu_dist.ops.flash_attention.backward_plan`` (the function
the backward call decides with) over ``tile_plan``; no device read.  A
program without ``backward_plan``, as the parent of PR 50 is, reports
nothing."""

import importlib


def read(run):
    # ``tpu_dist.ops.flash_attention`` the attribute is the function
    fa = importlib.import_module("tpu_dist.ops.flash_attention")
    backward, plan = (getattr(fa, "backward_plan", None),
                      getattr(fa, "tile_plan", None))
    if backward is None or plan is None:
        return None
    t, kw = run.counters["seq_len"], run.model_kwargs
    dtype = run.ctx.config["train"]["compute_dtype"]
    executed = plan(t, t, True, dtype=dtype)["executed"]
    made = backward(t, t, kw["dim"] // kw["num_heads"], True, dtype=dtype)
    return made["score_passes"] / executed if executed else None
