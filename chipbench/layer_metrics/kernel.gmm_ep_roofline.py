"""The grouped-matmul kernels' share of their roofline where the layer holds
a share of the experts: the least time the chip could take for the requests'
rows that fell on a held expert and the held experts' matrices they reach
(chipbench.gmm_ep_need) over the time the traced ``gmm_r<R>`` calls took.
Widths from ``hidden_size`` and ``moe_intermediate_size``.  A program without
the ``held_rows`` counter, as the parent of PR 30 is, reports nothing."""

from chipbench import gmm_ep_need


def read(run):
    moe = run.counters.get("engine", {}).get("moe")
    cfg = run.ctx.config
    if (not run.trace or run.peak is None or not moe
            or "moe_intermediate_size" not in cfg):
        return None
    return gmm_ep_need.roofline_share(
        run.trace, moe, cfg["serve"]["slots"] * cfg["num_experts_per_tok"],
        cfg["hidden_size"], cfg["moe_intermediate_size"], run.peak)
