"""The grouped-matmul kernels' share of their roofline: the least time the
chip could take for the traced calls' routed rows and the expert matrices
they reach (chipbench.gmm_need) over the time the kernels took."""

from chipbench import gmm_need


def read(run):
    moe = run.counters.get("engine", {}).get("moe")
    if not run.trace or run.peak is None or not moe:
        return None
    cfg = run.ctx.config
    return gmm_need.roofline_share(
        run.trace, moe, cfg["serve"]["slots"] * cfg["num_experts_per_tok"],
        cfg["hidden_size"], cfg["intermediate_size"], run.peak)
