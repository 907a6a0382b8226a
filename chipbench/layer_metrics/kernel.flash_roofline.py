"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the attention calls in the trace (chipbench.flops,
causal at half of T^2, backward without the recomputed scores) over the time
the three kernels took."""

from chipbench import flops
from chipbench.readers import kernel_roofline


def read(run):
    if not run.trace or run.peak is None:
        return None
    kw, c = run.model_kwargs, run.counters
    need = flops.flash_attention(c["per_chip_batch"], kw["num_heads"],
                                 c["seq_len"], kw["dim"] // kw["num_heads"])
    return kernel_roofline(
        run, {"fwd": ("flash_fwd",),
              "bwd": ("flash_bwd_dq", "flash_bwd_dkv")}, need)
