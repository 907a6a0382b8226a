"""The fused cross-entropy kernels' share of their roofline: logits read once
forward, read again and a gradient written backward, against HBM bandwidth."""

from chipbench import flops
from chipbench.readers import kernel_roofline


def read(run):
    if not run.trace or run.peak is None:
        return None
    c = run.counters
    need = flops.fused_cross_entropy(c["per_chip_batch"] * c["seq_len"],
                                     run.model_kwargs["vocab_size"])
    return kernel_roofline(run, {"fwd": ("fused_ce_fwd",),
                                 "bwd": ("fused_ce_bwd",)}, need)
