"""Seconds in the fused cross-entropy kernels over device 0's busy seconds."""

from chipbench.readers import kernel_share

KERNELS = ("fused_ce_fwd", "fused_ce_bwd")


def read(run):
    return kernel_share(run, KERNELS)
