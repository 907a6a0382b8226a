"""Seconds in the flash-attention kernels over device 0's busy seconds."""

from chipbench.readers import kernel_share

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run):
    return kernel_share(run, KERNELS)
