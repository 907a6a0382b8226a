"""Seconds in the grouped-matmul kernels (the expert layer's matmuls,
tpu_dist/ops/gmm.py) over device 0's busy seconds."""

from chipbench.readers import kernel_share


def read(run):
    return kernel_share(run, ("gmm",))
