"""A CONTROL, not the reference: the plain LFM2-MoE reference
(chipbench/reference/lfm2_moe.py) with every matrix a matmul reads ROUNDED TO
float8 e4m3, the nearest precision below the bfloat16 the configuration
states, WHERE IT IS READ (the reference's ``_mat``: the embedding's rows, the
convolution mixers' two projections, the attention's two, the dense SwiGLU's
three, the router, every expert's three and the head), and nothing else:
activations, the convolution's taps, the norms' weights and the router's bias
stay as they are, the weakest float8 computation there is, the form the older
cells' controls have.  Rounded at the read and not in ``stack_params``,
because a rounded copy of 10 GiB of matrices does not fit the chip beside the
program's own.  ``BENCHMARK.json`` beside this directory runs the cell
``serve-lfm2moe-reason`` as it is (the same configuration file, mix, driver
and comparison) and finds THIS file first where the configuration names its
reference, so::

    python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fp8_control_lfm2moe/BENCHMARK.json \
        --workload serve-lfm2moe-reason --seed <n> --seconds 30 --trace 0

judges the program's bfloat16 tokens by the same mathematics over float8
weights, through the comparison that decides ``correct``.  The two disagree
by what float8 loses, so the run has to end ``"correct": false``: a
``logit_tol`` this control passes cannot tell a precision from the one below
it (PERF.md section 6, PR 47, and the configuration's ``logit_tol_reason``
have both readings).
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from chipbench import spec

_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "lfm2_moe.py"))


def _e4m3(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


# this load of the plain module, no other
_plain._mat = _e4m3
forward = _plain.forward
stack_params = _plain.stack_params
