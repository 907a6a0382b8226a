"""A CONTROL, not the reference: the plain Xing4.0 reference
(chipbench/reference/xing4.py) over the program's parameters ROUNDED TO
float8 e4m3, the nearest precision below the bfloat16 the configuration
states: every matrix a matmul reads, and nothing else (activations, streams
and coefficients stay float32: the weakest float8 computation there is, the
form the older cells' controls have).  ``BENCHMARK.json`` beside this
directory runs the cell ``serve-xing4-longdocs`` as it is (the same
configuration file, mix, driver and comparison) and finds THIS file first
where the configuration names its reference, so::

    python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fp8_control_xing4/BENCHMARK.json \
        --workload serve-xing4-longdocs --seed <n> --seconds 30 --trace 0

judges the program's bfloat16 tokens by the same mathematics over float8
weights, through the comparison that decides ``correct``.  The two disagree
by what float8 loses, so the run has to end ``"correct": false``: a
``logit_tol`` this control passes cannot tell a precision from the one below
it (PERF.md section 6, PR 38, and the configuration's ``logit_tol_reason``
have the readings of this form and of two stronger ones).
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from chipbench import spec

_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "xing4.py"))
forward = _plain.forward
_gated_mlp = _plain.gated_mlp


def _e4m3(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _gated_mlp_low(gate, up, down, h):
    """The plain ``gated_mlp`` with its three matrices rounded where they
    are READ: the routed experts' stacks come here one expert of the scan at
    a time (a rounded copy of the 64 experts of five layers, 6.6 of the 7.4
    GiB, beside the program's parameters does not fit the chip); the dense
    and shared matrices come rounded already, and rounding is idempotent."""
    return _gated_mlp(_e4m3(gate), _e4m3(up), _e4m3(down), h)


# this load of the plain module, no other
_plain.gated_mlp = _gated_mlp_low


def stack_params(config: dict, params: dict) -> dict:
    """The plain reference's regrouping over the parameters with every
    matrix a matmul reads rounded to float8 e4m3 and back: not the norms'
    weights, the scales, the router's bias nor the hyper-connections'
    biases (``res_bias`` is n x n, and a bias all the same).  The experts'
    stacks (three axes) pass as they are and are rounded at use."""
    low = {path: {name: _e4m3(a)
                  if a.ndim == 2 and not name.endswith("_bias") else a
                  for name, a in leaves.items()}
           for path, leaves in params.items()}
    return _plain.stack_params(config, low)
