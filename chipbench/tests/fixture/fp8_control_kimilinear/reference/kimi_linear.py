"""A CONTROL, not the reference: the plain Kimi Linear reference
(chipbench/reference/kimi_linear.py) over the program's parameters ROUNDED TO
float8 e4m3, the nearest precision below the bfloat16 the configuration
states: every matrix a matmul reads, and nothing else (activations, the
recurrent state, the convolutions' taps, the norms' weights, ``A_log``,
``dt_bias`` and the router's bias stay as they are: the weakest float8
computation there is, the form the older cells' controls have).
``BENCHMARK.json`` beside this directory runs the cell
``serve-kimilinear-reason`` as it is (the same configuration file, mix,
driver and comparison) and finds THIS file first where the configuration
names its reference, so::

    python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fp8_control_kimilinear/BENCHMARK.json \
        --workload serve-kimilinear-reason --seed <n> --seconds 30 --trace 0

judges the program's bfloat16 tokens by the same mathematics over float8
weights, through the comparison that decides ``correct``.  The two disagree
by what float8 loses, so the run has to end ``"correct": false``: a
``logit_tol`` this control passes cannot tell a precision from the one below
it (PERF.md section 6, PR 40, and the configuration's ``logit_tol_reason``
have both readings).
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from chipbench import spec

_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "kimi_linear.py"))
forward = _plain.forward
_gated_mlp = _plain.gated_mlp


def _e4m3(a):
    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def _gated_mlp_low(gate, up, down, h):
    """The plain ``gated_mlp`` with its three matrices rounded where they
    are READ: the routed experts' stacks come here one expert of the scan at
    a time (a rounded copy of the 32 experts of thirteen layers, 5.5 of the
    6.9 GiB, beside the program's parameters does not fit the chip); the
    dense and shared matrices come rounded already, and rounding is
    idempotent."""
    return _gated_mlp(_e4m3(gate), _e4m3(up), _e4m3(down), h)


# this load of the plain module, no other
_plain.gated_mlp = _gated_mlp_low


def stack_params(config: dict, params: dict) -> dict:
    """The plain reference's regrouping over the parameters with every
    matrix a matmul reads rounded to float8 e4m3 and back: not the vectors
    (norms' weights, ``A_log``, ``dt_bias``, the router's bias) nor a
    convolution's ``(channels, taps)``.  The experts' stacks (three axes)
    pass as they are and are rounded at use."""
    low = {path: {name: _e4m3(a)
                  if a.ndim == 2 and not name.endswith("conv_weight") else a
                  for name, a in leaves.items()}
           for path, leaves in params.items()}
    return _plain.stack_params(config, low)
