"""A CONTROL, not the reference: the plain Kimi K2 reference
(chipbench/reference/kimi_k2.py) computed with every matrix rounded to
float8 e4m3, the nearest precision below the bfloat16 the configuration
states.  ``BENCHMARK.json`` beside this directory runs the cell
``serve-kimik2-agent`` as it is (the same configuration file, mix, driver and
comparison) and finds THIS file first where the configuration names its
reference, so::

    python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fp8_control_kimik2/BENCHMARK.json \
        --workload serve-kimik2-agent --seed <n> --seconds 30 --trace 0

judges the program's bfloat16 tokens by a float8 computation of the same
mathematics, through the comparison that decides ``correct``.  The two
disagree by what float8 loses, so the run has to end ``"correct": false``:
a ``logit_tol`` this control passes cannot tell a precision from the one
below it (PERF.md section 6, PR 32, has both readings).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from chipbench import spec

_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "kimi_k2.py"))
forward = _plain.forward


def stack_params(config: dict, params: dict) -> dict:
    """The plain reference's regrouping over the parameters with every
    matrix (not the norms' weights nor the router's bias) rounded to float8
    e4m3 and back."""
    low = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    return _plain.stack_params(config, low)
