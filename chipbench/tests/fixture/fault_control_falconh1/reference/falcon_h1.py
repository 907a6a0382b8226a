"""A CONTROL, not the reference: the plain Falcon-H1 reference
(chipbench/reference/falcon_h1.py) with ONE fault planted in its mathematics,
named by the environment variable ``FALCONH1_FAULT``:

- ``no_attention``: the attention branch dropped from every layer
  (``attention_out_multiplier`` taken as 0): a Mamba-2 model;
- ``no_ssm``: the state-space branch dropped (``ssm_out_multiplier`` taken
  as 0): a grouped-query transformer;
- ``one_norm_group``: the gated norm over ONE group of all ``mamba_d_ssm``
  numbers where ``mamba_n_groups`` says two;
- ``key_multiplier_1``: the keys unscaled;
- ``dt_no_softplus``: the step ``dt + dt_bias`` as it is, without its
  softplus (negative at the published initialiser, so the state GROWS).

``BENCHMARK.json`` beside this directory runs the cell
``serve-falconh1-reason`` as it is and finds THIS file first where the
configuration names its reference, so::

    FALCONH1_FAULT=no_ssm python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fault_control_falconh1/BENCHMARK.json \
        --workload serve-falconh1-reason --seed <n> --seconds 30 --trace 0

judges the program's tokens by ANOTHER model's mathematics, and has to end
``"correct": false`` (PERF.md section 6, PR 44, has each reading).
tests/test_falcon_h1.py plants the same five, and nineteen more, in the
float32 reference at a small size.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from chipbench import spec

FAULTS = ("no_attention", "no_ssm", "one_norm_group", "key_multiplier_1",
          "dt_no_softplus")
_CONFIG = {"no_attention": {"attention_out_multiplier": 0.0},
           "no_ssm": {"ssm_out_multiplier": 0.0},
           "key_multiplier_1": {"key_multiplier": 1.0}}
_HOOK = {"one_norm_group": ("_norm_groups", lambda config: 1),
         "dt_no_softplus": ("_dt_activation", lambda dt: dt)}

FAULT = os.environ.get("FALCONH1_FAULT", "")
if FAULT not in FAULTS:
    raise SystemExit(f"FALCONH1_FAULT must be one of {FAULTS}, got {FAULT!r}")

_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "falcon_h1.py"))
if FAULT in _HOOK:
    setattr(_plain, *_HOOK[FAULT])      # this load of the plain module only
stack_params = _plain.stack_params


def forward(config: dict, stacked: dict, tokens):
    """The plain forward under the fault.  A state that grows without bound
    (``dt_no_softplus``) ends in logits that are not numbers, and the
    comparison that decides ``correct`` keeps its worst margin with
    Python's ``max``, which drops a ``nan`` (PERF.md section 7): such a row
    is given the ramp 0, 1, 2, ... in place of the ``nan``, so that it
    disagrees with whatever was served as any other wrong row does."""
    logits = _plain.forward(dict(config, **_CONFIG.get(FAULT, {})), stacked,
                            tokens)
    ramp = jnp.arange(logits.shape[-1], dtype=logits.dtype)
    return jnp.where(jnp.isfinite(logits), logits, ramp)
