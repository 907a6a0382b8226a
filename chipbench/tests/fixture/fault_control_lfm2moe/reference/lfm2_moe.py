"""A CONTROL, not the reference: the plain LFM2-MoE reference
(chipbench/reference/lfm2_moe.py) with ONE fault planted in its mathematics,
named by the environment variable ``LFM2MOE_FAULT``:

- ``conv_silu``: SiLU after the convolution's taps, as the repo's three older
  users of the helper have it, where LFM2's has no activation;
- ``no_gate_in``: ``B`` dropped, the convolution over ``u`` alone;
- ``no_gate_out``: ``C`` dropped, the taps' sum straight into ``W_out``;
- ``taps_reversed``: tap 0 taken as the current position's;
- ``bias_in_weights``: the picks weighed by ``p + b``, the selection bias
  leaking into the weights;
- ``not_normalized``: the four weights as they are, ``norm_topk_prob`` false;
- ``no_head_norm``: the norms over the query and key heads dropped;
- ``norm_after_rope``: those norms applied after the rotation;
- ``experts_in_layer_1``: ``num_dense_layers`` taken as 1, layer 1's feed-
  forward the expert layer (lent layer 2's router and experts: the program
  has none for it).

``BENCHMARK.json`` beside this directory runs the cell
``serve-lfm2moe-reason`` as it is and finds THIS file first where the
configuration names its reference, so::

    LFM2MOE_FAULT=conv_silu python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fault_control_lfm2moe/BENCHMARK.json \
        --workload serve-lfm2moe-reason --seed <n> --seconds 30 --trace 0

judges the program's tokens by ANOTHER model's mathematics, and has to end
``"correct": false`` for each fault the cell's ``logit_tol`` sees (PERF.md
section 6, PR 47, has each reading and says which it does not).
tests/test_lfm2_moe.py plants the same nine in the float32 reference at a
small size.
"""

from __future__ import annotations

import os

import jax

from chipbench import spec

FAULTS = ("conv_silu", "no_gate_in", "no_gate_out", "taps_reversed",
          "bias_in_weights", "not_normalized", "no_head_norm",
          "norm_after_rope", "experts_in_layer_1")


def faulty(plain, fault: str) -> list:
    """``[(attribute of the plain reference's module, what to set it to),
    ...]`` for the fault of this name."""
    if fault == "experts_in_layer_1":
        stack = plain.stack_params

        def lent(config, params):
            stacked = stack(config, params)
            stacked["blocks"][1] = dict(stacked["blocks"][1],
                                        moe=stacked["blocks"][2]["moe"])
            return stacked
        return [("_is_moe", lambda config, i: i >= 1),
                ("stack_params", lent)]
    hooks = {
        "conv_silu": ("_conv_activation", jax.nn.silu),
        "no_gate_in": ("_gate_in", lambda b, u: u),
        "no_gate_out": ("_gate_out", lambda c, mixed: mixed),
        "taps_reversed": ("_taps", lambda w: w[:, ::-1]),
        "bias_in_weights": ("_pick_weights",
                            lambda scores, bias: scores + bias),
        "not_normalized": ("_normalized", lambda vals: vals),
        "no_head_norm": ("_head_norm", lambda x, w, eps: x),
        "norm_after_rope": ("_norm_then_rope", False)}
    if fault not in hooks:
        raise SystemExit(f"LFM2MOE_FAULT must be one of {FAULTS}, got "
                         f"{fault!r}")
    return [hooks[fault]]


_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "lfm2_moe.py"))
# this load of the plain module, no other
for _name, _value in faulty(_plain, os.environ.get("LFM2MOE_FAULT", "")):
    setattr(_plain, _name, _value)
forward = _plain.forward
stack_params = _plain.stack_params
