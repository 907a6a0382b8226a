"""A CONTROL, not the reference: the plain Kimi Linear reference
(chipbench/reference/kimi_linear.py) with ONE fault planted in its
mathematics, named by the environment variable ``KIMILINEAR_FAULT``:

- ``mean_decay``: the forget gate's log decay taken as its mean over a head's
  channels: a Gated DeltaNet (one decay a head) standing in for KDA;
- ``roped_k_pe``: ``q_pe`` and ``k_pe`` rotated (rotate-half by halves,
  ``rope_theta``), as the benchmark's other latent layers are, where
  ``mla_use_nope`` says they are not;
- ``silu_gate``: the output norm gated by SiLU, Gated DeltaNet's activation,
  where KDA's is a sigmoid.

``BENCHMARK.json`` beside this directory runs the cell
``serve-kimilinear-reason`` as it is and finds THIS file first where the
configuration names its reference, so::

    KIMILINEAR_FAULT=mean_decay python3 -m chipbench.run --benchmark \
        chipbench/tests/fixture/fault_control_kimilinear/BENCHMARK.json \
        --workload serve-kimilinear-reason --seed <n> --seconds 30 --trace 0

judges the program's tokens by ANOTHER model's mathematics, and has to end
``"correct": false`` (PERF.md section 6, PR 40): the cell's ``logit_tol``
sees each of the three.  tests/test_kimi_linear.py plants the same three in
the float32 reference at a small size.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from chipbench import spec

FAULTS = ("mean_decay", "roped_k_pe", "silu_gate")


def faulty(plain, fault: str) -> tuple:
    """``(attribute of the plain reference's module, what to set it to)``
    for the fault of this name."""
    if fault == "mean_decay":
        gates = plain.kda_gates

        def mean_gates(config, p, h):
            g, beta = gates(config, p, h)
            return jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta
        return "kda_gates", mean_gates
    if fault == "roped_k_pe":
        def rope(config, x):
            """x (B, T, H, rope), positions 0..T-1, rotate-half."""
            t, d = x.shape[1], x.shape[-1]
            inv = float(config["rope_theta"]) ** (
                -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
            ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
            cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
            sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
            x1, x2 = x[..., :d // 2], x[..., d // 2:]
            return x * cos + jnp.concatenate([-x2, x1], -1) * sin
        return "_positions", rope
    if fault == "silu_gate":
        return "_output_gate", jax.nn.silu
    raise SystemExit(f"KIMILINEAR_FAULT must be one of {FAULTS}, got "
                     f"{fault!r}")


_plain = spec.load_module(os.path.join(spec.ROOT, "chipbench", "reference",
                                       "kimi_linear.py"))
# this load of the plain module, no other
setattr(_plain, *faulty(_plain, os.environ.get("KIMILINEAR_FAULT", "")))
forward = _plain.forward
stack_params = _plain.stack_params
