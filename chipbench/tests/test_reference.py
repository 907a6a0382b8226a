"""chipbench/reference/gpt2.py against the program at a tiny size, float32:
pins the mapping of the program's parameter names onto the reference.

On the chip the same comparison runs at the published widths outside the
timed window, at the tolerances the configuration files state with their
reasons (bf16 compute and bf16 weights there; reduction order only here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec

CFG = spec.load_json(spec.find({"paths": ["chipbench/tests/fixture"]},
                               "configs", "tiny-train.json"))
REF = spec.load_module(spec.find({"paths": ["chipbench"]}, "reference",
                                 CFG["reference"]))


@pytest.fixture(scope="module")
def program():
    model = spec.resolve(CFG["model"]["factory"])(**spec.model_kwargs(CFG))
    params = model.init(jax.random.key(3))
    # biases start at zero in the program; a wrong bias mapping must show
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape),
        params)
    return model, params


def test_layernorm_eps_is_the_published_one():
    from tpu_dist import nn
    assert nn.LayerNorm(8).eps == CFG["layer_norm_epsilon"]


def test_logits_match_the_program(program):
    model, params = program
    tokens = np.random.default_rng(0).integers(0, CFG["vocab_size"], (3, 40))
    with jax.default_matmul_precision("highest"):
        want = model.apply(params, jnp.asarray(tokens))
    got = REF.forward(CFG, REF.stack_params(CFG, params), jnp.asarray(tokens))
    assert got.dtype == jnp.float32 and got.shape == want.shape
    # same mathematics in float32: only the order of sums differs
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)


def test_loss_matches_the_program(program):
    from tpu_dist import nn
    model, params = program
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, CFG["vocab_size"], (2, 32)))
    y = jnp.asarray(rng.integers(0, CFG["vocab_size"], (2, 32)))
    want = nn.CrossEntropyLoss()(model.apply(params, x), y)
    got = REF.loss(CFG, REF.stack_params(CFG, params), x, y)
    assert float(got) == pytest.approx(float(want), abs=1e-5)


def test_served_tokens_are_the_reference_argmax(program):
    """Prefill and decode through the slot cache agree with the reference's
    full forward, token by token."""
    model, params = program
    prompt = np.random.default_rng(2).integers(0, CFG["vocab_size"], 9)
    out = np.asarray(jax.jit(model.generate, static_argnums=(2,))(
        params, jnp.asarray(prompt)[None], 6))[0]
    logits = REF.forward(CFG, REF.stack_params(CFG, params),
                         jnp.asarray(out)[None])[0]
    for j in range(6):
        row = logits[len(prompt) - 1 + j]
        assert float(row.max() - row[out[len(prompt) + j]]) <= 1e-4
