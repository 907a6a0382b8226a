"""EMA parameter averaging (vs torch AveragedModel)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from tpu_dist import optim


class TestEMA:
    def test_matches_torch_averaged_model(self, rng):
        """Seeded shadow + debias=False reproduces torch's EMA avg_fn
        exactly (AveragedModel seeds its shadow with the first params)."""
        decay = 0.9
        w = rng.standard_normal((3, 2)).astype(np.float32)

        tmod = torch.nn.Linear(2, 3, bias=False)
        with torch.no_grad():
            tmod.weight.copy_(torch.tensor(w))
        from torch.optim.swa_utils import AveragedModel, get_ema_avg_fn
        avg = AveragedModel(tmod, avg_fn=get_ema_avg_fn(decay))
        avg.update_parameters(tmod)  # seeds shadow = w

        # debias=False init seeds shadow=params — AveragedModel's first
        # update_parameters call
        ema = optim.EMA(decay=decay, debias=False)
        state = ema.init({"w": jnp.asarray(w)})

        for _ in range(5):
            w2 = rng.standard_normal((3, 2)).astype(np.float32)
            with torch.no_grad():
                tmod.weight.copy_(torch.tensor(w2))
            avg.update_parameters(tmod)
            state = ema.update(state, {"w": jnp.asarray(w2)})

        want = next(avg.module.parameters()).detach().numpy()
        np.testing.assert_allclose(np.asarray(ema.params(state)["w"]), want,
                                   atol=1e-6)

    def test_exact_recurrence_and_debias(self, rng):
        decay = 0.99
        ema = optim.EMA(decay=decay)
        p = {"w": jnp.asarray(rng.standard_normal(4).astype(np.float32))}
        state = ema.init(p)
        shadow = np.zeros(4, np.float32)
        for i in range(10):
            v = rng.standard_normal(4).astype(np.float32)
            state = ema.update(state, {"w": jnp.asarray(v)})
            shadow = decay * shadow + (1 - decay) * v
            np.testing.assert_allclose(np.asarray(state["shadow"]["w"]),
                                       shadow, atol=1e-6)
            corrected = shadow / (1 - decay ** (i + 1))
            np.testing.assert_allclose(np.asarray(ema.params(state)["w"]),
                                       corrected, atol=1e-5)

    def test_constant_params_fixed_point(self):
        """Averaging a constant stream returns exactly that constant
        (debias makes this true from step 1)."""
        ema = optim.EMA(decay=0.999)
        p = {"w": jnp.full(3, 7.0)}
        state = ema.init(p)
        state = ema.update(state, p)
        # f32 rounding of (1-d) vs (1-d**t) costs ~1e-5 relative at d=0.999
        np.testing.assert_allclose(np.asarray(ema.params(state)["w"]), 7.0,
                                   rtol=1e-4)

    def test_validation(self):
        with pytest.raises(ValueError, match="decay"):
            optim.EMA(decay=1.0)

    def test_fuses_into_jit(self, rng):
        ema = optim.EMA(decay=0.9)
        p = {"w": jnp.ones(4)}
        state = ema.init(p)

        @jax.jit
        def step(state, p):
            return ema.update(state, p)

        s1 = step(state, p)
        assert int(s1["step"]) == 1
