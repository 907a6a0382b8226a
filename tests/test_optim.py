"""SGD parity vs torch.optim.SGD for every configuration the reference uses
(/root/reference/mpspawn_dist.py:64 plain lr; /root/reference/example_mp.py:84-90
momentum+nesterov+wd)."""

import numpy as np
import pytest
import torch

import jax.numpy as jnp

from tpu_dist.optim import SGD


@pytest.mark.parametrize("cfg", [
    dict(lr=1e-4),
    dict(lr=0.02, momentum=0.9),
    dict(lr=0.02, momentum=0.9, weight_decay=1e-4, nesterov=True),
])
def test_sgd_matches_torch(rng, cfg):
    w0 = rng.standard_normal((7, 3)).astype(np.float32)
    tparam = torch.nn.Parameter(torch.tensor(w0.copy()))
    topt = torch.optim.SGD([tparam], **cfg)

    opt = SGD(**cfg)
    params = {"w": jnp.asarray(w0)}
    opt_state = opt.init(params)

    for step in range(5):
        g = rng.standard_normal((7, 3)).astype(np.float32)
        tparam.grad = torch.tensor(g.copy())
        topt.step()
        params, opt_state = opt.update({"w": jnp.asarray(g)}, opt_state, params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tparam.detach().numpy(), atol=1e-5,
                                   err_msg=f"step {step} cfg {cfg}")


def test_nesterov_requires_momentum():
    with pytest.raises(ValueError):
        SGD(lr=0.1, nesterov=True)


@pytest.mark.parametrize("cls,tcls,cfg", [
    ("AdamW", torch.optim.AdamW, dict(lr=1e-3)),
    ("AdamW", torch.optim.AdamW, dict(lr=3e-4, betas=(0.85, 0.98),
                                      weight_decay=0.1)),
    ("Adam", torch.optim.Adam, dict(lr=1e-3)),
    ("Adam", torch.optim.Adam, dict(lr=1e-3, weight_decay=1e-2)),
])
def test_adam_family_matches_torch(rng, cls, tcls, cfg):
    from tpu_dist import optim

    w0 = rng.standard_normal((5, 4)).astype(np.float32)
    tparam = torch.nn.Parameter(torch.tensor(w0.copy()))
    topt = tcls([tparam], **cfg)

    opt = getattr(optim, cls)(**cfg)
    params = {"w": jnp.asarray(w0)}
    opt_state = opt.init(params)

    for step in range(6):
        g = rng.standard_normal((5, 4)).astype(np.float32)
        tparam.grad = torch.tensor(g.copy())
        topt.step()
        params, opt_state = opt.update({"w": jnp.asarray(g)}, opt_state,
                                       params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tparam.detach().numpy(), atol=2e-6,
                                   err_msg=f"step {step} {cls} {cfg}")


def test_clip_grad_norm_matches_torch(rng):
    from tpu_dist.optim import clip_grad_norm, global_norm

    gs = {"a": rng.standard_normal((6, 2)).astype(np.float32),
          "b": rng.standard_normal(11).astype(np.float32)}
    tparams = [torch.nn.Parameter(torch.zeros(6, 2)),
               torch.nn.Parameter(torch.zeros(11))]
    tparams[0].grad = torch.tensor(gs["a"].copy())
    tparams[1].grad = torch.tensor(gs["b"].copy())

    jgs = {k: jnp.asarray(v) for k, v in gs.items()}
    for max_norm in (0.5, 1e6):        # clipping active / inactive
        tnorm = torch.nn.utils.clip_grad_norm_(tparams, max_norm)
        clipped, norm = clip_grad_norm(jgs, max_norm)
        np.testing.assert_allclose(float(norm), float(tnorm), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   tparams[0].grad.numpy(), atol=1e-6)
        np.testing.assert_allclose(np.asarray(clipped["b"]),
                                   tparams[1].grad.numpy(), atol=1e-6)
        # reset torch grads for the next max_norm
        tparams[0].grad = torch.tensor(gs["a"].copy())
        tparams[1].grad = torch.tensor(gs["b"].copy())
        assert float(global_norm(jgs)) == pytest.approx(float(tnorm),
                                                        rel=1e-6)


def test_adamw_rejects_bad_hparams():
    from tpu_dist.optim import AdamW

    with pytest.raises(ValueError):
        AdamW(betas=(1.0, 0.999))
    with pytest.raises(ValueError):
        AdamW(eps=0.0)


@pytest.mark.parametrize("cls,tcls,cfg", [
    ("RMSprop", torch.optim.RMSprop, dict(lr=1e-2)),
    ("RMSprop", torch.optim.RMSprop, dict(lr=1e-2, momentum=0.9,
                                          weight_decay=1e-4)),
    ("RMSprop", torch.optim.RMSprop, dict(lr=1e-3, alpha=0.95,
                                          centered=True, momentum=0.5)),
    ("Adagrad", torch.optim.Adagrad, dict(lr=1e-2)),
    ("Adagrad", torch.optim.Adagrad, dict(lr=1e-2, lr_decay=0.1,
                                          weight_decay=1e-4)),
    ("Adagrad", torch.optim.Adagrad,
     dict(lr=1e-2, initial_accumulator_value=0.3)),
])
def test_rmsprop_adagrad_match_torch(rng, cls, tcls, cfg):
    from tpu_dist import optim

    w0 = rng.standard_normal((5, 4)).astype(np.float32)
    tparam = torch.nn.Parameter(torch.tensor(w0.copy()))
    topt = tcls([tparam], **cfg)

    opt = getattr(optim, cls)(**cfg)
    params = {"w": jnp.asarray(w0)}
    opt_state = opt.init(params)

    for step in range(6):
        g = rng.standard_normal((5, 4)).astype(np.float32)
        tparam.grad = torch.tensor(g.copy())
        topt.step()
        params, opt_state = opt.update({"w": jnp.asarray(g)}, opt_state,
                                       params)
        np.testing.assert_allclose(np.asarray(params["w"]),
                                   tparam.detach().numpy(), atol=2e-6,
                                   err_msg=f"step {step} {cls} {cfg}")


def test_rmsprop_adagrad_reject_bad_hparams():
    from tpu_dist.optim import Adagrad, RMSprop

    with pytest.raises(ValueError):
        RMSprop(alpha=1.0)
    with pytest.raises(ValueError):
        RMSprop(momentum=-0.1)
    with pytest.raises(ValueError):
        Adagrad(lr_decay=-1.0)
    with pytest.raises(ValueError):
        Adagrad(initial_accumulator_value=-0.5)


def test_memory_introspection_smoke():
    """torch.cuda.max_memory_allocated analogue: callable everywhere; on
    platforms with no allocator stats (CPU tests) it degrades to 0 instead
    of raising."""
    from tpu_dist import utils

    live = jnp.ones((256, 256))  # ensure at least one live device buffer
    live.block_until_ready()
    stats = utils.memory_stats()
    assert isinstance(stats, dict)
    peak = utils.max_memory_allocated()
    assert 0 <= peak
    if stats:  # a real accelerator: the live buffer must show up
        assert peak > 0
    del live
