"""The data-parallel step's sharded weight update (``parallel/ddp.py``): a
replica holds 1/world of each divided parameter leaf and of its moments along
``shard_axis``; a step all-gathers the parameters, reduce-scatters each
gradient leaf and updates the 1/world it holds.

Every numeric case is held against ``shard_optimizer=False`` — whole,
replicated updates — at the tolerances the slow tier's ``TestZero1`` uses.
The small models lie under ``SHARD_MIN_ELEMENTS``, so those tests lower the
constant (steered here, never by an option of the program); the ConvNet and
the GPT-2 medium shapes are read at the constant as it stands.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import tpu_dist.dist as dist
from tpu_dist import checkpoint, nn, optim
from tpu_dist.models import ConvNet, TransformerLM
from tpu_dist.parallel import DDP, ddp as ddp_mod
from tpu_dist.parallel.ddp import SHARD_MIN_ELEMENTS, shard_axis


@pytest.fixture
def pg():
    if dist.is_initialized():
        dist.destroy_process_group()
    pg = dist.init_process_group()
    if pg.size() < 2:
        pytest.skip("needs a multi-device mesh")
    yield pg
    if dist.is_initialized():
        dist.destroy_process_group()


@pytest.fixture
def small_cut(monkeypatch):
    """Leaves of 256 elements and more are sharded: the MLP's two weights."""
    monkeypatch.setattr(ddp_mod, "SHARD_MIN_ELEMENTS", 256)


def _mlp():
    # weights 64 x 48 and 48 x 10 (8 divides the first axis of both), two
    # biases that stay whole
    return nn.Sequential(nn.Flatten(), nn.Linear(64, 48), nn.ReLU(),
                         nn.Linear(48, 10))


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(n, 8, 8)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 10, n)))


def _mk(pg, opt=None, **kw):
    opt = opt or optim.SGD(lr=0.05, momentum=0.9)
    return DDP(_mlp(), optimizer=opt, loss_fn=nn.CrossEntropyLoss(),
               group=pg, donate=False, **kw)


def _close(a, b, rtol=2e-4, atol=2e-6):
    jax.tree.map(lambda u, v: np.testing.assert_allclose(
        np.asarray(u), np.asarray(v), rtol=rtol, atol=atol), a, b)


OPTIMIZERS = {
    "adamw": lambda: optim.AdamW(lr=1e-3),
    "sgd_momentum": lambda: optim.SGD(lr=0.05, momentum=0.9),
    "sgd_scheduled": lambda: optim.SGD(
        lr=optim.step_lr(0.05, step_size=2), momentum=0.9),
}
OPTIONS = {
    "plain": {},
    "accum2": {"accum_steps": 2},
    "comm_bf16": {"comm_dtype": jnp.bfloat16},
    "compute_bf16": {"compute_dtype": jnp.bfloat16},
}


class TestMatchesWholeUpdate:
    @pytest.mark.parametrize("options", list(OPTIONS))
    @pytest.mark.parametrize("opt", list(OPTIMIZERS))
    def test_three_steps(self, pg, small_cut, opt, options):
        """Default (sharded by group size) against the whole update, same
        options on both sides: the quarter's result is the whole's."""
        x, y = _batch()
        kw = OPTIONS[options]
        sharded = _mk(pg, OPTIMIZERS[opt](), **kw)
        whole = _mk(pg, OPTIMIZERS[opt](), shard_optimizer=False, **kw)
        assert sharded.update_plan()["sharded_leaves"] == 2
        assert whole.update_plan()["sharded_leaves"] == 0
        ss, sw = sharded.init(seed=0), whole.init(seed=0)
        for _ in range(3):
            ss, ms = sharded.train_step(ss, x, y)
            sw, mw = whole.train_step(sw, x, y)
        np.testing.assert_allclose(float(ms["loss"]), float(mw["loss"]),
                                   rtol=1e-5)
        _close(ss.params, sw.params)
        if "step" in ss.opt_state:
            assert int(ss.opt_state["step"]) == 3

    def test_true_means_the_per_leaf_path(self, pg, small_cut):
        """``True`` stays accepted and is the default's path: no flat
        vector, the moments shaped as their parameters."""
        x, y = _batch()
        a, b = _mk(pg, shard_optimizer=True), _mk(pg)
        assert a.update_plan() == b.update_plan()
        sa, _ = a.train_step(a.init(seed=0), x, y)
        sb, _ = b.train_step(b.init(seed=0), x, y)
        _close(sa.params, sb.params, rtol=0, atol=0)
        assert (jax.tree.structure(sa.opt_state["momentum"])
                == jax.tree.structure(sa.params))

    def test_chunk_and_repeat_build_on_the_same_step(self, pg, small_cut):
        x, y = _batch()
        seq, chk, rep = _mk(pg), _mk(pg), _mk(pg)
        st = seq.init(seed=0)
        for _ in range(2):
            st, _ = seq.train_step(st, x, y)
        sc, _ = chk.train_chunk(chk.init(seed=0), jnp.stack([x, x]),
                                jnp.stack([y, y]))
        sr, _ = rep.train_repeat(rep.init(seed=0), x, y, 2)
        _close(st.params, sc.params, rtol=1e-5, atol=1e-7)
        _close(st.params, sr.params, rtol=1e-5, atol=1e-7)

    def test_convnet_at_the_constant_as_it_stands(self, pg):
        """The reference's MNIST ConvNet: one leaf of eight passes the
        constant, ``conv3.weight`` ``(3, 3, 64, 128)``, divided along its
        THIRD axis (3 is odd); the rest keeps the whole update."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(16, 28, 28, 1)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, 10, 16))
        mk = lambda **kw: DDP(
            ConvNet(), optimizer=optim.SGD(lr=0.05, momentum=0.9),
            loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False, **kw)
        sharded, whole = mk(), mk(shard_optimizer=False)
        plan = sharded.update_plan()
        assert (plan["sharded_leaves"], plan["whole_leaves"]) == (1, 7)
        assert plan["sharded_elements"] == 3 * 3 * 64 * 128
        ss, sw = sharded.init(seed=0), whole.init(seed=0)
        for tree in (ss.params, ss.opt_state["momentum"]):
            assert (tree["conv3"]["weight"].sharding.spec
                    == P(None, None, pg.axis_name))
        assert all(l.sharding.spec == P() for l in jax.tree.leaves(sw))
        for _ in range(2):
            ss, _ = sharded.train_step(ss, x, y)
            sw, _ = whole.train_step(sw, x, y)
        _close(ss.params, sw.params, atol=1e-6)


class TestHeldParametersElsewhere:
    def test_eval_and_forward_take_the_held_parameters(self, pg, small_cut):
        """``eval_step`` and ``forward`` want replicated parameters: handed
        the held ones they give what the whole ones give."""
        x, y = _batch()
        sharded, whole = _mk(pg), _mk(pg, shard_optimizer=False)
        ss, _ = sharded.train_step(sharded.init(seed=0), x, y)
        sw, _ = whole.train_step(whole.init(seed=0), x, y)
        es, ew = sharded.eval_step(ss, x, y), whole.eval_step(sw, x, y)
        np.testing.assert_allclose(float(es["loss"]), float(ew["loss"]),
                                   rtol=1e-5)
        assert int(es["correct"]) == int(ew["correct"])
        np.testing.assert_allclose(np.asarray(sharded.forward(ss, x)),
                                   np.asarray(whole.forward(sw, x)),
                                   rtol=1e-4, atol=1e-5)


class TestShardAxis:
    @pytest.mark.parametrize("shape,want", [
        ((50257, 1024), 1),      # the token table: 50257 is odd
        ((50257,), None),        # the head's bias: no axis
        ((1024, 4096), 0),
        ((4096, 1024), 0),
        ((1024, 3072), 0),
        ((1024, 50257), 0),      # the untied head
        ((1024, 1024), 0),       # positions, attention's output
        ((4096,), None),         # under the constant
        ((1024,), None),
    ])
    def test_gpt2_medium_leaves_at_four(self, shape, want):
        assert shard_axis(shape, 4) == want

    def test_the_constants_two_sides(self):
        assert SHARD_MIN_ELEMENTS == 1 << 16
        assert shard_axis((256, 256), 4) == 0          # 65,536: sharded
        assert shard_axis((255, 256), 4) is None       # 65,280: whole
        assert shard_axis((3, 65536), 4) == 1
        assert shard_axis((65537,), 4) is None         # large, no axis

    @pytest.mark.parametrize("n", [0, 1])
    def test_a_group_of_one_shards_nothing(self, n):
        assert shard_axis((1024, 4096), n) is None

    def test_gpt2_medium_plan(self):
        """294 leaves: 99 matrices hold 99.91% of 406,336,593 elements and
        each has an axis 4 divides; 195 vectors stay whole."""
        model = TransformerLM(vocab_size=50257, dim=1024, depth=24,
                              num_heads=16, max_seq_len=1024)
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        leaves = jax.tree.leaves(shapes)
        sharded = [l for l in leaves if shard_axis(l.shape, 4) is not None]
        whole = [l for l in leaves if shard_axis(l.shape, 4) is None]
        assert (len(leaves), len(sharded), len(whole)) == (294, 99, 195)
        assert sum(l.size for l in leaves) == 406_336_593
        assert sum(l.size for l in whole) == 371_793
        assert max(l.size for l in whole) == 50257
        assert min(l.size for l in sharded) == 1 << 20


class TestPlacement:
    def test_state_sharded_along_the_leafs_axis(self, pg, small_cut):
        """A divided parameter and its moments are HELD as 1/world along the
        same axis, before and after a step; scalar counters and small
        leaves replicate; read on the host a held leaf is whole."""
        d = _mk(pg, optim.AdamW(lr=1e-3))
        s = d.init(seed=0)
        x, y = _batch()
        s2, _ = d.train_step(s, x, y)
        for state in (s, s2):
            for tree in (state.params, state.opt_state["m"],
                         state.opt_state["v"]):
                w = tree["1"]["weight"]
                assert w.shape == (64, 48)
                assert w.sharding.spec == P(pg.axis_name)
                assert (w.sharding.shard_shape(w.shape)
                        == (w.shape[0] // pg.size(), w.shape[1]))
                assert tree["1"]["bias"].sharding.spec == P()
            assert state.opt_state["step"].sharding.spec == P()
            assert state.step.sharding.spec == P()
            assert np.asarray(state.params["1"]["weight"]).shape == (64, 48)
        # the step's in/out placement is state_shardings', leaf for leaf
        want = d.state_shardings(s2)
        jax.tree.map(lambda l, sh: l.sharding.is_equivalent_to(sh, l.ndim)
                     or pytest.fail(f"{l.sharding} != {sh}"), s2, want)

    def test_update_plan_counts(self, pg, small_cut):
        d = _mk(pg)
        assert d.update_plan() == {
            "world": pg.size(), "sharded_leaves": 2, "whole_leaves": 2,
            "sharded_elements": 64 * 48 + 48 * 10, "whole_elements": 48 + 10}
        off = _mk(pg, shard_optimizer=False).update_plan()
        assert off["sharded_elements"] == 0 and off["whole_leaves"] == 4
        # the same facts after a build, from the state's own shapes
        x, y = _batch()
        d.train_step(d.init(seed=0), x, y)
        assert d.update_plan()["sharded_leaves"] == 2

    def test_moments_saved_sharded_restore_and_resume(self, tmp_path, pg,
                                                      small_cut):
        """A state saved under the default restores through
        ``state_shardings`` to the same placement, and one more step from it
        equals the uninterrupted run."""
        x, y = _batch()
        d = _mk(pg, optim.AdamW(lr=1e-3))
        state = d.init(seed=0)
        for _ in range(2):
            state, _ = d.train_step(state, x, y)
        checkpoint.save(str(tmp_path), state, step=2)
        restored = checkpoint.restore(str(tmp_path), state,
                                      sharding=d.state_shardings(state))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), state, restored)
        jax.tree.map(lambda a, b: a.sharding.is_equivalent_to(
            b.sharding, a.ndim) or pytest.fail(f"{a.sharding} {b.sharding}"),
            state, restored)
        for tree in (restored.params, restored.opt_state["m"]):
            assert tree["1"]["weight"].sharding.spec == P(pg.axis_name)
        on, m_on = d.train_step(state, x, y)
        back, m_back = d.train_step(restored, x, y)
        assert float(m_on["loss"]) == float(m_back["loss"])
        _close(on.params, back.params, rtol=0, atol=0)


class TestLowering:
    def test_a_group_of_one_lowers_to_the_whole_updates_text(self):
        """``n == 1`` keeps the program as it was: whatever
        ``shard_optimizer`` says, the lowered step is the whole update's,
        with no slice, scatter or gather of a parameter in it."""
        if dist.is_initialized():
            dist.destroy_process_group()
        dist.init_process_group()
        try:
            one = dist.new_group(ranks=[0])
            x, y = _batch(8)
            texts = []
            for flag in (False, None, True):
                d = DDP(_mlp(), optimizer=optim.AdamW(lr=1e-3),
                        loss_fn=nn.CrossEntropyLoss(), group=one,
                        donate=False, shard_optimizer=flag)
                st = d.init(seed=0)
                texts.append(d._build_train_step(st).lower(st, x, y)
                             .as_text())
                assert d.update_plan()["sharded_leaves"] == 0
            assert texts[0] == texts[1] == texts[2]
            for op in ("reduce_scatter", "all_gather", "dynamic_slice"):
                assert op not in texts[1], op
        finally:
            dist.destroy_process_group()

    def test_sharded_leaves_ride_scatter_and_gather(self, pg, small_cut):
        """In the lowered step a divided leaf's parameter is an all_gather
        and its gradient a reduce_scatter; what is left to all_reduce is
        the whole leaves and the metrics."""
        x, y = _batch()
        d = _mk(pg)
        st = d.init(seed=0)
        text = d._build_train_step(st).lower(st, x, y).as_text()
        assert len(re.findall(r"stablehlo\.reduce_scatter", text)) == 2
        assert len(re.findall(r"stablehlo\.all_gather", text)) == 2
        whole = _mk(pg, shard_optimizer=False)
        sw = whole.init(seed=0)
        text_w = whole._build_train_step(sw).lower(sw, x, y).as_text()
        assert "reduce_scatter" not in text_w and "all_gather" not in text_w
        assert (len(re.findall(r"stablehlo\.all_reduce", text_w))
                - len(re.findall(r"stablehlo\.all_reduce", text))) == 2
