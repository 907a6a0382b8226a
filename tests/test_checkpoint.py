"""Checkpoint save/restore (SURVEY.md §5 extension)."""

import os

import jax
import numpy as np
import pytest

import tpu_dist.dist as dist
from tpu_dist import checkpoint, nn, optim
from tpu_dist.models import ConvNet
from tpu_dist.parallel import DDP
# compile-heavy file: excluded from the fast tier (`pytest -m "not slow"`)
pytestmark = pytest.mark.slow



@pytest.fixture
def pg():
    if dist.is_initialized():
        dist.destroy_process_group()
    pg = dist.init_process_group()
    yield pg
    if dist.is_initialized():
        dist.destroy_process_group()


def test_roundtrip_trainstate(tmp_path, pg):
    ddp = DDP(ConvNet(), optimizer=optim.SGD(lr=0.1, momentum=0.9),
              loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False)
    state = ddp.init(seed=0)
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(16, 28, 28, 1)), np.float32)
    y = rng.integers(0, 10, 16)
    state, _ = ddp.train_step(state, x, y)

    path = checkpoint.save(str(tmp_path), state, step=1,
                           metadata={"note": "after one step"})
    assert os.path.isdir(path)
    restored = checkpoint.restore(str(tmp_path), state)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)

    # resume training from restored state must continue identically
    s_a, m_a = ddp.train_step(state, x, y)
    s_b, m_b = ddp.train_step(restored, x, y)
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=1e-6)


def test_latest_and_keep(tmp_path):
    tree = {"w": np.arange(4.0)}
    for s in (1, 5, 3):
        checkpoint.save(str(tmp_path), tree, step=s)
    assert checkpoint.all_steps(str(tmp_path)) == [1, 3, 5]
    assert checkpoint.latest_step(str(tmp_path)) == 5
    checkpoint.save(str(tmp_path), tree, step=7, keep=2)
    assert checkpoint.all_steps(str(tmp_path)) == [5, 7]


def test_restore_specific_step(tmp_path):
    for s in (1, 2):
        checkpoint.save(str(tmp_path), {"w": np.full(3, float(s))}, step=s)
    out = checkpoint.restore(str(tmp_path), {"w": np.zeros(3)}, step=1)
    np.testing.assert_array_equal(out["w"], np.ones(3))


def test_empty_root_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no checkpoints"):
        checkpoint.restore(str(tmp_path / "none"), {"w": np.zeros(2)})


def test_structure_mismatch_raises(tmp_path):
    checkpoint.save(str(tmp_path), {"w": np.zeros(3)}, step=1)
    with pytest.raises(ValueError, match="does not match template"):
        checkpoint.restore(str(tmp_path), {"w": np.zeros(3),
                                           "b": np.zeros(1)})


def test_shape_mismatch_raises(tmp_path):
    checkpoint.save(str(tmp_path), {"w": np.zeros(3)}, step=1)
    with pytest.raises(ValueError, match="shape"):
        checkpoint.restore(str(tmp_path), {"w": np.zeros(4)})


def test_metadata_written(tmp_path):
    import json
    p = checkpoint.save(str(tmp_path), {"w": np.zeros(1)}, step=9,
                        metadata={"epoch": 3})
    with open(os.path.join(p, "tree.json")) as f:
        meta = json.load(f)
    assert meta["metadata"] == {"epoch": 3}
    assert meta["step"] == 9


def test_dtype_mismatch_raises(tmp_path):
    checkpoint.save(str(tmp_path), {"w": np.zeros(3, np.float32)}, step=1)
    with pytest.raises(ValueError, match="dtype"):
        checkpoint.restore(str(tmp_path), {"w": np.zeros(3, np.int32)})


def test_sharding_pytree(tmp_path, pg):
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": np.arange(8.0), "b": np.arange(4.0)}
    checkpoint.save(str(tmp_path), tree, step=0)
    repl = NamedSharding(pg.mesh, P())
    row = NamedSharding(pg.mesh, P("data"))
    out = checkpoint.restore(str(tmp_path), tree,
                             sharding={"w": row, "b": repl})
    assert out["w"].sharding == row and out["b"].sharding == repl
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


def test_sharded_restore(tmp_path, pg):
    from jax.sharding import NamedSharding, PartitionSpec as P
    tree = {"w": np.arange(8.0)}
    checkpoint.save(str(tmp_path), tree, step=0)
    sh = NamedSharding(pg.mesh, P())
    out = checkpoint.restore(str(tmp_path), tree, sharding=sh)
    assert out["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


def test_zero1_sharded_opt_state_roundtrip(tmp_path, pg):
    """VERDICT r1 weak #5: a shard_optimizer=True (ZeRO-1) TrainState — whose
    opt_state holds its large leaf 1/world a device along that leaf's
    shard_axis — must save, restore with its placement (via
    state_shardings), and resume training identically."""
    from jax.sharding import PartitionSpec as P

    ddp = DDP(ConvNet(), optimizer=optim.SGD(lr=0.1, momentum=0.9),
              loss_fn=nn.CrossEntropyLoss(), group=pg, donate=False,
              shard_optimizer=True)
    state = ddp.init(seed=0)
    rng = np.random.default_rng(0)
    x = np.asarray(rng.normal(size=(16, 28, 28, 1)), np.float32)
    y = rng.integers(0, 10, 16)
    state, _ = ddp.train_step(state, x, y)

    # sanity: the opt_state really is sharded over the data axis, along
    # the third axis of ConvNet's one leaf past ddp.SHARD_MIN_ELEMENTS
    big = lambda st: st.opt_state["momentum"]["conv3"]["weight"]
    assert big(state).sharding.spec == P(None, None, pg.axis_name)

    checkpoint.save(str(tmp_path), state, step=1)
    restored = checkpoint.restore(str(tmp_path), state,
                                  sharding=ddp.state_shardings(state))

    # values identical...
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)
    # ...and the ZeRO-1 placement survived the round trip
    assert big(restored).sharding.spec == P(None, None, pg.axis_name)
    p_leaf = jax.tree.leaves(restored.params)[0]
    assert p_leaf.sharding.spec == P()         # a bias: stays replicated
    assert (restored.params["conv3"]["weight"].sharding.spec
            == P(None, None, pg.axis_name))

    # resume: both continue to the same numbers
    _, m_a = ddp.train_step(state, x, y)
    _, m_b = ddp.train_step(restored, x, y)
    np.testing.assert_allclose(float(m_a["loss"]), float(m_b["loss"]),
                               rtol=1e-6)


class TestAsyncCheckpointer:
    def test_roundtrip_and_interchange(self, tmp_path):
        """Async-written checkpoints restore via the plain restore()."""
        import time
        from tpu_dist.checkpoint import AsyncCheckpointer, restore, all_steps

        tree = {"w": np.arange(12.0, dtype=np.float32).reshape(3, 4),
                "b": np.ones(4, np.float32)}
        with AsyncCheckpointer(str(tmp_path), keep=2) as ckpt:
            for s in (1, 2, 3):
                ckpt.save({"w": tree["w"] + s, "b": tree["b"]}, step=s)
        assert all_steps(str(tmp_path)) == [2, 3]  # keep=2 pruned step 1
        got = restore(str(tmp_path), template=tree, step=3)
        np.testing.assert_array_equal(got["w"], tree["w"] + 3)

    def test_snapshot_isolated_from_later_mutation(self, tmp_path):
        """The host copy is taken at save() time: mutating the source
        arrays after save returns must not corrupt the write."""
        from tpu_dist.checkpoint import AsyncCheckpointer, restore

        arr = np.zeros(8, np.float32)
        with AsyncCheckpointer(str(tmp_path)) as ckpt:
            ckpt.save({"a": arr}, step=0)
            arr += 999.0  # mutate AFTER the (possibly pending) save
        got = restore(str(tmp_path), template={"a": arr}, step=0)
        np.testing.assert_array_equal(got["a"], np.zeros(8, np.float32))

    def test_error_surfaces_on_wait(self, tmp_path):
        from tpu_dist.checkpoint import AsyncCheckpointer

        blocker = tmp_path / "root"
        blocker.write_text("not a directory")  # makedirs will fail
        ckpt = AsyncCheckpointer(str(blocker))
        ckpt.save({"a": np.ones(2, np.float32)}, step=0)
        with pytest.raises(Exception):
            ckpt.wait()
        ckpt.close()

    def test_closed_raises(self, tmp_path):
        from tpu_dist.checkpoint import AsyncCheckpointer

        ckpt = AsyncCheckpointer(str(tmp_path))
        ckpt.close()
        with pytest.raises(RuntimeError, match="closed"):
            ckpt.save({"a": np.ones(2, np.float32)}, step=0)

    def test_snapshot_isolated_from_donation(self, tmp_path):
        """CPU-backend jax Arrays are zero-copy views under np.asarray;
        the async snapshot must copy them or in-place buffer reuse
        (donation) tears the pending write."""
        import jax.numpy as jnp
        from tpu_dist.checkpoint import AsyncCheckpointer, restore

        a = jnp.zeros(1024, jnp.float32)
        with AsyncCheckpointer(str(tmp_path)) as ckpt:
            ckpt.save({"a": a}, step=0)
            # donation-style reuse: delete + overwrite likely reuses the
            # buffer; the saved bytes must remain the zeros snapshot
            jitted = jax.jit(lambda x: x + 7.0, donate_argnums=0)
            a = jitted(a)
            jax.block_until_ready(a)
        got = restore(str(tmp_path), template={"a": np.zeros(1024,
                                                            np.float32)},
                      step=0)
        np.testing.assert_array_equal(got["a"], np.zeros(1024, np.float32))


class TestExampleResume:
    def test_example_mp_checkpoint_and_resume(self, tmp_path):
        """examples/example_mp.py --checkpoint-dir/--resume round-trip:
        train, checkpoint, resume from the latest step."""
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        base = [sys.executable, os.path.join(repo, "examples/example_mp.py"),
                "--backend", "cpu", "--synthetic", "--epochs", "1",
                "--batch-size", "32", "--checkpoint-dir", str(tmp_path)]
        r1 = subprocess.run(base + ["--max-steps", "3",
                                    "--checkpoint-every", "2"],
                            env=env, capture_output=True, text=True,
                            timeout=300)
        assert r1.returncode == 0, r1.stderr
        assert sorted(os.listdir(tmp_path)) == ["step_00000002",
                                                "step_00000003"]
        r2 = subprocess.run(base + ["--max-steps", "2", "--resume"],
                            env=env, capture_output=True, text=True,
                            timeout=300)
        assert r2.returncode == 0, r2.stderr
        assert "resumed from step 3" in r2.stdout
        # resumed run checkpointed past the restored step
        assert "step_00000005" in os.listdir(tmp_path)


class TestGracefulShutdown:
    def test_flag_set_and_handlers_restored(self):
        import os
        import signal

        from tpu_dist.checkpoint import GracefulShutdown

        before = signal.getsignal(signal.SIGTERM)
        with GracefulShutdown() as stop:
            assert not stop.requested
            os.kill(os.getpid(), signal.SIGTERM)
            assert stop.requested and stop.signum == signal.SIGTERM
        assert signal.getsignal(signal.SIGTERM) is before

    def test_sigterm_mid_training_saves_then_resume(self, tmp_path):
        """Preemption flow end to end: child trains, gets SIGTERM, writes
        a final checkpoint and exits 0; the parent restores it."""
        import signal
        import subprocess
        import sys
        import time

        from tpu_dist import checkpoint

        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        script = f"""
import sys, time
sys.path.insert(0, {repo!r})
import numpy as np
from tpu_dist import checkpoint

state = {{"w": np.zeros((4,), np.float32)}}
with checkpoint.GracefulShutdown() as stop:
    print("ready", flush=True)
    for step in range(10_000):
        state["w"] = state["w"] + 1.0   # the "train step"
        time.sleep(0.01)
        if stop.requested:
            checkpoint.save({str(tmp_path)!r}, state, step=step)
            print("saved", step, flush=True)
            sys.exit(0)
sys.exit(3)  # loop finished without the signal: test failure
"""
        proc = subprocess.Popen([sys.executable, "-c", script],
                                stdout=subprocess.PIPE, text=True)
        assert proc.stdout.readline().strip() == "ready"
        time.sleep(0.3)                      # let it take some steps
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
        assert proc.returncode == 0, out
        assert "saved" in out

        step = checkpoint.latest_step(str(tmp_path))
        assert step is not None
        got = checkpoint.restore(str(tmp_path),
                                 {"w": np.zeros((4,), np.float32)})
        # the checkpoint is self-consistent: w == step + 1 increments
        assert float(got["w"][0]) == float(step + 1)
