"""Process-group lifecycle + topology (c10d API parity, SURVEY.md §2 #7-8)."""

import os

import numpy as np
import pytest

import tpu_dist.dist as dist


@pytest.fixture(autouse=True)
def _clean_group():
    if dist.is_initialized():
        dist.destroy_process_group()
    yield
    if dist.is_initialized():
        dist.destroy_process_group()


class TestLifecycle:
    def test_init_and_destroy(self):
        pg = dist.init_process_group(backend="cpu")
        assert dist.is_initialized()
        assert pg is dist.get_default_group()
        dist.destroy_process_group()
        assert not dist.is_initialized()

    def test_double_init_raises(self):
        dist.init_process_group(backend="cpu")
        with pytest.raises(RuntimeError, match="already initialized"):
            dist.init_process_group(backend="cpu")

    def test_use_after_destroy_raises(self):
        pg = dist.init_process_group(backend="cpu")
        dist.destroy_process_group()
        with pytest.raises(RuntimeError, match="destroy"):
            _ = pg.mesh

    def test_uninitialized_get_raises(self):
        with pytest.raises(RuntimeError, match="not been initialized"):
            dist.get_world_size()

    def test_backend_aliases(self):
        pg = dist.init_process_group(backend="gloo")  # → cpu
        assert dist.get_backend(pg) == "cpu"
        dist.destroy_process_group()
        # nccl/xla/mpi → tpu (ref README:133): a demand this CPU mesh
        # cannot meet, refused by name instead of stamped "tpu"
        for alias in ("nccl", "mpi"):
            with pytest.raises(dist.BackendMismatchError):
                dist.init_process_group(backend=alias)
            assert not dist.is_initialized()

    def test_explicit_tpu_on_cpu_names_both_platforms(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        with pytest.raises(dist.BackendMismatchError) as ei:
            dist.init_process_group(backend="tpu")
        msg = str(ei.value)
        assert "'tpu'" in msg and "resolved 'cpu'" in msg
        assert "JAX_PLATFORMS='cpu'" in msg

    def test_bare_call_reports_what_jax_resolved(self):
        pg = dist.init_process_group()
        assert dist.get_backend() == "cpu"
        assert dist.get_backend(dist.new_group([0, 1])) == "cpu"
        assert pg.devices[0].platform == "cpu"

    def test_tpu_refused_when_host_chips_are_shared(self, monkeypatch):
        """One process per host drives all local chips: LOCAL_WORLD_SIZE > 1
        with a TPU backend is refused — for an explicit ask before JAX is
        touched, for the default once the platform is known."""
        import jax
        monkeypatch.setenv("LOCAL_WORLD_SIZE", "2")
        with pytest.raises(dist.BackendMismatchError,
                           match="one process per host"):
            dist.init_process_group(backend="tpu")

        class _Chip:
            platform = "tpu"

        monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
        with pytest.raises(dist.BackendMismatchError,
                           match="LOCAL_WORLD_SIZE=2"):
            dist.resolve_backend(None)
        monkeypatch.setenv("LOCAL_WORLD_SIZE", "1")
        assert dist.resolve_backend("nccl") == "tpu"

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="backend"):
            dist.init_process_group(backend="smoke-signals")


class TestTopology:
    def test_world_is_devices(self):
        import jax
        dist.init_process_group()
        assert dist.get_world_size() == len(jax.devices()) == 8
        assert dist.get_rank() == 0          # single process
        assert dist.get_num_processes() == 1
        assert dist.get_local_world_size() == 8

    def test_local_rank_env(self, monkeypatch):
        monkeypatch.setenv("LOCAL_RANK", "3")
        assert dist.get_local_rank() == 3
        monkeypatch.delenv("LOCAL_RANK")
        assert dist.get_local_rank() == 0

    def test_mesh_axis(self):
        pg = dist.init_process_group()
        assert pg.axis_name == "data"
        assert pg.mesh.devices.shape == (8,)

    def test_custom_mesh_shape(self):
        pg = dist.init_process_group(axis_names=("data", "model"),
                                     mesh_shape=(4, 2))
        assert pg.mesh.devices.shape == (4, 2)
        assert pg.axis_names == ("data", "model")

    def test_bad_mesh_shape_raises(self):
        with pytest.raises(ValueError, match="cover"):
            dist.init_process_group(axis_names=("data",), mesh_shape=(3,))

    def test_local_device_ranks(self):
        pg = dist.init_process_group()
        assert pg.local_device_ranks() == tuple(range(8))


class TestNewGroup:
    def test_subgroup(self):
        dist.init_process_group()
        sub = dist.new_group(ranks=[0, 2, 4, 6])
        assert sub.size() == 4
        assert dist.get_world_size(sub) == 4
        assert dist.get_world_size() == 8  # default untouched

    def test_subgroup_collective(self):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from tpu_dist import collectives as C

        dist.init_process_group()
        sub = dist.new_group(ranks=[0, 1, 2, 3])
        f = shard_map(lambda v: C.psum(v, sub.axis_name), mesh=sub.mesh,
                      in_specs=(P("data"),), out_specs=P("data"))
        out = jax.jit(f)(jnp.ones((4, 2)))
        np.testing.assert_allclose(np.asarray(out), np.full((4, 2), 4.0))


class TestBarrier:
    def test_single_process_noop(self):
        dist.init_process_group()
        dist.barrier()  # must not hang


class TestRendezvousParsing:
    def test_none_single_process(self):
        assert dist.parse_init_method(None) == (None, 1, 0)

    def test_env(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "29500")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("RANK", "2")
        assert dist.parse_init_method("env://") == ("10.0.0.1:29500", 4, 2)

    def test_env_explicit_override(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "29500")
        assert dist.parse_init_method("env://", world_size=8, rank=5) == \
            ("10.0.0.1:29500", 8, 5)

    def test_env_missing_raises(self, monkeypatch):
        monkeypatch.delenv("MASTER_ADDR", raising=False)
        with pytest.raises(ValueError, match="MASTER_ADDR"):
            dist.parse_init_method("env://")

    def test_env_missing_world_size_fails_fast(self, monkeypatch):
        # no silent degradation to N independent single-process worlds
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "29500")
        monkeypatch.delenv("WORLD_SIZE", raising=False)
        with pytest.raises(ValueError, match="WORLD_SIZE"):
            dist.parse_init_method("env://")

    def test_env_missing_rank_fails_fast(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "10.0.0.1")
        monkeypatch.setenv("MASTER_PORT", "29500")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.delenv("RANK", raising=False)
        with pytest.raises(ValueError, match="RANK"):
            dist.parse_init_method("env://")

    def test_tcp_url(self):
        # the reference's style: /root/reference/example_mp.py:18,37-42
        assert dist.parse_init_method("tcp://10.157.106.151:12345",
                                      world_size=16, rank=3) == \
            ("10.157.106.151:12345", 16, 3)

    def test_tcp_requires_world_and_rank(self):
        with pytest.raises(ValueError, match="world_size"):
            dist.parse_init_method("tcp://h:1")

    def test_bad_scheme_raises(self):
        with pytest.raises(ValueError, match="init_method"):
            dist.parse_init_method("carrier-pigeon://x")

    def test_none_with_launcher_env(self, monkeypatch):
        monkeypatch.setenv("MASTER_ADDR", "h")
        monkeypatch.setenv("MASTER_PORT", "1")
        monkeypatch.setenv("WORLD_SIZE", "2")
        monkeypatch.setenv("RANK", "1")
        assert dist.parse_init_method(None) == ("h:1", 2, 1)


class TestGetBackend:
    def test_backend_normalization_and_query(self):
        if dist.is_initialized():
            dist.destroy_process_group()
        dist.init_process_group(backend="gloo")  # alias -> cpu
        assert dist.get_backend() == "cpu"
        sub = dist.new_group(ranks=range(2))
        assert dist.get_backend(sub) == "cpu"  # subgroups inherit
        dist.destroy_process_group()
