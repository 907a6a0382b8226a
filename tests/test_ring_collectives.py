"""Ring collectives over the p2p data plane (ISSUE 2): numerics vs the
store path, transport behavior, threshold routing, and the benchmark smoke.

Tier-1 on purpose (``collectives`` marker, NOT ``slow``): the data plane is
now the hot path for large host payloads — including the chaos e2e's
gradient sync — so it must be proven on every PR.

The spawned workers use the same lightweight wiring as
benchmarks/bench_host_collectives.py: a TCPStore hosted by the test
process, worker processes that inject the store into the rendezvous module
and drive the eager collectives through a rank/num_processes shim — no
jax.distributed, so worlds 2–4 spawn in seconds on the CPU-only box.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = [pytest.mark.collectives, pytest.mark.multiprocess]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# in-process transport + ring units (two DataPlane endpoints, one process)
# ---------------------------------------------------------------------------

@pytest.fixture
def store():
    from tpu_dist.dist.store import TCPStore
    s = TCPStore(is_master=True)
    yield s
    s.close()


@pytest.fixture
def dp_pair(store):
    from tpu_dist.collectives.transport import DataPlane
    dp0 = DataPlane(store, 0, 2)
    dp1 = DataPlane(store, 1, 2)
    yield dp0, dp1
    dp0.close()
    dp1.close()


class TestTransport:
    def test_array_roundtrip_shapes_dtypes(self, dp_pair):
        dp0, dp1 = dp_pair
        import ml_dtypes
        for arr in (np.arange(12, dtype=np.int32).reshape(3, 4),
                    np.linspace(0, 1, 7, dtype=np.float32),
                    np.ones((2, 3, 2), dtype=ml_dtypes.bfloat16),
                    np.array([], dtype=np.float64),
                    np.array(3.5, dtype=np.float32)):
            dp0.send_array(1, "t", arr)
            got = dp1.recv_array(0, "t", timeout=30)
            assert got.dtype == arr.dtype and got.shape == arr.shape
            np.testing.assert_array_equal(np.asarray(got, np.float64),
                                          np.asarray(arr, np.float64))

    def test_fifo_order_per_tag_and_tag_isolation(self, dp_pair):
        dp0, dp1 = dp_pair
        for i in range(5):
            dp0.send_array(1, "a", np.array([i]))
        dp0.send_array(1, "b", np.array([99]))
        assert dp1.recv_array(0, "b", timeout=30)[0] == 99
        for i in range(5):
            assert dp1.recv_array(0, "a", timeout=30)[0] == i

    def test_recv_timeout_names_src_and_tag(self, dp_pair):
        dp0, dp1 = dp_pair
        with pytest.raises(TimeoutError, match="rank 0.*tag 'nothing'"):
            dp1.recv_array(0, "nothing", timeout=0.2)

    def test_try_recv_nonblocking(self, dp_pair):
        dp0, dp1 = dp_pair
        assert dp1.try_recv_array(0, "x") is None
        dp0.send_array(1, "x", np.array([7]))
        assert dp1.recv_array(0, "x", timeout=30)[0] == 7

    def test_send_to_self_rejected(self, dp_pair):
        dp0, _ = dp_pair
        with pytest.raises(ValueError, match="self"):
            dp0.send_array(0, "t", np.zeros(1))


class TestRingInProcess:
    """World-2/3 ring numerics without process spawns: one DataPlane per
    'rank', each driven by a thread."""

    def _run_world(self, store, n, fn):
        import threading
        from tpu_dist.collectives.transport import DataPlane
        dps = [DataPlane(store, r, n) for r in range(n)]
        out, errs = [None] * n, []

        def run(r):
            try:
                out[r] = fn(dps[r], r)
            except Exception as e:  # surface worker thread failures
                errs.append((r, e))

        threads = [threading.Thread(target=run, args=(r,)) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        for dp in dps:
            dp.close()
        assert not errs, errs
        return out

    @pytest.mark.parametrize("op,expect", [
        ("sum", lambda vals: np.sum(vals, axis=0)),
        ("avg", lambda vals: np.mean(vals, axis=0)),
        ("max", lambda vals: np.max(vals, axis=0)),
        ("min", lambda vals: np.min(vals, axis=0)),
    ])
    def test_all_reduce_ops_world3_uneven(self, store, op, expect):
        from tpu_dist.collectives import ring
        n = 3
        vals = [np.random.default_rng(r).standard_normal(1001)
                .astype(np.float32) for r in range(n)]  # 1001 % 3 != 0

        outs = self._run_world(
            store, n, lambda dp, r: ring.ring_all_reduce(dp, vals[r], op=op,
                                                         tag="t"))
        ref = expect(np.stack(vals))
        for r in range(n):
            np.testing.assert_allclose(outs[r], ref, rtol=2e-6, atol=1e-5)
            assert outs[r].dtype == ref.dtype
        # all ranks bit-identical (the chaos-resume determinism property)
        assert len({o.tobytes() for o in outs}) == 1

    def test_all_gather_and_broadcast_world2(self, store):
        from tpu_dist.collectives import ring
        vals = [np.arange(10, dtype=np.int32) * (r + 1) for r in range(2)]
        outs = self._run_world(
            store, 2, lambda dp, r: ring.ring_all_gather(dp, vals[r],
                                                         tag="g"))
        for o in outs:
            np.testing.assert_array_equal(o, np.stack(vals))
        outs = self._run_world(
            store, 2, lambda dp, r: ring.tree_broadcast(dp, vals[0] if r == 0
                                                        else np.zeros_like(
                                                            vals[0]),
                                                        src=0, tag="b"))
        for o in outs:
            np.testing.assert_array_equal(o, vals[0])

    def test_reduce_scatter_spans_world3(self, store):
        from tpu_dist.collectives import ring
        n = 3
        vals = [np.arange(8, dtype=np.float32) + r for r in range(n)]
        outs = self._run_world(
            store, n, lambda dp, r: ring.ring_reduce_scatter(dp, vals[r],
                                                             op="sum",
                                                             tag="rs"))
        full = np.sum(np.stack(vals), axis=0)
        for r in range(n):
            lo, hi = ring.ring_chunk_span(8, n, r)
            np.testing.assert_allclose(outs[r], full[lo:hi], rtol=1e-6)

    def test_comm_dtype_compression_consistent(self, store):
        from tpu_dist.collectives import ring
        vals = [np.random.default_rng(r).standard_normal(513)
                .astype(np.float32) for r in range(2)]
        outs = self._run_world(
            store, 2, lambda dp, r: ring.ring_all_reduce(
                dp, vals[r], op="sum", tag="c", comm_dtype="bfloat16"))
        ref = np.sum(np.stack(vals), axis=0)
        # lossy on the wire, but consistent across ranks...
        assert outs[0].tobytes() == outs[1].tobytes()
        # ...and within bf16 tolerance of the exact sum
        np.testing.assert_allclose(outs[0], ref, rtol=0.05, atol=0.1)


def test_chunk_bounds_uneven():
    from tpu_dist.collectives.ring import ring_chunk_span
    spans = [ring_chunk_span(10, 3, r) for r in range(3)]
    assert spans == [(0, 4), (4, 7), (7, 10)]
    assert [ring_chunk_span(2, 3, r) for r in range(3)] == \
        [(0, 1), (1, 2), (2, 2)]


# ---------------------------------------------------------------------------
# spawned-process coverage (worlds 2-4, eager routing, peer death)
# ---------------------------------------------------------------------------

_WORKER_PRELUDE = textwrap.dedent("""
    import hashlib, importlib, json, os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    rank = int(os.environ["RANK"]); world = int(os.environ["WORLD_SIZE"])
    from tpu_dist.dist.store import TCPStore
    host, _, port = os.environ["TPU_DIST_STORE_ADDR"].rpartition(":")
    store = TCPStore(host, int(port))
    rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
    rdzv._store = store

    class _Group:
        def __init__(self, rank, num_processes):
            self.rank, self.num_processes = rank, num_processes
    g = _Group(rank, world)
    from tpu_dist import collectives as C

    def on_ring():
        os.environ["TPU_DIST_DP_THRESHOLD"] = "0"
    def on_store():
        os.environ["TPU_DIST_DP_THRESHOLD"] = str(1 << 60)
""")

# every (op, dtype) pair compared ring-vs-store in the SAME worker run, on
# a payload size coprime with worlds 2-4 so no chunking is ever even
_NUMERICS_WORKER = _WORKER_PRELUDE + textwrap.dedent("""
    import ml_dtypes
    from tpu_dist.utils.metrics import (collective_counters,
                                        reset_collective_counters)
    reset_collective_counters()
    out = {"rank": rank, "digests": {}}
    f32 = (np.random.default_rng(100 + rank)
           .standard_normal(10007).astype(np.float32))
    bf16 = f32[:3001].astype(ml_dtypes.bfloat16)
    i32 = np.random.default_rng(200 + rank).integers(
        -1000, 1000, size=5003).astype(np.int32)

    for name, x, rtol, atol in (("f32", f32, 2e-6, 1e-5),
                                ("bf16", bf16, 0.05, 0.2),
                                ("i32", i32, 0, 0)):
        for op in ("sum", "avg", "max", "min"):
            on_ring(); got = C.all_reduce_host(x, group=g, op=op)
            on_store(); ref = C.all_reduce_host(x, group=g, op=op)
            assert got.dtype == ref.dtype, (name, op, got.dtype, ref.dtype)
            assert got.shape == ref.shape, (name, op, got.shape)
            if name == "i32" and op in ("sum", "max", "min"):
                np.testing.assert_array_equal(got, ref, err_msg=f"{name}/{op}")
            else:
                np.testing.assert_allclose(
                    np.asarray(got, np.float64), np.asarray(ref, np.float64),
                    rtol=rtol, atol=atol, err_msg=f"{name}/{op}")
            out["digests"][f"ar/{name}/{op}"] = hashlib.sha256(
                np.ascontiguousarray(got).tobytes()).hexdigest()

    # every 'ring' leg above ACTUALLY rode the data plane (this is what
    # catches a dtype-gate regression silently demoting e.g. bfloat16 —
    # whose numpy kind is 'V' — to a store-vs-store comparison)
    c = collective_counters()
    assert c["all_reduce/dataplane"]["calls"] == 12, c   # 3 dtypes x 4 ops
    assert c["all_reduce/store"]["calls"] == 12, c       # the reference legs

    # ring all-gather == store all-gather, exactly (no arithmetic)
    on_ring(); ag = C.all_gather_host(f32, group=g)
    on_store(); ag_ref = C.all_gather_host(f32, group=g)
    np.testing.assert_array_equal(ag, ag_ref)
    assert ag.shape == (world, 10007)

    # tree broadcast == store broadcast, exactly
    on_ring(); bc = C.broadcast_host(f32, group=g, src=world - 1)
    on_store(); bc_ref = C.broadcast_host(f32, group=g, src=world - 1)
    np.testing.assert_array_equal(bc, bc_ref)
    out["digests"]["bcast"] = hashlib.sha256(bc.tobytes()).hexdigest()

    # trees route per-leaf: big leaves ring, small leaves store, same result
    tree = {"w": f32, "b": np.float32(rank + 1.0)}
    os.environ["TPU_DIST_DP_THRESHOLD"] = "1024"
    mixed = C.all_reduce_host(tree, group=g, op="sum")
    on_store(); ref = C.all_reduce_host(tree, group=g, op="sum")
    np.testing.assert_allclose(mixed["w"], ref["w"], rtol=2e-6, atol=1e-5)
    np.testing.assert_allclose(mixed["b"], ref["b"])

    store.barrier(world, tag="done")
    with open(sys.argv[1] + f"/result{rank}.json", "w") as f:
        json.dump(out, f)
    store.close()
""")

_PEER_DEATH_WORKER = _WORKER_PRELUDE + textwrap.dedent("""
    on_ring()
    if rank == 1:
        C.send(np.arange(5000, dtype=np.float32), dst=0, group=g)
        store.close()
        sys.exit(0)   # dies with a message still owed to rank 0
    got = C.recv(src=1, group=g)
    assert got.shape == (5000,), got.shape

    from tpu_dist.collectives import transport
    dp = transport.get_data_plane(store, 0, 2)
    try:
        dp.recv_array(1, "never-sent", timeout=60)
        raise SystemExit("expected PeerGoneError, got a frame")
    except transport.PeerGoneError as e:
        assert "rank 1" in str(e), str(e)
    with open(sys.argv[1] + "/result0.json", "w") as f:
        json.dump({"ok": True, "error": "PeerGoneError"}, f)
    store.close()
""")

_THRESHOLD_WORKER = _WORKER_PRELUDE + textwrap.dedent("""
    from tpu_dist.utils.metrics import (collective_counters,
                                        reset_collective_counters)
    x = np.full(64, float(rank + 1), np.float32)   # 256 B: always "small"
    big = np.full(100_000, float(rank + 1), np.float32)

    os.environ["TPU_DIST_DP_THRESHOLD"] = str(64 * 1024)  # the default
    reset_collective_counters()
    out_small = C.all_reduce_host(x, group=g, op="sum")
    c = collective_counters()
    assert "all_reduce/store" in c and c["all_reduce/store"]["calls"] == 1, c
    assert "all_reduce/dataplane" not in c, c

    reset_collective_counters()
    out_big = C.all_reduce_host(big, group=g, op="sum")
    c = collective_counters()
    assert "all_reduce/dataplane" in c, c
    assert c["all_reduce/dataplane"]["bytes"] == big.nbytes, c
    assert "all_reduce/store" not in c, c

    total = sum(r + 1 for r in range(world))
    np.testing.assert_allclose(out_small, np.full(64, total, np.float32))
    np.testing.assert_allclose(out_big, np.full(100_000, total, np.float32))
    store.barrier(world, tag="done")
    with open(sys.argv[1] + f"/result{rank}.json", "w") as f:
        json.dump({"ok": True}, f)
    store.close()
""")


def _spawn_world(tmp_path, source, world, timeout=180):
    """Host a store, run ``source`` as `world` rank processes against it."""
    from tpu_dist.dist.store import TCPStore
    script = tmp_path / "worker.py"
    script.write_text(source)
    server = TCPStore(is_master=True)
    env = dict(os.environ,
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""),
               JAX_PLATFORMS="cpu",
               TPU_DIST_STORE_ADDR=f"127.0.0.1:{server.port}",
               WORLD_SIZE=str(world))
    env.pop("TPU_DIST_RESTART_COUNT", None)
    env.pop("TPU_DIST_DP_THRESHOLD", None)
    try:
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(tmp_path)],
            env=dict(env, RANK=str(r)), cwd=_REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for r in range(world)]
        outs = [p.communicate(timeout=timeout) for p in procs]
        rcs = [p.returncode for p in procs]
    finally:
        server.close()
    assert rcs == [0] * world, "\n\n".join(
        f"rank {r} rc={rc}\nstdout:\n{o}\nstderr:\n{e}"
        for r, (rc, (o, e)) in enumerate(zip(rcs, outs)) if rc != 0)
    return [json.loads((tmp_path / f"result{r}.json").read_text())
            if (tmp_path / f"result{r}.json").exists() else None
            for r in range(world)]


@pytest.mark.parametrize("world", [2, 3, 4])
def test_ring_numerics_vs_store_path(tmp_path, world):
    """sum/avg/max/min x float32/bfloat16/int32, payloads that never divide
    evenly, ring vs store results in the same run — and ring outputs
    bit-identical across all ranks."""
    res = _spawn_world(tmp_path, _NUMERICS_WORKER, world)
    digests = [r["digests"] for r in res]
    for key in digests[0]:
        assert len({d[key] for d in digests}) == 1, \
            f"{key} differs across ranks"


def test_peer_death_surfaces_named_error(tmp_path):
    """A rank that dies with frames owed must surface as PeerGoneError
    naming the rank — not a hang, not a raw socket errno."""
    res = _spawn_world(tmp_path, _PEER_DEATH_WORKER, 2)
    assert res[0] == {"ok": True, "error": "PeerGoneError"}


def test_threshold_routes_small_payloads_to_store(tmp_path):
    """Payloads under TPU_DIST_DP_THRESHOLD stay on the store transport
    (observed through the per-collective counters); big ones take the data
    plane.  Both produce the right numbers."""
    res = _spawn_world(tmp_path, _THRESHOLD_WORKER, 2)
    assert all(r == {"ok": True} for r in res)


# ---------------------------------------------------------------------------
# the benchmark's smoke mode IS a tier-1 test: the full store-vs-dataplane
# comparison (with numeric cross-check) runs on every PR
# ---------------------------------------------------------------------------

def test_bench_host_collectives_smoke():
    env = dict(os.environ,
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_host_collectives",
         "--smoke"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    by_path = {(row["op"], row["path"]): row["value"] for row in rows
               if row.get("metric") == "host_collective"}
    for op in ("all_reduce", "all_gather", "broadcast"):
        assert by_path[(op, "dataplane")] > 0
        assert by_path[(op, "store")] > 0
    # the checksum's cost is measured and printed on every PR: each rep
    # times the armed and the disarmed arm back to back over the same
    # 8 MiB on the same paced wire.  What it reads is a time on a shared
    # CPU under six workers (4.5% to 6.5% alone, against a threshold of
    # 5%), so it is reported and not judged; the checksum itself is held
    # by tests/test_netchaos.py
    crc = [row for row in rows
           if str(row.get("metric", "")).startswith("crc_overhead")]
    assert crc, "bench smoke emitted no crc_overhead summary"
    assert crc[0].get("estimator") == "paired-median", crc
    paired = [row for row in rows if row.get("metric") == "crc_paired"]
    assert len(paired) == 1, paired
    assert paired[0]["bytes"] == 8 << 20 and paired[0]["iters"] >= 1
    assert len(paired[0]["pair_pcts"]) == paired[0]["pairs"] \
        == crc[0]["pairs"] >= 1
    assert paired[0]["on_mb_s"] > 0 and paired[0]["off_mb_s"] > 0
    assert crc[0]["value"] == paired[0]["value"] >= 0
