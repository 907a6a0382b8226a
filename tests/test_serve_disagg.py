"""tpu_dist.serve disaggregated prefill/decode serving (ISSUE 17).

The load-bearing contracts:

- **KV wire**: per-layer CRC-sealed fragments over the p2p data plane —
  exact-dtype rows round-trip bitwise; the lossy ``int8_block`` wire is
  an opt-in; every drift (shape, layer count, deadline) is a NAMED
  ``KVTransferError``, never a silent reshape.
- **Prefix cache**: content-verified token-block chains — a forced hash
  collision degrades to a verified MISS (cached KV never serves another
  prompt); eviction under the byte cap pages cold entries to the spill
  tier and a paged-then-restored hit is BITWISE-equal to the inserted
  rows; the spill index survives a cache restart.
- **Decode engine**: a missed KV arrival re-dispatches the descriptor
  ONCE, then fails the request by name (no unbounded waits).
- **Scheduler**: a sweep-time engine death (where the sharded leader's
  liveness probe raises) takes the cause-naming fatal path, not a silent
  loop-thread death.
- **Smoke gate** (tier-1): disaggregated greedy tokens — prefix-cache
  hits included — token-identical to offline ``generate()``.

The real-process SIGKILL e2e (prefill rank death under load) is in the
slow tier, like the sharded chaos cells; everything above keeps the
contracts tier-1-covered in-process.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import serve
from tpu_dist.models import TransformerLM
from tpu_dist.serve import (DisaggError, DisaggSlotEngine, KVTransfer,
                            KVTransferError, PrefixCache, Request,
                            kv_template)

pytestmark = pytest.mark.serve

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def store():
    from tpu_dist.dist.store import TCPStore
    s = TCPStore(is_master=True)
    yield s
    s.close()


def _mk_rows(T, layers=2, heads=2, hd=4, seed=0):
    """A per-layer batch-1 KV row tree, float32 — the host-side shape
    ``TransformerLM.prefill_rows`` hands the transfer layer: time LAST
    (``nn.cache.time_axis``)."""
    rng = np.random.default_rng(seed)
    return {f"blocks/{j}": {k: rng.standard_normal(
        (1, heads, hd, T)).astype(np.float32) for k in ("k", "v")}
        for j in range(layers)}


def _mk_int8_rows(T, layers=2, heads=2, hd=4, seed=0):
    """The int8-slot-cache row shape: already-quantized int8 k/v with
    float32 per-(token, head) scales riding as sibling keys — exactly
    what ``prefill_rows(..., dtype=jnp.int8)`` produces."""
    rng = np.random.default_rng(seed)
    return {f"blocks/{j}": {
        "k": rng.integers(-127, 128, (1, heads, hd, T)).astype(np.int8),
        "v": rng.integers(-127, 128, (1, heads, hd, T)).astype(np.int8),
        "k_scale": rng.random((1, heads, T)).astype(np.float32),
        "v_scale": rng.random((1, heads, T)).astype(np.float32)}
        for j in range(layers)}


# ---------------------------------------------------------------------------
# KV transfer wire
# ---------------------------------------------------------------------------


class TestKVTransfer:
    def _pair(self, store, wire=None, recv_template=None):
        from tpu_dist.collectives.transport import DataPlane
        dp0, dp1 = DataPlane(store, 0, 2), DataPlane(store, 1, 2)
        template = kv_template(_mk_rows(8))
        kv0 = KVTransfer(dp0, template, wire=wire)
        kv1 = KVTransfer(dp1, recv_template or template, wire=wire)
        return dp0, dp1, kv0, kv1

    def test_round_trip_exact_bitwise(self, store):
        dp0, dp1, kv0, kv1 = self._pair(store)
        try:
            rows = _mk_rows(12, seed=3)
            err = []

            def send():
                try:
                    kv0.send(1, 7, rows, length=10, first_tok=42,
                             prefix_hit=4, prefill_ns=1234)
                except Exception as e:     # surfaces in the assert below
                    err.append(e)
            t = threading.Thread(target=send)
            t.start()
            got = kv1.fetch(0, 7, 30.0)
            t.join(30)
            assert not err, err
            assert got["length"] == 10 and got["first_tok"] == 42
            assert got["prefix_hit"] == 4 and got["prefill_ns"] == 1234
            for path in rows:
                for k in ("k", "v"):
                    # only the TRUE length columns travel, bit-exact
                    np.testing.assert_array_equal(
                        got["rows"][path][k], rows[path][k][..., :10])
            assert kv1.fetched_bytes == got["bytes"] > 0
        finally:
            dp0.close(), dp1.close()

    def test_int8_block_wire_lossy_optin(self, store):
        dp0, dp1, kv0, kv1 = self._pair(store, wire="int8_block32")
        try:
            rows = _mk_rows(16, seed=5)
            t = threading.Thread(
                target=lambda: kv0.send(1, 9, rows, 16, 1))
            t.start()
            got = kv1.fetch(0, 9, 30.0)
            t.join(30)
            for path in rows:
                for k in ("k", "v"):
                    a, b = got["rows"][path][k], rows[path][k]
                    assert a.shape == b.shape and a.dtype == b.dtype
                    # block-quantized: close, NOT bitwise (the opt-in
                    # that excludes this wire from the parity smoke)
                    assert np.max(np.abs(a - b)) < 0.1
                    assert not np.array_equal(a, b)
            # ~4x fewer payload bytes than the exact wire would ship
            exact = sum(r[k][..., :16].nbytes for r in rows.values()
                        for k in r)
            assert kv1.fetched_bytes < exact / 2
        finally:
            dp0.close(), dp1.close()

    def test_int8_cache_rows_exact_on_quant_wire(self, store):
        # an int8 SLOT cache's rows on the lossy wire: the int8 k/v
        # fragments are ALREADY quantized and ship bit-exact
        # (re-quantizing integer data would be pure loss); only their
        # float scale fragments ride the int8_block wire
        from tpu_dist.collectives.transport import DataPlane
        dp0, dp1 = DataPlane(store, 0, 2), DataPlane(store, 1, 2)
        template = kv_template(_mk_int8_rows(8))
        kv0 = KVTransfer(dp0, template, wire="int8_block32")
        kv1 = KVTransfer(dp1, template, wire="int8_block32")
        try:
            rows = _mk_int8_rows(16, seed=11)
            err = []

            def send():
                try:
                    kv0.send(1, 21, rows, 16, 3)
                except Exception as e:
                    err.append(e)
            t = threading.Thread(target=send)
            t.start()
            got = kv1.fetch(0, 21, 30.0)
            t.join(30)
            assert not err, err
            for path in rows:
                for k in ("k", "v"):
                    a = got["rows"][path][k]
                    assert a.dtype == np.int8
                    np.testing.assert_array_equal(a, rows[path][k])
                for k in ("k_scale", "v_scale"):
                    a, b = got["rows"][path][k], rows[path][k]
                    assert a.dtype == np.float32
                    assert np.max(np.abs(a - b)) < 0.1
                    assert not np.array_equal(a, b)   # the lossy opt-in
        finally:
            dp0.close(), dp1.close()

    def test_int8_cache_rows_round_trip_exact_wire(self, store):
        # and on the default exact wire the whole mixed tree — int8
        # k/v AND f32 scales — round-trips bitwise
        from tpu_dist.collectives.transport import DataPlane
        dp0, dp1 = DataPlane(store, 0, 2), DataPlane(store, 1, 2)
        template = kv_template(_mk_int8_rows(8))
        kv0, kv1 = KVTransfer(dp0, template), KVTransfer(dp1, template)
        try:
            rows = _mk_int8_rows(12, seed=13)
            t = threading.Thread(
                target=lambda: kv0.send(1, 23, rows, 10, 5))
            t.start()
            got = kv1.fetch(0, 23, 30.0)
            t.join(30)
            for path in rows:
                for k in ("k", "v", "k_scale", "v_scale"):
                    np.testing.assert_array_equal(
                        got["rows"][path][k], rows[path][k][..., :10])
        finally:
            dp0.close(), dp1.close()

    def test_bad_wire_spec_named(self, store):
        from tpu_dist.collectives.transport import DataPlane
        dp = DataPlane(store, 1, 2)     # no peer needed: ctor-time check
        try:
            with pytest.raises(KVTransferError, match="int8_block"):
                KVTransfer(dp, kv_template(_mk_rows(8)), wire="gzip")
        finally:
            dp.close()

    def test_sender_shape_drift_named(self, store):
        dp0, dp1, kv0, kv1 = self._pair(store)
        try:
            bad = _mk_rows(8, hd=6)     # head_dim drifted vs template
            with pytest.raises(KVTransferError,
                               match="models disagree"):
                kv0.send(1, 11, bad, 8, 0)
        finally:
            dp0.close(), dp1.close()

    def test_layer_count_drift_named(self, store):
        # receiver's model has 2 layers, sender ships 3 → named error
        # from the meta frame, before any fragment is interpreted
        dp0, dp1, kv0, kv1 = self._pair(
            store, recv_template=kv_template(_mk_rows(8)))
        kv0 = KVTransfer(kv0.dp, kv_template(_mk_rows(8, layers=3)))
        try:
            rows = _mk_rows(8, layers=3)
            t = threading.Thread(
                target=lambda: kv0.send(1, 13, rows, 8, 0))
            t.start()
            with pytest.raises(KVTransferError,
                               match="layer layout drift"):
                kv1.fetch(0, 13, 30.0)
            t.join(30)
        finally:
            dp0.close(), dp1.close()

    def test_fetch_deadline_names_request_and_peer(self, store):
        dp0, dp1, kv0, kv1 = self._pair(store)
        try:
            with pytest.raises(KVTransferError,
                               match=r"kv fetch 99.*rank 0"):
                kv1.fetch(0, 99, 0.3)
        finally:
            dp0.close(), dp1.close()


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------


class TestPrefixCache:
    def test_hit_is_bitwise_and_capped_below_prompt(self):
        pc = PrefixCache(block_tokens=4)
        prompt = np.arange(10, 26, dtype=np.int32)      # 16 tokens
        rows = _mk_rows(16, seed=1)
        assert pc.insert(prompt, rows, 16) == 4
        # full-prompt match: capped at len-1 so one token still prefills
        hit, got = pc.match(prompt)
        assert hit == 12
        for path in rows:
            for k in ("k", "v"):
                np.testing.assert_array_equal(got[path][k],
                                              rows[path][k][..., :12])
        # longer prompt sharing the prefix: the whole 16 cached tokens
        hit, got = pc.match(np.concatenate([prompt, [7, 8, 9]]))
        assert hit == 16
        np.testing.assert_array_equal(got["blocks/0"]["k"],
                                      rows["blocks/0"]["k"])
        assert pc.stats()["tokens_saved"] == 28

    def test_forced_collision_is_verified_miss(self, monkeypatch):
        pc = PrefixCache(block_tokens=4)
        # chain keys collapse to the prefix LENGTH: two different
        # prompts now collide at every level by construction
        monkeypatch.setattr(pc, "_key_for",
                            lambda tokens: f"len{len(tokens)}")
        a = np.arange(1, 9, dtype=np.int32)
        b = np.arange(101, 109, dtype=np.int32)
        pc.insert(a, _mk_rows(8, seed=2), 8)
        hit, got = pc.match(np.concatenate([b, [5]]))
        # same key, different tokens: a verified MISS — prompt b never
        # sees prompt a's KV rows
        assert (hit, got) == (0, None)
        assert pc.collisions == 1 and pc.hits == 0
        # ...and the colliding insert does not clobber a's entry
        pc.insert(b, _mk_rows(8, seed=3), 8)
        hit, got = pc.match(np.concatenate([a, [5]]))
        assert hit == 8
        np.testing.assert_array_equal(
            got["blocks/0"]["k"], _mk_rows(8, seed=2)["blocks/0"]["k"])

    def test_eviction_under_byte_cap_without_spill(self):
        # one level = 2 layers x k/v x (1,4,2,4) f32 = 512 bytes
        pc = PrefixCache(block_tokens=4, capacity_bytes=600)
        a = np.arange(1, 9, dtype=np.int32)
        pc.insert(a, _mk_rows(8, seed=4), 8)            # 2 levels = 1024B
        assert pc.evicted >= 1
        assert pc.resident_bytes() <= 600

    def test_spill_page_out_restore_bitwise(self, tmp_path):
        pc = PrefixCache(block_tokens=4, capacity_bytes=600,
                         spill_dir=str(tmp_path))
        a = np.arange(1, 9, dtype=np.int32)
        rows = _mk_rows(8, seed=6)
        pc.insert(a, rows, 8)
        assert pc.paged_out >= 1 and pc.evicted == 0
        assert pc.resident_bytes() <= 600
        hit, got = pc.match(np.concatenate([a, [3]]))
        assert hit == 8 and pc.paged_in >= 1
        for path in rows:
            for k in ("k", "v"):
                # paged through npz + fragment range-reads: BITWISE
                np.testing.assert_array_equal(got[path][k],
                                              rows[path][k])

    def test_spill_index_survives_restart(self, tmp_path):
        pc = PrefixCache(block_tokens=4, capacity_bytes=600,
                         spill_dir=str(tmp_path))
        a = np.arange(1, 9, dtype=np.int32)
        rows = _mk_rows(8, seed=8)
        pc.insert(a, rows, 8)
        paged = pc.paged_out
        assert paged >= 1
        pc.close()

        pc2 = PrefixCache(block_tokens=4, capacity_bytes=600,
                          spill_dir=str(tmp_path))
        assert len(pc2._entries) == paged   # paged entries reloaded
        hit, got = pc2.match(np.concatenate([a, [3]]))
        # the restarted cache serves its paged entries WITHOUT
        # recomputing them — level 2 was never spilled, so the hit is
        # the reloaded level-1 block, bitwise
        assert hit == 4 and pc2.paged_in == 1
        np.testing.assert_array_equal(got["blocks/0"]["k"],
                                      rows["blocks/0"]["k"][..., :4])
        # a different block size re-keys every chain: stale spill ignored
        pc3 = PrefixCache(block_tokens=8, spill_dir=str(tmp_path))
        assert len(pc3._entries) == 0


# ---------------------------------------------------------------------------
# decode engine / role graph units
# ---------------------------------------------------------------------------


class _StubDispatch:
    """Accepts every descriptor (the queue channel, minus the wire)."""

    def __init__(self):
        self.put_count = 0

    def put(self, desc, timeout=None):
        self.put_count += 1


class _StubArrive:
    """An arrival envelope channel nobody ever publishes on."""

    def get(self, timeout=None):
        time.sleep(min(timeout or 0.1, 0.1))
        raise TimeoutError("empty")


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=61, dim=24, depth=2, num_heads=2,
                          max_seq_len=64)
    params = model.init(jax.random.key(0))
    return model, params


class TestDisaggEngine:
    def test_stage_timeout_redispatches_once_then_names_request(self, lm):
        model, params = lm
        eng = DisaggSlotEngine(
            model, params, kv=SimpleNamespace(fetched_bytes=0),
            dispatch_ch=_StubDispatch(), arrive_ch=_StubArrive(),
            num_slots=2, max_len=64, kv_timeout=0.3, rank=1)
        try:
            req = Request(np.arange(1, 7, dtype=np.int32), 4)
            eng.dispatch({"id": int(req.id), "prompt": req.prompt.tolist(),
                          "dst": 1, "dst_rr": 0})
            t0 = time.monotonic()
            with pytest.raises(KVTransferError,
                               match=rf"request {req.id}.*no KV arrival"
                                     r".*after one re-dispatch"):
                eng.stage(req)
            # bounded: one deadline + exactly one re-dispatched deadline
            assert 0.5 < time.monotonic() - t0 < 5.0
            assert eng.redispatches == 1
        finally:
            eng.close()

    def test_cancelled_request_stops_waiting_by_name(self, lm):
        model, params = lm
        eng = DisaggSlotEngine(
            model, params, kv=SimpleNamespace(fetched_bytes=0),
            dispatch_ch=_StubDispatch(), arrive_ch=_StubArrive(),
            num_slots=2, max_len=64, kv_timeout=30.0, rank=1)
        try:
            req = Request(np.arange(1, 7, dtype=np.int32), 4)
            eng.dispatch({"id": int(req.id)})
            threading.Timer(0.2, req.cancel).start()
            with pytest.raises(KVTransferError,
                               match="cancelled/expired"):
                eng.stage(req)
        finally:
            eng.close()

    def test_int8_slot_cache_pool_carries_scales(self, lm):
        # the int8 slot cache is a first-class disagg citizen: the
        # engine builds, its pool holds int8 k/v plus the f32
        # per-(token, head) scales, and kv_template lists every
        # fragment so the scales travel like ordinary rows
        model, params = lm
        eng = DisaggSlotEngine(model, params,
                               kv=SimpleNamespace(fetched_bytes=0),
                               dispatch_ch=_StubDispatch(),
                               arrive_ch=_StubArrive(),
                               num_slots=2, max_len=64,
                               cache_dtype=jnp.int8, rank=1)
        try:
            entry = next(iter(eng.cache.values()))
            assert entry["k"].dtype == jnp.int8
            assert entry["k_scale"].dtype == jnp.float32
            tpl = kv_template(model.init_slot_cache(1, 64, jnp.int8))
            assert set(next(iter(tpl.values()))) == {
                "k", "v", "k_scale", "v_scale"}
        finally:
            eng.close()

    def test_disagg_graph_shape(self):
        g = serve.disagg_graph(2, 3)
        assert [(r.name, r.world) for r in g.roles] == \
            [("prefill", 2), ("decode", 3)]
        names = {c.name for c in g.channels}
        assert names == {"prefill-q", "kv0", "kv1", "kv2"}
        with pytest.raises(DisaggError, match="prefill:0"):
            serve.disagg_graph(0, 1)


class TestSchedulerSweepFatal:
    def test_sweep_death_takes_cause_naming_fatal_path(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2, max_len=64)
        boom = RuntimeError("probe hit a dead follower")
        engine.sweep_expired = lambda: (_ for _ in ()).throw(boom)
        sched = serve.Scheduler(engine)
        try:
            # the loop dies at its first sweep boundary; whether the
            # submit races in before or after, it terminates BOUNDED
            # with the cause named — never a silent zombie loop
            with pytest.raises(Exception) as ei:
                sched.submit(list(range(2, 8)), max_new_tokens=4,
                             timeout=10.0).wait_done(timeout=30.0)
            assert "dead follower" in str(ei.value)
            assert sched.fatal is boom
        finally:
            sched.close()


# ---------------------------------------------------------------------------
# sharded idle-liveness probe (the satellite on tpu_dist.serve.sharded)
# ---------------------------------------------------------------------------


class TestShardedIdleProbe:
    def test_follower_ping_plan_is_noop(self):
        from tpu_dist.serve.sharded import ShardFollower
        f = SimpleNamespace(plans_applied=0)
        assert ShardFollower.apply_plan(f, {"op": "ping"}) is not False
        assert f.plans_applied == 1

    def _leader_stub(self, world=2, idle_for=10.0):
        from tpu_dist.serve.sharded import ShardedSlotEngine
        pings = []
        stub = SimpleNamespace(
            decoder=SimpleNamespace(world=world), _poisoned=None,
            _closed_plan_sent=False,
            _last_plan=time.monotonic() - idle_for,
            _bcast=lambda plan: pings.append(plan))
        return stub, pings, ShardedSlotEngine

    def test_idle_leader_pings_after_probe_interval(self, monkeypatch):
        monkeypatch.setenv("TPU_DIST_SERVE_PROBE", "0.5")
        stub, pings, eng = self._leader_stub(idle_for=10.0)
        eng._probe_followers(stub)
        assert pings == [{"op": "ping"}]

    def test_busy_or_disabled_probe_stays_quiet(self, monkeypatch):
        monkeypatch.setenv("TPU_DIST_SERVE_PROBE", "5.0")
        stub, pings, eng = self._leader_stub(idle_for=0.0)  # plan just sent
        eng._probe_followers(stub)
        assert pings == []
        monkeypatch.setenv("TPU_DIST_SERVE_PROBE", "0")     # disabled
        stub, pings, eng = self._leader_stub(idle_for=100.0)
        eng._probe_followers(stub)
        assert pings == []
        stub, pings, eng = self._leader_stub(world=1)       # no followers
        monkeypatch.setenv("TPU_DIST_SERVE_PROBE", "0.1")
        eng._probe_followers(stub)
        assert pings == []


# ---------------------------------------------------------------------------
# the tier-1 smoke gate: disagg greedy decode == offline generate()
# ---------------------------------------------------------------------------


def test_bench_serve_disagg_smoke():
    """In-process (a second jax import would bust the tier-1 budget):
    the full submit→dispatch→prefill→transfer→inject→decode path over
    real channels + data planes, prefix-cache hits included, asserted
    token-identical to offline ``generate()`` inside run_disagg."""
    sys.path.insert(0, _REPO)
    from benchmarks import bench_serve
    row = bench_serve.run_disagg(smoke=True, write_json=False)
    assert row["tokens_ok"] is True
    assert row["transfers"] == row["requests"] == 5
    assert row["prefix_hits"] >= 2


def test_int8_disagg_parity_vs_offline_generate(lm):
    """int8 slot cache end-to-end through the disaggregated stack:
    greedy tokens with ``cache_dtype=int8`` — prefill forward, quantized
    rows + scales over the KV wire, slot scatter, quantized decode — are
    token-identical to offline ``generate(cache_dtype=int8)``, which
    runs the same per-(token, head) quantized-cache math in one
    process."""
    sys.path.insert(0, _REPO)
    from benchmarks import bench_serve
    model, params = lm
    rig = bench_serve._DisaggRig(model, params, max_len=64, slots=2,
                                 cache_dtype=jnp.int8)
    try:
        reqs = [(np.arange(2, 10, dtype=np.int32), 5),
                (np.arange(11, 31, dtype=np.int32), 4)]
        refs = bench_serve._offline_refs(model, params, reqs,
                                         cache_dtype=jnp.int8)
        for i, (p, g) in enumerate(reqs):
            out = rig.sched.submit(
                p, max_new_tokens=g,
                timeout=60.0).wait_done(timeout=600.0)
            assert out == refs[i], (
                f"int8 disagg request {i} diverged from offline int8 "
                f"generate(): {out} vs {refs[i]}")
        assert rig.engine.stats()["kv"]["transfers"] == len(reqs)
    finally:
        rig.close()


# ---------------------------------------------------------------------------
# chaos e2e: SIGKILL the prefill rank under load (slow tier)
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TPU_DIST_CHAOS", None)
    return env


def _tiny_ref(prompt, n):
    model = TransformerLM(vocab_size=503, dim=64, depth=2, num_heads=2,
                          max_seq_len=192)
    params = model.init(jax.random.key(0))
    out = model.generate(params, jnp.asarray(prompt)[None, :], n)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.mark.chaos
@pytest.mark.multiprocess
@pytest.mark.slow
class TestDisaggChaosE2E:
    """ISSUE 17 chaos acceptance: SIGKILL the prefill rank of a
    prefill:1,decode:1 graph under load.  In-flight transfers terminate
    bounded with a NAMED error (or complete via the one re-dispatch
    after the solo restart); the restarted prefill rank re-attaches and
    the SAME client connection reproduces pre-kill tokens exactly."""

    def test_prefill_rank_sigkill_redispatch_and_recover(self, tmp_path):
        serve_port = _free_port()
        pid_file = str(tmp_path / "worker.pid")
        log = open(tmp_path / "launcher.log", "w")
        launcher = subprocess.Popen(
            [sys.executable, "-m", "tpu_dist.launch", "--standalone",
             "--max_restarts", "3",
             "--serve", "--serve_port", str(serve_port),
             "--roles", "prefill:1,decode:1",
             os.path.join(_REPO, "examples", "serve_lm.py"),
             "--tiny", "--disagg", "--pid-file", pid_file,
             "--run-seconds", "600"],
            env=_env(), cwd=_REPO, stdout=log, stderr=log)
        cli = None
        try:
            cli = serve.ServeClient("127.0.0.1", serve_port,
                                    connect_retry=180.0)
            probe = list(range(3, 10))
            ref = cli.submit(probe, max_new_tokens=8).wait_done(300.0)
            assert ref == _tiny_ref(probe, 8)

            inflight = [cli.submit(list(range(2, 8 + i)),
                                   max_new_tokens=150) for i in range(4)]
            next(iter(inflight[0].iter_tokens(timeout=120.0)))
            # prefill spans ranks [0, P): rank 0 IS the prefill rank,
            # so its pid file carries no .rN suffix
            with open(pid_file) as f:
                victim = int(f.read().strip())
            os.kill(victim, signal.SIGKILL)

            outcomes = {"done": 0, "named": 0}
            for h in inflight:
                try:
                    h.wait_done(timeout=240.0)  # BOUNDED: no hangs
                    outcomes["done"] += 1
                except serve.RequestFailedError as e:
                    # already-transferred requests decode to completion;
                    # ones waiting on the dead rank fail by name —
                    # KVTransferError (deadline / transfer plane), the
                    # channel's peer-death, or the gateway's view of a
                    # worker that chose to exit
                    assert e.error in (
                        "KVTransferError", "ChannelPeerGoneError",
                        "PeerGoneError", "BackendGoneError",
                        "BackendUnavailableError",
                        "SchedulerClosedError"), e
                    outcomes["named"] += 1
            assert outcomes["done"] + outcomes["named"] == len(inflight)

            # solo restart: the SAME client reproduces pre-kill tokens
            # once the restarted prefill rank re-attaches by name
            deadline = time.monotonic() + 300
            got = None
            while time.monotonic() < deadline:
                try:
                    got = cli.submit(probe,
                                     max_new_tokens=8).wait_done(120.0)
                    break
                except serve.RequestFailedError:
                    time.sleep(1.0)
            assert got == ref, f"post-restart output diverged: {got}"
        finally:
            if cli is not None:
                cli.close()
            if launcher.poll() is None:
                launcher.send_signal(signal.SIGINT)
                try:
                    launcher.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    launcher.kill()
                    launcher.wait()
            log.close()
            for suffix in ("", ".r1"):
                try:
                    with open(pid_file + suffix) as f:
                        os.kill(int(f.read().strip()), signal.SIGKILL)
                except (OSError, ValueError):
                    pass
