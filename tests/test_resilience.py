"""Resilience layer units: chaos spec parsing/triggering, heartbeat
publisher + monitor (RankLostError within the deadline, generation scoping),
auto-resume TrainState round-trips, generation fencing, checkpoint
durability/verification, and the spawn supervisor.

Everything here runs on the CPU backend with sub-second deadlines — the
``chaos`` marker is tier-1 by design (pytest.ini): fault handling is only
real if it is exercised on every PR.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

from tpu_dist.dist.store import TCPStore
from tpu_dist.resilience import chaos
from tpu_dist.resilience.heartbeat import (Heartbeat, HeartbeatMonitor,
                                           RankLostError, hb_key)

pytestmark = pytest.mark.chaos


@pytest.fixture
def store():
    s = TCPStore(is_master=True)
    yield s
    s.close()


@pytest.fixture(autouse=True)
def _clean_chaos():
    yield
    chaos.uninstall()


# -- chaos spec ---------------------------------------------------------------

class TestChaosSpec:
    def test_parse_multi(self):
        faults = chaos.parse("kill:rank=1,step=5;"
                             "drop-store:rank=0,op=3;"
                             "delay-store:op=2,delay=0.25;"
                             "stall-heartbeat:rank=1,step=2")
        assert [f.kind for f in faults] == [
            "kill", "drop-store", "delay-store", "stall-heartbeat"]
        assert faults[0].rank == 1 and faults[0].step == 5
        assert faults[2].rank is None and faults[2].delay == 0.25

    @pytest.mark.parametrize("bad", [
        "nuke:step=1",            # unknown kind
        "kill",                   # missing step
        "drop-store:rank=0",      # missing op
        "delay-store:op=1",       # missing delay
        "kill:step=1,color=red",  # unknown param
        "kill:step",              # not key=value
        "",                       # empty
    ])
    def test_parse_rejects(self, bad):
        with pytest.raises(ValueError):
            chaos.parse(bad)

    def test_raise_fault_fires_at_exact_step_and_rank(self):
        c = chaos.Chaos(chaos.parse("raise:rank=0,step=3"), rank=0)
        for step in (0, 1, 2, 4):
            c.on_step(step)  # no fault
        with pytest.raises(chaos.ChaosError, match="rank 0 at step 3"):
            c.on_step(3)
        other = chaos.Chaos(chaos.parse("raise:rank=0,step=3"), rank=1)
        other.on_step(3)  # different rank: untouched

    def test_install_from_env_idempotent(self, monkeypatch):
        monkeypatch.setenv("TPU_DIST_CHAOS", "raise:step=9")
        c1 = chaos.install_from_env()
        c2 = chaos.install_from_env()
        assert c1 is c2  # op counters survive re-entry
        monkeypatch.delenv("TPU_DIST_CHAOS")
        assert chaos.install_from_env() is c1  # unset env keeps the active

    def test_stall_heartbeat_predicate(self):
        c = chaos.Chaos(chaos.parse("stall-heartbeat:rank=1,step=2"), rank=1)
        assert not c.heartbeat_stalled(1)
        assert c.heartbeat_stalled(2) and c.heartbeat_stalled(7)
        assert not c.heartbeat_stalled(None)
        assert not c.heartbeat_stalled(5, rank=0)


# -- heartbeat ----------------------------------------------------------------

class TestHeartbeat:
    def test_publisher_and_monitor_healthy(self, store):
        hbs = [Heartbeat(rank=r, store=store, interval=0.05,
                         generation=0).start() for r in range(2)]
        mon = HeartbeatMonitor(store, 2, timeout=0.5, generation=0,
                               startup_grace=0.5)
        try:
            deadline = time.monotonic() + 2
            while time.monotonic() < deadline:
                mon.check()  # never raises while both publish
                time.sleep(0.05)
        finally:
            for hb in hbs:
                hb.stop()  # fixture store passed in: stop() won't close it

    def test_stalled_rank_raises_named_within_deadline(self, store):
        hb0 = Heartbeat(rank=0, store=store, interval=0.05,
                        generation=0).start()
        hb1 = Heartbeat(rank=1, store=store, interval=0.05,
                        generation=0).start()
        hb1.set_step(4)
        mon = HeartbeatMonitor(store, 2, timeout=0.4, generation=0,
                               startup_grace=0.4)
        assert mon.poll() == []
        # rank 1 goes silent while its process stays "alive"
        hb1._stop.set()
        hb1._thread.join()
        t0 = time.monotonic()
        err = None
        while time.monotonic() - t0 < 3:
            try:
                mon.check()
            except RankLostError as e:
                err = e
                break
            time.sleep(0.05)
        for hb in (hb0, hb1):
            hb.stop()
        assert err is not None, "stalled rank never diagnosed"
        assert err.rank == 1
        assert err.last_step == 4 and err.pid == os.getpid()
        assert "rank 1" in str(err)
        assert time.monotonic() - t0 < 2, "diagnosis exceeded the deadline"

    def test_never_published_rank_lost_after_grace(self, store):
        mon = HeartbeatMonitor(store, 2, timeout=10.0, generation=0,
                               startup_grace=0.2)
        time.sleep(0.3)
        lost = mon.poll()
        assert [e.rank for e in lost] == [0, 1]
        assert "never published" in str(lost[0])

    def test_generation_scoping(self, store):
        # a publisher from generation 0 cannot satisfy a gen-1 monitor:
        # stale ranks of the previous incarnation look dead, not alive
        hb = Heartbeat(rank=0, store=store, interval=0.05,
                       generation=0).start()
        mon = HeartbeatMonitor(store, 1, timeout=10.0, generation=1,
                               startup_grace=0.2)
        time.sleep(0.3)
        lost = mon.poll()
        hb.stop()
        assert [e.rank for e in lost] == [0]

    def test_chaos_stall_blocks_publishing(self, store):
        chaos.install("stall-heartbeat:rank=3,step=2", rank=3)
        hb = Heartbeat(rank=3, store=store, interval=0.02, generation=0)
        hb.start()
        hb.set_step(1)
        assert store.check(hb_key(0, 3))
        payload_at_1 = store.get(hb_key(0, 3))
        hb.set_step(2)  # stalled from here on
        time.sleep(0.2)
        stalled_payload = store.get(hb_key(0, 3))
        hb.stop()
        assert stalled_payload == payload_at_1

    def test_progress_timeout_catches_hung_loop(self, store):
        # publisher keeps beating (alive) but step never advances — the
        # hung-collective shape a liveness-only watchdog cannot see
        hb = Heartbeat(rank=0, store=store, interval=0.02,
                       generation=0).start()
        hb.set_step(7)
        mon = HeartbeatMonitor(store, 1, timeout=30.0, generation=0,
                               startup_grace=30.0, progress_timeout=0.3)
        assert mon.poll() == []  # baseline poll records step 7
        time.sleep(0.5)
        lost = mon.poll()
        hb.stop()
        assert lost and lost[0].rank == 0
        assert "no step progress" in str(lost[0])

    def test_clean_stop_reads_as_done_not_lost(self, store):
        # a finished rank publishes a terminal exit beat: the monitor must
        # never condemn it, no matter how long its peers keep running
        hb0 = Heartbeat(rank=0, store=store, interval=0.05,
                        generation=0).start()
        hb1 = Heartbeat(rank=1, store=store, interval=0.05,
                        generation=0).start()
        mon = HeartbeatMonitor(store, 2, timeout=0.3, generation=0,
                               startup_grace=0.3)
        assert mon.poll() == []
        hb1.set_step(9)
        hb1.stop()  # rank 1 finishes cleanly; rank 0 keeps going
        time.sleep(0.6)  # well past rank 1's staleness deadline
        assert mon.poll() == []
        hb0.stop()

    def test_mark_done_exempts_rank(self, store):
        mon = HeartbeatMonitor(store, 2, timeout=10.0, generation=0,
                               startup_grace=0.1)
        mon.mark_done(1)  # e.g. the launcher saw its process exit 0
        time.sleep(0.2)
        assert [e.rank for e in mon.poll()] == [0]

    def test_watch_calls_on_lost(self, store):
        fired = []
        mon = HeartbeatMonitor(store, 1, timeout=5.0, generation=0,
                               startup_grace=0.1)
        mon.watch(fired.append, interval=0.05)
        deadline = time.monotonic() + 3
        while not fired and time.monotonic() < deadline:
            time.sleep(0.05)
        mon.stop()
        assert fired and fired[0].rank == 0

    def test_disabled_without_store_env(self, monkeypatch):
        monkeypatch.delenv("TPU_DIST_STORE_ADDR", raising=False)
        hb = Heartbeat(rank=0)
        assert not hb.enabled
        hb.start()
        hb.set_step(1)  # all no-ops
        hb.stop()


# -- store faults through the chaos hook -------------------------------------

class TestChaosStoreFaults:
    @pytest.fixture
    def py_store(self, monkeypatch):
        from tpu_dist.dist.store import _load_native
        monkeypatch.setenv("TPU_DIST_PURE_PYTHON_STORE", "1")
        _load_native.reset()
        s = TCPStore(is_master=True)
        yield s
        s.close()
        _load_native.reset()

    def test_drop_store_recovers_on_idempotent_op(self, py_store):
        py_store.set("k", b"v")
        c = chaos.install("drop-store:op=3", rank=0)
        try:
            assert py_store.get("k") == b"v"       # op 1
            assert py_store.check("k")             # op 2
            # op 3: socket closed under us -> reconnect -> replayed GET
            assert py_store.get("k") == b"v"
            assert c._op_count == 3
        finally:
            chaos.uninstall()

    def test_drop_store_set_stays_at_most_once(self, py_store):
        chaos.install("drop-store:op=1", rank=0)
        try:
            with pytest.raises(ConnectionError):
                py_store.set("k2", b"v2")
        finally:
            chaos.uninstall()
        # connection is re-established for the NEXT request
        py_store.set("k2", b"v2")
        assert py_store.get("k2") == b"v2"

    def test_delay_store_injects_latency(self, py_store):
        chaos.install("delay-store:op=1,delay=0.15", rank=0)
        try:
            t0 = time.monotonic()
            py_store.set("k3", b"v")
            assert time.monotonic() - t0 >= 0.15
        finally:
            chaos.uninstall()


# -- auto-resume TrainState ---------------------------------------------------

class TestTrainState:
    def _tree(self, scale=1.0):
        return {"w": np.full((4, 3), scale, np.float32),
                "b": np.arange(3, dtype=np.float32) * scale}

    def test_fresh_run_passthrough(self, tmp_path):
        from tpu_dist.resilience import TrainState
        with TrainState(str(tmp_path / "ckpt"), save_every=2,
                        heartbeat=False) as ts:
            state, start = ts.resume(self._tree())
            assert start == 0
            np.testing.assert_array_equal(state["w"], self._tree()["w"])

    def test_resume_from_latest(self, tmp_path):
        from tpu_dist.resilience import TrainState
        root = str(tmp_path / "ckpt")
        with TrainState(root, save_every=5, keep=None,
                        heartbeat=False) as ts:
            for step in range(7):  # saves at 0 and 5
                ts.end_step(self._tree(scale=float(step)), step)
        with TrainState(root, save_every=5, verify=True,
                        heartbeat=False) as ts:
            state, start = ts.resume(self._tree())
            assert start == 6
            np.testing.assert_array_equal(
                state["w"], self._tree(scale=5.0)["w"])

    def test_chaos_raise_fires_after_save(self, tmp_path):
        from tpu_dist import checkpoint
        from tpu_dist.resilience import TrainState
        root = str(tmp_path / "ckpt")
        chaos.install("raise:step=4", rank=0)
        with TrainState(root, save_every=4, keep=None,
                        heartbeat=False) as ts:
            for step in range(4):
                ts.end_step(self._tree(), step)
            with pytest.raises(chaos.ChaosError):
                ts.end_step(self._tree(scale=4.0), 4)
        # the step-4 checkpoint landed BEFORE the injected failure
        assert checkpoint.latest_step(root) == 4


# -- checkpoint durability / verification ------------------------------------

class TestCheckpointVerify:
    def test_digest_recorded_and_verifies(self, tmp_path):
        from tpu_dist import checkpoint
        root = str(tmp_path)
        tree = {"x": np.arange(6, dtype=np.float32)}
        checkpoint.save(root, tree, step=1)
        with open(os.path.join(root, "step_00000001", "tree.json")) as f:
            assert len(json.load(f)["arrays_sha256"]) == 64
        out = checkpoint.restore(root, tree, verify=True)
        np.testing.assert_array_equal(out["x"], tree["x"])

    def test_corrupt_npz_detected(self, tmp_path):
        from tpu_dist import checkpoint
        root = str(tmp_path)
        tree = {"x": np.arange(1024, dtype=np.float32)}
        checkpoint.save(root, tree, step=1)
        npz = os.path.join(root, "step_00000001", "arrays.npz")
        with open(npz, "r+b") as f:  # truncation: the crash signature
            f.truncate(os.path.getsize(npz) // 2)
        with pytest.raises(ValueError, match="digest"):
            checkpoint.restore(root, tree, verify=True)

    def test_missing_digest_with_verify_raises(self, tmp_path):
        from tpu_dist import checkpoint
        root = str(tmp_path)
        tree = {"x": np.zeros(3, np.float32)}
        checkpoint.save(root, tree, step=2)
        meta_path = os.path.join(root, "step_00000002", "tree.json")
        with open(meta_path) as f:
            meta = json.load(f)
        del meta["arrays_sha256"]  # pre-digest-era checkpoint
        with open(meta_path, "w") as f:
            json.dump(meta, f)
        with pytest.raises(ValueError, match="no arrays digest"):
            checkpoint.restore(root, tree, verify=True)
        checkpoint.restore(root, tree)  # verify=False still loads


# -- generation fencing -------------------------------------------------------

class TestGenerationFence:
    def test_stale_rank_fenced(self, store, monkeypatch):
        import importlib
        rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
        store.set(rdzv.GENERATION_KEY, b"2")
        monkeypatch.setenv("TPU_DIST_RESTART_COUNT", "1")
        with pytest.raises(RuntimeError, match="fenced out"):
            rdzv._fence_generation(store, process_id=3)

    def test_current_or_future_generation_passes(self, store, monkeypatch):
        import importlib
        rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
        store.set(rdzv.GENERATION_KEY, b"2")
        monkeypatch.setenv("TPU_DIST_RESTART_COUNT", "2")
        rdzv._fence_generation(store, process_id=0)
        # supervisor not yet published this round: key BEHIND the rank
        monkeypatch.setenv("TPU_DIST_RESTART_COUNT", "3")
        rdzv._fence_generation(store, process_id=0)

    def test_no_key_no_store_harmless(self, store, monkeypatch):
        import importlib
        rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
        monkeypatch.setenv("TPU_DIST_RESTART_COUNT", "0")
        rdzv._fence_generation(store, process_id=0)
        monkeypatch.setenv("TPU_DIST_RESTART_COUNT", "4")
        assert rdzv.generation() == 4


# -- spawn supervisor ---------------------------------------------------------

def _ki_worker(i):
    raise KeyboardInterrupt  # must exit 130, not 0


def _flaky_worker(i, path):
    gen = int(os.environ.get("TPU_DIST_RESTART_COUNT", "0"))
    with open(os.path.join(path, f"gen{gen}_rank{i}"), "w") as f:
        f.write("x")
    if gen == 0 and i == 1:
        # generation 0 always fails; generation 1 succeeds.  The supervisor
        # kills the world on the first failure, so fail only once rank 0 has
        # written its file: on a loaded box rank 0 may start seconds later
        # (the test below lists all four files)
        deadline = time.monotonic() + 60
        while (not os.path.exists(os.path.join(path, "gen0_rank0"))
               and time.monotonic() < deadline):
            time.sleep(0.01)
        sys.exit(5)


class TestSpawnSupervisor:
    def test_keyboard_interrupt_exits_130_and_surfaces(self):
        from tpu_dist.launch import ProcessExitedException, spawn
        with pytest.raises(ProcessExitedException,
                           match="KeyboardInterrupt") as ei:
            spawn(_ki_worker, nprocs=1)
        assert ei.value.exit_code == 130

    def test_max_restarts_respawns_and_resumes_generation(self, tmp_path,
                                                          monkeypatch):
        from tpu_dist.launch import spawn
        monkeypatch.delenv("TPU_DIST_RESTART_COUNT", raising=False)
        spawn(_flaky_worker, args=(str(tmp_path),), nprocs=2,
              max_restarts=1, restart_backoff=0.05)
        assert sorted(os.listdir(tmp_path)) == [
            "gen0_rank0", "gen0_rank1", "gen1_rank0", "gen1_rank1"]

    def test_max_restarts_exhausted_reraises(self, tmp_path, monkeypatch):
        from tpu_dist.launch import ProcessExitedException, spawn
        monkeypatch.delenv("TPU_DIST_RESTART_COUNT", raising=False)
        # _flaky_worker fails at generation 0 only — with 0 restarts the
        # first failure is final (fail-fast preserved exactly)
        with pytest.raises(ProcessExitedException) as ei:
            spawn(_flaky_worker, args=(str(tmp_path),), nprocs=2,
                  max_restarts=0)
        assert ei.value.exit_code == 5
        assert "gen1_rank0" not in os.listdir(tmp_path)

    def test_max_restarts_requires_join(self):
        from tpu_dist.launch import spawn
        with pytest.raises(ValueError, match="join"):
            spawn(_flaky_worker, nprocs=1, join=False, max_restarts=1)


# -- preflight partition diagnosis (fast path; e2e in test_launch_store) -----

class TestPreflightDiagnosis:
    def test_preflight_names_missing_rank(self, store, monkeypatch):
        import importlib
        rdzv = importlib.import_module("tpu_dist.dist.rendezvous")
        monkeypatch.delenv("TPU_DIST_PREFLIGHT_TIMEOUT", raising=False)
        with pytest.raises(RuntimeError, match=r"missing ranks: \[1\]"):
            rdzv._preflight(store, num_processes=2, process_id=0,
                            timeout=0.4)
