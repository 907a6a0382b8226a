"""tpu_dist.obs: flight recorder, trace merge, hang diagnosis — the ISSUE 4
acceptance tests.

Unit tier: ring-buffer overwrite + pending-span pinning, armed/disarmed
semantics (disarmed hooks are a shared no-op), dump/merge schema (valid
Chrome trace_event JSON), CLI merge/diagnose over synthetic dumps, and the
metrics-shim single-ingestion invariant.

E2E tier (``multiprocess``): a world-2 job whose rank 1 is chaos-``stall``ed
at step 3 must yield (a) a supervisor RankLostError carrying the lost
rank's last posted obs tail, (b) a per-rank "last known positions" table,
and (c) merged dumps whose diagnosis names the straggler rank, the
collective sequence number it never reached, and the user call-site.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from tpu_dist import obs
from tpu_dist.obs import hooks

pytestmark = [pytest.mark.obs]

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_recorder(monkeypatch):
    """Each test starts disarmed with no singleton recorder or counters."""
    monkeypatch.delenv("TPU_DIST_OBS", raising=False)
    monkeypatch.delenv("TPU_DIST_OBS_DIR", raising=False)
    obs.reset()
    yield
    obs.reset()


def _armed(monkeypatch, tmp_path):
    monkeypatch.setenv("TPU_DIST_OBS", "1")
    monkeypatch.setenv("TPU_DIST_OBS_DIR", str(tmp_path))
    obs.reset()


# -- ring buffer --------------------------------------------------------------


class TestRingBuffer:
    def test_overwrite_keeps_newest(self):
        rec = obs.FlightRecorder(capacity=8, rank=0, world=1, generation=0)
        for i in range(20):
            rec.record("user", f"ev{i}")
        evs = rec.snapshot()
        assert len(evs) == 8
        assert [e["seq"] for e in evs] == list(range(12, 20))
        assert evs[-1]["op"] == "ev19"

    def test_pending_span_survives_eviction(self):
        # THE hang-dump property: a flood of later events (store polls
        # while blocked) must not evict the pending collective that
        # explains the hang
        rec = obs.FlightRecorder(capacity=4, rank=0, world=1, generation=0)
        ev = rec.begin("collective", "all_reduce", coll=0, site="x.py:1")
        for _ in range(50):
            rec.record("store", "set")
        evs = rec.snapshot()
        pend = [e for e in evs if e["outcome"] == "pending"]
        assert len(pend) == 1 and pend[0]["op"] == "all_reduce"
        rec.end(ev)
        assert all(e["outcome"] != "pending" for e in rec.snapshot())

    def test_capacity_env(self, monkeypatch, tmp_path):
        monkeypatch.setenv("TPU_DIST_OBS_CAPACITY", "32")
        _armed(monkeypatch, tmp_path)
        assert obs.get_recorder().capacity == 32

    def test_last_position_prefers_collectives(self):
        rec = obs.FlightRecorder(capacity=16, rank=2, world=4, generation=1)
        ev = rec.begin("collective", "broadcast", coll=5, site="t.py:9")
        rec.end(ev)
        rec.record("beat", "beat", step=7)
        pos = rec.last_position()
        assert pos["rank"] == 2 and pos["generation"] == 1
        assert pos["coll"] == 5 and pos["op"] == "broadcast"
        assert pos["outcome"] == "ok"


# -- armed/disarmed -----------------------------------------------------------


class TestArming:
    def test_disarmed_is_noop(self):
        assert not obs.enabled()
        assert obs.get_recorder() is None
        ctx = hooks.collective_span("all_reduce")
        with ctx as ev:
            assert ev is None
        # the disarmed context is SHARED (no per-call allocation)
        assert hooks.collective_span("broadcast") is ctx

    def test_disarmed_cost_stays_small(self):
        n = 5000
        t0 = time.perf_counter()
        for _ in range(n):
            with hooks.collective_span("all_reduce"):
                pass
        per_call = (time.perf_counter() - t0) / n
        # reality is ~1µs; the bound is generous for noisy CI boxes, and
        # the real acceptance is benchmarks/bench_obs_overhead.py --smoke
        assert per_call < 50e-6, f"disarmed span cost {per_call * 1e6:.1f}µs"

    def test_armed_span_records_everything(self, monkeypatch, tmp_path):
        _armed(monkeypatch, tmp_path)
        with hooks.collective_span("all_reduce",
                                   value=np.zeros(1024, np.float32),
                                   reduce_op="SUM") as ev:
            assert ev["outcome"] == "pending"
            obs.record_transport("all_reduce", "store", 4096, 0.001)
        evs = [e for e in obs.get_recorder().snapshot()
               if e["kind"] == "collective"]
        e = evs[-1]
        assert e["outcome"] == "ok" and e["coll"] == 0
        assert e["reduce"] == "sum" and e["path"] == "store"
        assert "float32[1024]" in e["digest"] and e["bytes"] == 4096
        assert e["t1"] >= e["t0"] and e["site"]
        # counters agree with the event stream: one ingestion point
        assert obs.transport_counters()["all_reduce/store"]["calls"] == 1

    def test_error_outcome_and_nesting(self, monkeypatch, tmp_path):
        _armed(monkeypatch, tmp_path)
        with pytest.raises(RuntimeError):
            with hooks.collective_span("broadcast", src=0):
                with hooks.collective_span("ring_all_reduce",
                                           value=np.zeros(4)):
                    raise RuntimeError("boom")
        evs = [e for e in obs.get_recorder().snapshot()
               if e["kind"] == "collective"]
        assert [e["coll"] for e in evs] == [0, 1]  # lockstep counter
        assert all(e["outcome"] == "error:RuntimeError" for e in evs)

    def test_p2p_spans_do_not_consume_coll_seq(self, monkeypatch, tmp_path):
        # send/recv are rank-asymmetric: consuming the lockstep counter
        # would desynchronize the cross-rank alignment key
        _armed(monkeypatch, tmp_path)
        with hooks.collective_span("send", dst=1, kind="p2p"):
            pass
        with hooks.collective_span("all_reduce"):
            pass
        evs = obs.get_recorder().snapshot()
        p2p = next(e for e in evs if e["kind"] == "p2p")
        coll = next(e for e in evs if e["kind"] == "collective")
        assert "coll" not in p2p and p2p["dst"] == 1
        assert coll["coll"] == 0


# -- metrics shim -------------------------------------------------------------


def test_metrics_shim_reads_obs_stream():
    from tpu_dist.utils import metrics
    metrics.reset_collective_counters()
    obs.record_transport("send", "dataplane", 10, 0.001)
    obs.record_transport("send", "dataplane", 20, 0.002)
    c = metrics.collective_counters()
    assert c["send/dataplane"]["calls"] == 2
    assert c["send/dataplane"]["bytes"] == 30
    assert c == obs.transport_counters()
    metrics.reset_collective_counters()
    assert obs.transport_counters() == {}


# -- store tails --------------------------------------------------------------


def test_post_and_fetch_tail_roundtrip(monkeypatch, tmp_path):
    from tpu_dist.dist.store import FileStore
    _armed(monkeypatch, tmp_path)
    store = FileStore(str(tmp_path / "fs"))
    rec = obs.FlightRecorder(capacity=16, rank=3, world=4, generation=2)
    rec.begin("collective", "all_reduce", coll=7, site="train.py:42")
    hooks.post_tail(store, rec)
    tail = hooks.fetch_tail(store, 2, 3)
    assert tail["coll"] == 7 and tail["outcome"] == "pending"
    assert tail["rank"] == 3
    rendered = hooks.render_tail(tail)
    assert "collective #7" in rendered and "train.py:42" in rendered
    # wrong generation / never-posted rank -> None, never a blocking get
    assert hooks.fetch_tail(store, 0, 3) is None
    assert hooks.fetch_tail(store, 2, 1) is None


def test_rank_lost_error_attaches_obs_tail():
    from tpu_dist.resilience import RankLostError
    tail = {"rank": 1, "generation": 0, "seq": 57, "kind": "collective",
            "op": "all_reduce", "coll": 12, "site": "train.py:88",
            "outcome": "pending", "events": 58}
    err = RankLostError(1, 5.0, 3.0, last_payload=b"123:4:9", obs_tail=tail)
    assert "last obs:" in str(err) and "collective #12" in str(err)
    assert "train.py:88" in str(err)
    assert err.obs_tail is tail
    # without a tail the message is unchanged in shape
    assert "last obs" not in str(RankLostError(1, 5.0, 3.0))


# -- dumps / merge / diagnose -------------------------------------------------


def _mk_dump(dir_path, rank, done, pending, gen=0, world=2):
    rec = obs.FlightRecorder(capacity=64, rank=rank, world=world,
                             generation=gen)
    for i in range(done):
        ev = rec.begin("collective", "all_reduce", coll=i,
                       site="train.py:10", reduce="sum")
        rec.end(ev)
    if pending:
        rec.begin("collective", "all_reduce", coll=done,
                  site="train.py:10", reduce="sum")
    return rec.dump("test", dir=str(dir_path))


class TestTrace:
    def test_dump_schema_and_read(self, tmp_path):
        path = _mk_dump(tmp_path, 0, 3, pending=True)
        with open(path) as f:
            doc = json.load(f)
        for key in ("version", "rank", "world", "generation", "pid",
                    "reason", "wall_anchor_ns", "mono_anchor_ns",
                    "mono_dump_ns", "events"):
            assert key in doc, key
        dumps = obs.read_dumps(str(tmp_path))
        assert len(dumps) == 1 and dumps[0]["rank"] == 0

    def test_read_dumps_picks_newest_generation(self, tmp_path):
        _mk_dump(tmp_path, 0, 2, pending=False, gen=0)
        _mk_dump(tmp_path, 0, 5, pending=False, gen=1)
        dumps = obs.read_dumps(str(tmp_path))
        assert len(dumps) == 1 and dumps[0]["generation"] == 1
        assert len(obs.read_dumps(str(tmp_path), generation=0)) == 1

    def test_merge_trace_is_valid_chrome_json(self, tmp_path):
        _mk_dump(tmp_path, 0, 4, pending=True)
        _mk_dump(tmp_path, 1, 4, pending=False)
        tr = obs.merge_trace(obs.read_dumps(str(tmp_path)))
        # JSON round-trip (the acceptance: loads as valid trace_event JSON)
        tr = json.loads(json.dumps(tr))
        assert isinstance(tr["traceEvents"], list)
        xs = [e for e in tr["traceEvents"] if e.get("ph") == "X"]
        assert {e["pid"] for e in xs} == {0, 1}  # one track per rank
        for e in xs:
            assert isinstance(e["ts"], (int, float))
            assert e["dur"] > 0 and e["name"]
        # collectives are named by lockstep seq for visual alignment
        assert any(e["name"] == "all_reduce #0" for e in xs)
        # the pending collective spans to dump time with its outcome kept
        pend = [e for e in xs if e["args"].get("outcome") == "pending"]
        assert len(pend) == 1 and pend[0]["pid"] == 0

    def test_diagnose_straggler(self, tmp_path):
        _mk_dump(tmp_path, 0, 4, pending=True)   # waiting in #4
        _mk_dump(tmp_path, 1, 4, pending=False)  # finished #3, never at #4
        d = obs.diagnose(obs.read_dumps(str(tmp_path)))
        assert d["verdict"] == "straggler"
        assert d["straggler"] == 1
        assert d["straggler_last_coll"] == 3
        assert d["stuck_coll"] == 4 and d["stuck_op"] == "all_reduce"
        assert d["stuck_site"] == "train.py:10"
        assert d["waiting_ranks"] == [0]
        text = obs.render_diagnosis(d)
        assert "rank 1" in text and "#4" in text and "train.py:10" in text

    def test_diagnose_healthy_and_stuck(self, tmp_path):
        _mk_dump(tmp_path, 0, 4, pending=False)
        _mk_dump(tmp_path, 1, 4, pending=False)
        assert obs.diagnose(obs.read_dumps(str(tmp_path)))["verdict"] == \
            "healthy"
        stuck_dir = tmp_path / "stuck"
        stuck_dir.mkdir()
        _mk_dump(stuck_dir, 0, 4, pending=True)
        _mk_dump(stuck_dir, 1, 4, pending=True)
        d = obs.diagnose(obs.read_dumps(str(stuck_dir)))
        assert d["verdict"] == "stuck" and d["stuck_coll"] == 4

    def test_diagnose_empty(self):
        assert obs.diagnose([])["verdict"] == "no-dumps"

    @pytest.mark.cluster
    def test_diagnose_surfaces_store_failover_naming_promoted_leader(
            self, tmp_path):
        # a client that rode a leader failover records kind="store"
        # op="failover" (store.py's endpoint re-resolution); the merged
        # diagnosis must surface the control-plane move and NAME the
        # promoted leader, whatever the hang/straggler verdict is
        rec = obs.FlightRecorder(capacity=64, rank=0, world=2)
        ev = rec.begin("collective", "all_reduce", coll=0,
                       site="train.py:10", reduce="sum")
        rec.end(ev)
        rec.record("store", "failover", key="127.0.0.1:9102",
                   old="127.0.0.1:9101", epoch=1)
        rec.dump("test", dir=str(tmp_path))
        _mk_dump(tmp_path, 1, 1, pending=False)
        d = obs.diagnose(obs.read_dumps(str(tmp_path)))
        assert d["store_failovers"] == [
            {"rank": 0, "leader": "127.0.0.1:9102",
             "old": "127.0.0.1:9101", "epoch": 1}]
        text = obs.render_diagnosis(d)
        assert "leader 127.0.0.1:9101 lost" in text, text
        assert "promoted leader 127.0.0.1:9102" in text, text
        assert "epoch 1" in text and "rank(s) [0]" in text, text

    def test_diagnose_missing_ranks_is_not_healthy(self, tmp_path):
        # a SIGKILLed rank leaves no dump: a clean-looking partial world
        # must not read as healthy
        _mk_dump(tmp_path, 0, 4, pending=False, world=3)
        _mk_dump(tmp_path, 1, 4, pending=False, world=3)
        d = obs.diagnose(obs.read_dumps(str(tmp_path)))
        assert d["verdict"] == "missing-ranks"
        assert d["missing_ranks"] == [2]
        assert "no dump from rank(s) [2]" in obs.render_diagnosis(d)

    def test_diagnose_no_collectives_is_not_healthy_on_crash(self, tmp_path):
        # a pre-first-collective hang flushed by a signal must NOT read as
        # healthy; the same dump from a clean exit is benign
        rec = obs.FlightRecorder(capacity=8, rank=0, world=1, generation=0)
        rec.record("store", "set")
        rec.dump("signal:10", dir=str(tmp_path))
        d = obs.diagnose(obs.read_dumps(str(tmp_path)))
        assert d["verdict"] == "no-collectives" and not d["clean_exit"]
        assert "NOT a clean exit" in obs.render_diagnosis(d)
        rec.dump("exit", dir=str(tmp_path))  # same rank, clean reason
        d2 = obs.diagnose(obs.read_dumps(str(tmp_path)))
        assert d2["verdict"] == "no-collectives" and d2["clean_exit"]


# -- CLI ----------------------------------------------------------------------


def _cli(*args, **kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "tpu_dist.obs", *args],
                          cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=120, **kw)


class TestCLI:
    def test_merge_writes_valid_trace(self, tmp_path):
        _mk_dump(tmp_path, 0, 4, pending=True)
        _mk_dump(tmp_path, 1, 4, pending=False)
        out = tmp_path / "trace.json"
        r = _cli("merge", "--dir", str(tmp_path), "--out", str(out))
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            tr = json.load(f)
        assert tr["traceEvents"] and "merged 2 rank(s)" in r.stderr

    def test_diagnose_names_straggler_exit_3(self, tmp_path):
        _mk_dump(tmp_path, 0, 4, pending=True)
        _mk_dump(tmp_path, 1, 4, pending=False)
        r = _cli("diagnose", "--dir", str(tmp_path))
        assert r.returncode == 3
        assert "rank 1" in r.stdout and "#4" in r.stdout
        rj = _cli("diagnose", "--dir", str(tmp_path), "--json")
        doc = json.loads(rj.stdout)
        # versioned envelope shared with `analysis replay --format json`
        assert doc["version"] == 1 and doc["tool"] == "diagnose"
        assert doc["ranks"] == [0, 1]
        d = doc["diagnosis"]
        assert d["straggler"] == 1 and d["stuck_coll"] == 4

    def test_no_dumps_exit_1(self, tmp_path):
        r = _cli("diagnose", "--dir", str(tmp_path / "empty"))
        assert r.returncode == 1 and "no flight-recorder dumps" in r.stderr

    def test_show_prints_events(self, tmp_path):
        _mk_dump(tmp_path, 0, 2, pending=False)
        r = _cli("show", "--dir", str(tmp_path), "--rank", "0")
        assert r.returncode == 0 and "all_reduce" in r.stdout


# -- world-2 e2e: chaos-stalled rank -> named diagnosis -----------------------

_STALL_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import tpu_dist.dist as dist
    from tpu_dist import collectives as C
    from tpu_dist import resilience

    ckpt = sys.argv[1]
    pg = dist.init_process_group(backend="cpu", init_method="env://")
    # monitor=False: the launcher's watchdog is the system under test (an
    # in-process monitor racing it would make the stderr assertion flaky)
    with resilience.TrainState(ckpt, save_every=0, heartbeat_interval=0.2,
                               monitor=False) as ts:
        state, start = ts.resume({"x": np.zeros(1)})
        for step in range(start, 10):
            g = np.full(256, float(step), np.float32)
            C.all_reduce_host(g, group=pg, op="sum")  # the hang site
            ts.end_step(state, step)
    dist.destroy_process_group()
""")


@pytest.mark.multiprocess
def test_world2_stalled_rank_yields_named_diagnosis(tmp_path):
    """THE acceptance run: rank 1 stalls (sleep + frozen heartbeat/tail)
    at step 3 while rank 0 enters step 4's all_reduce and waits.  The
    supervisor must name the lost rank WITH its last obs position, print
    the per-rank table, and the merged dumps must diagnose: rank 1 behind,
    collective seq #4, call-site in the worker script."""
    script = tmp_path / "stall_worker.py"
    script.write_text(_STALL_WORKER)
    obs_dir = tmp_path / "obsdumps"
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the known-good CPU multiprocess topology (see test_chaos_e2e.py)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["TPU_DIST_CHAOS"] = "stall:rank=1,step=3"
    env["TPU_DIST_OBS_DIR"] = str(obs_dir)
    env.pop("TPU_DIST_OBS", None)
    r = subprocess.run(
        [sys.executable, "-m", "tpu_dist.launch", "--nproc_per_node=2",
         "--master_port=0", "--heartbeat_timeout=3", "--flight-recorder",
         str(script), str(tmp_path / "ckpt")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=240)

    assert r.returncode != 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    # (a) the watchdog names the rank AND its last posted obs position
    assert "RankLostError" in r.stderr, r.stderr
    assert "rank 1" in r.stderr
    assert "last obs:" in r.stderr and "all_reduce" in r.stderr
    # (b) the supervisor's per-rank table, from the store tails
    assert "last known positions" in r.stderr, r.stderr
    assert "flight-recorder dumps in" in r.stderr

    # (c) both ranks flushed dumps (rank 0 via SIGTERM/abort, rank 1's
    # TERM handler interrupts the chaos sleep)
    dumps = obs.read_dumps(str(obs_dir))
    assert {d["rank"] for d in dumps} == {0, 1}, \
        f"dumps: {[d.get('rank') for d in dumps]}\nstderr:\n{r.stderr}"
    diag = obs.diagnose(dumps)
    assert diag["verdict"] == "straggler", diag
    assert diag["straggler"] == 1
    # steps 0-3 completed on rank 1 -> its last collective is #3; rank 0
    # is pending in step 4's all_reduce = collective #4
    assert diag["straggler_last_coll"] == 3, diag
    assert diag["stuck_coll"] == 4, diag
    assert "stall_worker.py" in (diag["stuck_site"] or ""), diag
    # the CLI agrees and exits 3 (hang found)
    p = _cli("diagnose", "--dir", str(obs_dir))
    assert p.returncode == 3
    assert "rank 1" in p.stdout and "#4" in p.stdout

    # (d) the offline replay sanitizer re-derives the SAME verdict from
    # the dump files alone: a TD115 error naming the straggler rank and
    # the collective seq, with the live diagnosis embedded verbatim
    from tpu_dist.analysis import replay_dir
    rep = replay_dir(str(obs_dir))
    td115 = [f for f in rep.findings if f.rule == "TD115"]
    assert td115 and td115[0].severity == "error", rep.findings
    assert "rank 1" in td115[0].message and "#4" in td115[0].message
    assert rep.diagnosis["straggler"] == diag["straggler"]
    assert rep.diagnosis["straggler_last_coll"] == \
        diag["straggler_last_coll"]
    assert rep.diagnosis["stuck_coll"] == diag["stuck_coll"]


# -- armed-overhead bench smoke (slow-tier wiring of bench_obs_overhead) ------


# slow: ~2 min of best-of-N timing on a box where the <5% overhead gate
# is dominated by scheduler noise (it fails under any concurrent load —
# see the ABBA-estimator note in test_ring_collectives); run it alone.
@pytest.mark.slow
@pytest.mark.multiprocess
def test_bench_obs_overhead_smoke():
    """Armed-recorder overhead on the host-collective smoke bench stays
    under 5% (the bench retries internally: the bound is about the
    recorder, not scheduler noise)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TPU_DIST_OBS", None)
    for outer in range(2):  # one spare run: 2-core CI noise, not recorder
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_obs_overhead",
             "--smoke"],
            cwd=_REPO, env=env, capture_output=True, text=True, timeout=540)
        if r.returncode == 0:
            break
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.strip().startswith("{")]
    assert any(row.get("metric") == "obs_overhead_pct" for row in lines)
