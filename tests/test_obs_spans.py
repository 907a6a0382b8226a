"""tpu_dist.obs.spans and the scopes on the compiled path (ISSUE 23).

The primitive (nesting, fields, thread safety, the phase table); the serving
loop's phases counted where the work happens and carried by the wire
``stats`` frame; the ``td/`` annotations with their ``req``/``step`` fields in
a CPU profiler capture; the module path, ``optimizer``, ``grad_reduce`` and
the decode program's scopes in lowered text; and bitwise-equal outputs with
and without the scopes.  A CPU run checks names and counts, never a time on
the device.
"""

import contextlib
import glob
import os
import re
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import tpu_dist.dist as dist
from tpu_dist import nn, obs, optim, serve
from tpu_dist.models import TransformerLM
from tpu_dist.parallel import DistributedDataParallel
from tpu_dist.serve.engine import SERVE_PHASES

pytestmark = pytest.mark.obs

DECODE = ("decode.dispatch", "decode.readback", "decode.emit")
PREFILL = ("prefill.prepare", "prefill.dispatch", "prefill.readback",
           "prefill.emit")
# every phase the loop thread itself runs (stage.put is the stage thread's)
LOOP = ("sweep", "sched.wait") + PREFILL + DECODE


def _counts(names):
    return {n: s["count"] for n, s in obs.phase_times(names).items()}


def _sum(names):
    return sum(s["mean"] * s["count"]
               for s in obs.phase_times(names).values())


@contextlib.contextmanager
def _profile(tmp_path):
    """A jax.profiler capture of what runs inside, host annotations only."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _annotations(tmp_path):
    """[(name without td/, fields, thread, start_ns, end_ns)]; a thread is
    one line of the host's plane (they all carry the process's name)."""
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [(e.name[len(obs.spans.PREFIX):], dict(e.stats), (plane.name, i),
             e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes for i, line in enumerate(plane.lines)
            for e in line.events if e.name.startswith(obs.spans.PREFIX)]


# -- the primitive -------------------------------------------------------------

class TestSpan:
    def test_nested_spans_each_add_one_sample(self):
        obs.reset_phases()
        with obs.span("t.outer", step=1):
            with obs.span("t.inner", req=9):
                time.sleep(0.002)
            with obs.span("t.inner", req=10):
                pass
        got = obs.phase_times()
        assert got["t.outer"]["count"] == 1 and got["t.inner"]["count"] == 2
        assert got["t.outer"]["max"] >= got["t.inner"]["max"] >= 0.002
        # mean * count is the exact sum
        inner = got["t.inner"]
        assert inner["mean"] * inner["count"] <= got["t.outer"]["max"]

    def test_an_exception_still_closes_the_span(self):
        obs.reset_phases()
        with pytest.raises(KeyError):
            with obs.span("t.raises"):
                raise KeyError("x")
        assert _counts(["t.raises"]) == {"t.raises": 1}

    def test_phase_times_of_names_and_reset(self):
        obs.reset_phases()
        for _ in range(3):
            with obs.span("t.a"):
                pass
        with obs.span("t.b"):
            pass
        assert _counts(["t.a", "t.never"]) == {"t.a": 3, "t.never": 0}
        assert obs.phase_times(["t.never"])["t.never"]["p50"] == 0.0
        obs.reset_phases(["t.a"])
        assert _counts(["t.a", "t.b"]) == {"t.a": 0, "t.b": 1}
        obs.reset_phases()
        assert all(s["count"] == 0 for s in obs.phase_times().values())

    def test_concurrent_spans_lose_no_sample(self):
        obs.reset_phases()
        workers, each = 16, 400
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work(i):
                for _ in range(each):
                    with obs.span("t.shared", slot=i):
                        with obs.span(f"t.own{i % 4}"):
                            pass
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        assert _counts(["t.shared"]) == {"t.shared": workers * each}
        assert sum(_counts([f"t.own{k}" for k in range(4)]).values()) \
            == workers * each

    def test_span_lands_in_the_profiler_trace_with_its_fields(self, tmp_path):
        with _profile(tmp_path):
            with obs.span("t.outer", step=3, active=2):
                with obs.span("t.inner", req=7, slot=1, bucket=16):
                    jnp.ones(4).block_until_ready()
        rows = {name: (fields, s, e)
                for name, fields, _, s, e in _annotations(tmp_path)}
        assert rows["t.outer"][0] == {"step": 3, "active": 2}
        assert rows["t.inner"][0] == {"req": 7, "slot": 1, "bucket": 16}
        # nesting gives the cause: the inner span lies inside the outer
        assert rows["t.outer"][1] <= rows["t.inner"][1]
        assert rows["t.inner"][2] <= rows["t.outer"][2]


# -- the serving loop's phases -------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=97, dim=32, depth=2, num_heads=4,
                          max_seq_len=64)
    return model, model.init(jax.random.key(0))


class TestServingPhases:
    def test_each_step_and_admission_adds_one_sample_per_phase(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        engine.reset_stats()
        rng = np.random.default_rng(3)
        reqs = [serve.Request(rng.integers(0, 97, n).astype(np.int32), 6)
                for n in (5, 9, 12)]
        for r in reqs:
            engine.admit(r)
        steps = 0
        while not engine.idle():
            engine.step()
            steps += 1
        assert engine.step() == 0                # idle: no phase runs
        engine.sweep_expired()
        got = _counts(SERVE_PHASES)
        assert {got[n] for n in PREFILL} == {len(reqs)}
        assert {got[n] for n in DECODE} == {steps}
        assert steps == engine.stats()["decode_steps"] == 5
        # nothing was staged ahead, so _admit staged inline: once a request
        assert got["stage.put"] == len(reqs)
        assert got["sweep"] == 1 and got["sched.wait"] == 0
        phases = engine.stats()["phases"]
        assert tuple(phases) == SERVE_PHASES
        assert phases["decode.readback"]["count"] == steps
        # the old histograms are as they were: dispatch + readback
        tok = engine.stats()["decode_step"]
        assert tok["count"] == steps
        inside = _sum(("decode.dispatch", "decode.readback"))
        assert inside <= tok["mean"] * tok["count"] + 1e-9

    def test_reset_stats_zeroes_the_phases_and_only_them(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        engine.admit(serve.Request(np.arange(4, dtype=np.int32), 3))
        engine.step()
        with obs.span("t.not_serving"):
            pass
        engine.reset_stats()
        assert set(_counts(SERVE_PHASES).values()) == {0}
        assert _counts(["t.not_serving"]) == {"t.not_serving": 1}
        assert engine.stats()["phases"]["decode.emit"]["count"] == 0

    def test_loop_phases_fill_the_loop_threads_time_and_ride_the_wire(
            self, lm, tmp_path):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            # warm every shape, so the measured stretch compiles nothing
            for n in (5, 20):
                cli.generate(list(range(1, n)), max_new_tokens=3,
                             timeout=120.0)
            time.sleep(0.05)          # the loop is back in its idle wait
            engine.reset_stats()
            t0 = time.perf_counter()
            with _profile(tmp_path):
                rng = np.random.default_rng(5)
                handles = [cli.submit(
                    rng.integers(0, 97, int(rng.integers(3, 30))).tolist(),
                    max_new_tokens=int(rng.integers(8, 24)))
                    for _ in range(10)]
                ids = [h.id for h in handles]
                for h in handles:
                    h.wait_done(120.0)
            time.sleep(0.12)          # idle waits: sched.wait has samples
            wall = time.perf_counter() - t0
            stats = cli.stats()
        finally:
            cli.close()
            fe.close()
            sched.close()
        phases = stats["phases"]
        assert tuple(phases) == SERVE_PHASES
        steps = stats["decode_steps"]
        assert steps > 0 and stats["completed"] == 10
        assert {phases[n]["count"] for n in DECODE} == {steps}
        # a prefill program may carry several of the ten (ISSUE 48): the
        # counters that say how many ride the wire's stats frame
        pipeline = stats["pipeline"]
        programs = pipeline["launches"]["prefill"]
        assert 1 <= programs <= pipeline["prefill_prompts"] == 10
        assert pipeline["prefill_absent_rows"] >= 0
        assert pipeline["deferred_slot_steps"] >= 0
        assert {phases[n]["count"] for n in PREFILL} == {programs}
        assert phases["stage.put"]["count"] == 10
        assert phases["sched.wait"]["count"] > 0
        assert phases["sweep"]["count"] >= steps
        # the loop is one loop: its phases account for its wall time, but
        # for the one wait (at most 50 ms) open when the counters were read
        covered = sum(phases[n]["mean"] * phases[n]["count"] for n in LOOP)
        assert 0.8 * (wall - 0.05) <= covered <= 1.01 * wall, (covered, wall)

        rows = _annotations(tmp_path)
        by_name = {}
        for name, fields, thread, s, e in rows:
            by_name.setdefault(name, []).append((fields, thread, s, e))
        assert set(DECODE + PREFILL + ("stage.put", "sweep")) <= set(by_name)
        # req on every span of one request, the same id the handle carries
        served = {f["req"] for f, *_ in by_name["prefill.dispatch"]}
        assert served and served <= set(ids)
        for name in PREFILL + ("stage.put",):
            assert all({"req"} <= set(f) for f, *_ in by_name[name]), name
        # the program's spans carry its group's size beside its bucket
        for name in ("prefill.prepare", "prefill.dispatch"):
            assert all({"req", "slot", "bucket", "prompts"} <= set(f)
                       for f, *_ in by_name[name]), name
        assert sum(f["prompts"] for f, *_ in
                   by_name["prefill.dispatch"]) == 10
        # and every member's id and slot, the first of them under req / slot
        members = [str(f["reqs"]).split("/")
                   for f, *_ in by_name["prefill.dispatch"]]
        assert sorted(int(r) for m in members for r in m) == sorted(ids)
        for f, *_ in by_name["prefill.dispatch"]:
            reqs, slots = (str(f[k]).split("/") for k in ("reqs", "slots"))
            assert len(reqs) == len(slots) == int(f["prompts"])
            assert int(reqs[0]) == int(f["req"])
            assert int(slots[0]) == int(f["slot"])
            assert len(set(slots)) == len(slots)
        # step on the spans of one decode iteration: the three phases of an
        # iteration share it and follow one another on the loop thread
        for name in DECODE:
            assert all({"step", "active"} <= set(f)
                       for f, *_ in by_name[name])
        at = {n: {f["step"]: (s, e, th) for f, th, s, e in by_name[n]}
              for n in DECODE}
        common = set.intersection(*(set(v) for v in at.values()))
        assert common
        for k in common:
            d, r, m = (at[n][k] for n in DECODE)
            assert d[1] <= r[0] and r[1] <= m[0]
            assert d[2] == r[2] == m[2]
        # staging runs on a thread of its own
        loop_thread = at["decode.emit"][min(common)][2]
        assert loop_thread not in {th for _, th, _, _ in by_name["stage.put"]}
        # and on the trace's own clock, from the loop thread's first span to
        # its last: what no phase covers is the scheduler's own few lines
        # between them, a tenth of this toy model's half-millisecond
        # iteration (nothing of a real model's)
        on_loop = sorted((s, e) for _, _, th, s, e in rows
                         if th == loop_thread)
        inside, upto = 0, on_loop[0][0]
        for s, e in on_loop:            # nested spans count once
            inside += max(0, e - max(s, upto))
            upto = max(upto, e)
        extent = upto - on_loop[0][0]
        assert inside >= 0.7 * extent, (inside, extent)


# -- the loop thread's clock ---------------------------------------------------

KINDS = ("prefill", "decode", "idle")
WAITS = ("t.readback", "t.wait")


@pytest.fixture
def clock():
    """A clock this test's thread owns; the thread is disowned afterwards,
    so the spans of later tests pay the one lookup again."""
    c = obs.LoopClock("test loop", KINDS, WAITS, sleep="t.wait")
    c.tick("idle")
    yield c
    obs.spans._local.clock = None


def _spin(seconds):
    end = time.perf_counter() + seconds
    n = 0
    while time.perf_counter() < end:
        n += 1
    return n


class TestLoopClock:
    def test_the_books_balance_and_nested_spans_count_once(self, clock):
        for i in range(3):
            with obs.span("t.outer"):
                with obs.span("t.inner"):
                    time.sleep(0.003)
                with obs.span("t.inner"):
                    pass
            with obs.span("t.readback"):
                time.sleep(0.002)
            time.sleep(0.002)                 # under no span: unnamed
            clock.tick("decode", step=i + 1)
        got = clock.stats()
        assert got["iterations"] == {"prefill": 0, "decode": 3, "idle": 0}
        assert got["covered_s"] + got["unnamed_s"] == pytest.approx(
            got["wall_s"], rel=1e-12)
        assert got["unnamed_s"] >= 3 * 0.002
        rec = got["longest"]["decode"][0]
        # t.inner lies inside t.outer: in by_phase under its own name, in
        # the iteration's covered time once
        assert rec["by_phase"]["t.outer"] >= rec["by_phase"]["t.inner"] > 0
        covered = rec["by_phase"]["t.outer"] + rec["by_phase"]["t.readback"]
        assert rec["wall"] - rec["unnamed"] == pytest.approx(covered)
        assert got["wait_s"] == pytest.approx(sum(
            r["by_phase"]["t.readback"] for r in got["longest"]["decode"]))
        assert all(r["wait"] == r["by_phase"]["t.readback"]
                   for r in got["longest"]["decode"])

    def test_a_span_on_another_thread_adds_nothing(self, clock):
        def other():
            with obs.span("t.elsewhere"):
                time.sleep(0.01)
        t = threading.Thread(target=other)
        with obs.span("t.mine"):
            t.start()
            t.join()
        clock.tick("decode")
        rec, = clock.stats()["longest"]["decode"]
        assert set(rec["by_phase"]) == {"t.mine"}
        assert clock.stats()["covered_s"] == rec["by_phase"]["t.mine"]

    def test_a_sleep_in_a_phase_is_time_off_the_cpu(self, clock):
        with obs.span("t.emit"):
            time.sleep(0.05)
        clock.tick("decode", step=7)
        rec, = clock.stats()["longest"]["decode"]
        assert rec["phase"] == "t.emit" and rec["step"] == 7
        assert rec["wall"] >= 0.05
        # compared, not gated on microseconds: the thread stood off the CPU
        assert rec["offcpu"] > 0.6 * rec["wall"] > rec["cpu"]
        assert rec["offcpu"] <= rec["wall"] and rec["wait"] == 0.0

    def test_a_busy_loop_in_a_phase_is_cpu(self, clock):
        # six workers wide the OS may take the CPU away for most of one
        # spin: the best of a few is compared, no microsecond is gated
        for _ in range(5):
            with obs.span("t.emit"):
                _spin(0.05)
            clock.tick("decode")
        kept = clock.stats()["longest"]["decode"]
        assert {r["phase"] for r in kept} == {"t.emit"}
        assert any(r["cpu"] > r["offcpu"] for r in kept)
        assert all(r["cpu"] <= r["wall"] * 1.05 + 0.005 for r in kept)

    def test_a_designed_wait_is_neither(self, clock):
        with obs.span("t.readback"):
            time.sleep(0.03)
        with obs.span("t.wait"):
            time.sleep(0.02)
        clock.tick("idle")
        got = clock.stats()
        rec, = got["longest"]["idle"]
        assert rec["wait"] >= 0.05 and rec["offcpu"] < 0.02
        # the histogram and the ranking leave the loop's own sleep out
        assert got["iteration"]["idle"]["max"] == pytest.approx(
            rec["wall"] - rec["by_phase"]["t.wait"])
        assert rec["phase"] == "t.readback"

    def test_what_the_other_threads_burn_is_counted_beside(self, clock):
        stop = threading.Event()
        burner = threading.Thread(target=lambda: [_spin(0.01) for _ in
                                                  iter(stop.is_set, True)])
        burner.start()
        try:
            with obs.span("t.emit"):
                time.sleep(0.08)
        finally:
            stop.set()
            burner.join()
        clock.tick("decode")
        rec, = clock.stats()["longest"]["decode"]
        assert rec["cpu_others"] > rec["cpu"]
        assert clock.stats()["cpu_others_s"] == rec["cpu_others"]

    def test_a_collection_is_counted_and_is_a_span_with_its_gen(
            self, clock, tmp_path):
        import gc
        before = clock.stats()
        with _profile(tmp_path):
            with obs.span("t.emit"):
                junk = [[i] for i in range(20000)]
                gc.collect()
            clock.tick("decode")
        got = clock.stats()
        assert got["gc_s"] > before["gc_s"]
        # (the junk may set a full collection off by itself before ours)
        assert got["gc_collections"][2] >= before["gc_collections"][2] + 1
        rec = got["longest"]["decode"][0]
        assert 0 < rec["gc"] <= rec["by_phase"]["t.emit"]
        gens = [f["gen"] for name, f, *_ in _annotations(tmp_path)
                if name == "gc"]
        assert 2 in gens
        del junk

    def test_the_collector_is_watched_once(self, clock):
        import gc
        obs.LoopClock("another", KINDS, WAITS, sleep="t.wait")
        assert gc.callbacks.count(obs.spans._on_gc) == 1

    def test_the_heap_keeps_the_longest_eight_longest_first(self, clock):
        naps = [0.001 * k for k in (3, 9, 1, 12, 5, 7, 2, 11, 4, 10, 6, 8)]
        for i, nap in enumerate(naps):
            with obs.span("t.emit"):
                time.sleep(nap)
            clock.tick("decode", step=i)
        got = clock.stats()
        kept = got["longest"]["decode"]
        assert len(kept) == obs.spans.KEPT == 8
        walls = [r["wall"] for r in kept]
        assert walls == sorted(walls, reverse=True)
        # the eight longest naps, whatever the scheduler added to each
        order = sorted(range(len(naps)), key=naps.__getitem__, reverse=True)
        assert {r["step"] for r in kept[:4]} <= set(order[:8])
        assert got["iterations"]["decode"] == len(naps)
        assert got["iteration"]["decode"]["count"] == len(naps)
        assert got["longest"]["prefill"] == got["longest"]["idle"] == []
        assert all(0 <= r["at"] <= got["wall_s"] for r in kept)

    def test_an_iteration_open_across_a_reset_is_dropped(self, clock):
        with obs.span("t.emit"):
            time.sleep(0.02)
        clock.reset()                    # mid-iteration, as a harness does
        with obs.span("t.emit"):
            pass
        clock.tick("decode")
        got = clock.stats()
        assert got["wall_s"] == 0.0 and got["iterations"]["decode"] == 0
        assert got["longest"]["decode"] == []
        with obs.span("t.emit"):
            pass
        clock.tick("decode")
        got = clock.stats()
        assert got["iterations"]["decode"] == 1 and 0 < got["wall_s"] < 0.02

    def test_a_tick_from_another_thread_takes_the_clock_over(self, clock):
        took, stale = threading.Event(), threading.Event()

        def loop():
            clock.tick("decode")         # takes over: the open one is dropped
            took.set()
            assert stale.wait(30.0)
            with obs.span("t.emit"):
                time.sleep(0.005)
            clock.tick("decode")
        with obs.span("t.mine"):         # the old owner's: dropped
            pass
        t = threading.Thread(target=loop)
        t.start()
        assert took.wait(30.0)
        with obs.span("t.stale"):        # no longer the owner: adds nothing
            pass
        assert obs.spans._local.clock is None
        stale.set()
        t.join(30.0)
        assert not t.is_alive()
        got = clock.stats()
        assert got["iterations"]["decode"] == 1
        rec, = got["longest"]["decode"]
        assert set(rec["by_phase"]) == {"t.emit"}

    def test_two_stalls_a_second_apart_print_one_line(self, clock, capfd):
        for step in (41, 42):
            with obs.span("t.emit"):
                time.sleep(obs.spans.STALL_S + 0.05)
            clock.tick("decode", step=step)
        err = capfd.readouterr().err
        lines = [l for l in err.splitlines() if "test loop stalled" in l]
        assert len(lines) == 1, err
        line = lines[0]
        assert line.startswith("[tpu_dist] test loop stalled 0.")
        assert " at step 41 in t.emit: cpu " in line
        for part in ("off-cpu 0.", "gc ", "others' cpu "):
            assert part in line
        assert line.endswith(" involuntary switches") \
            == obs.spans._counts_switches()
        # both are kept, whatever was printed
        assert [r["step"] for r in clock.stats()["longest"]["decode"]] \
            in ([41, 42], [42, 41])

    def test_a_platform_without_switch_counts_pays_and_says_nothing(
            self, monkeypatch, capfd):
        monkeypatch.setattr(obs.spans, "_counts_switches", lambda: False)
        c = obs.LoopClock("bare loop", KINDS, WAITS, sleep="t.wait")
        try:
            c.tick("idle")
            with obs.span("t.emit"):
                time.sleep(obs.spans.STALL_S + 0.05)
            c.tick("decode", step=3)
        finally:
            obs.spans._local.clock = None
        rec, = c.stats()["longest"]["decode"]
        assert not {"switches", "voluntary_switches", "major_faults"} \
            & set(rec)
        line, = [l for l in capfd.readouterr().err.splitlines()
                 if "bare loop stalled" in l]
        assert line.endswith(" s") and "switches" not in line

    def test_the_sums_are_clamped_as_sums(self, clock):
        # a wait that burns CPU (more CPU than wall less the waits) makes
        # one iteration's difference negative: the record clamps it, the
        # window's sum is taken over the sums
        with obs.span("t.readback"):
            _spin(0.03)
        clock.tick("decode")
        with obs.span("t.emit"):
            time.sleep(0.03)
        clock.tick("decode")
        got = clock.stats()
        assert got["offcpu_s"] == pytest.approx(max(
            0.0, got["wall_s"] - got["wait_s"] - got["cpu_s"]))
        assert got["offcpu_s"] <= sum(r["offcpu"]
                                      for r in got["longest"]["decode"])

    def test_a_thread_with_no_clock_is_left_alone(self):
        seen = []
        def work():
            with obs.span("t.free"):
                seen.append(obs.spans._local.clock)
        t = threading.Thread(target=work)
        t.start()
        t.join()
        assert seen == [None]


def test_the_cost_benchmark_measures_a_span_and_a_tick():
    """benchmarks/bench_loop_clock.py at a small count: the three numbers
    exist and are positive; what they are is a chip host's to say."""
    from benchmarks import bench_loop_clock
    obs.spans._local.clock = None
    got = bench_loop_clock.measure(2000)
    assert got["n"] == 2000
    assert all(got[k] > 0 for k in ("span_ns", "span_on_clock_ns",
                                    "tick_ns"))
    assert obs.spans._local.clock is None    # measured on its own thread


class TestServingLoopClock:
    def test_stats_loop_counts_iterations_by_what_they_launched(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        try:
            engine.sweep_expired()           # this thread owns the clock
            engine.reset_stats()
            engine.sweep_expired()           # dropped: open across the reset
            engine.admit(serve.Request(np.arange(4, dtype=np.int32), 4))
            engine.step()                    # a prefill names the iteration
            engine.sweep_expired()
            engine.step()
            engine.sweep_expired()
            engine.step()
            engine.sweep_expired()
            engine.sweep_expired()           # nothing launched: idle
            loop = engine.stats()["loop"]
        finally:
            obs.spans._local.clock = None
        assert loop["iterations"] == {"prefill": 1, "decode": 2, "idle": 1}
        assert loop["covered_s"] + loop["unnamed_s"] == pytest.approx(
            loop["wall_s"], rel=1e-12)
        assert set(loop) == {
            "iterations", "wall_s", "covered_s", "unnamed_s", "wait_s",
            "cpu_s", "cpu_others_s", "offcpu_s", "gc_s", "gc_collections",
            "compile_s", "compiles", "iteration", "longest"}
        pre, = loop["longest"]["prefill"]
        assert {"prefill.dispatch", "prefill.readback", "decode.dispatch",
                "sweep", "stage.put"} <= set(pre["by_phase"])
        # stage.put ran inline, inside prefill.prepare: counted once; and
        # the first dispatches compiled, inside their spans (ISSUE 49)
        top = sum(v for k, v in pre["by_phase"].items()
                  if k not in ("stage.put", "compile"))
        assert pre["compiled"] == ["prefill", "decode"]
        assert pre["wall"] - pre["unnamed"] == pytest.approx(top)
        assert [r["step"] for r in loop["longest"]["decode"]] in ([2, 3],
                                                                  [3, 2])
        assert loop["wait_s"] == pytest.approx(sum(
            r["by_phase"].get(n, 0.0) for k in KINDS
            for r in loop["longest"][k]
            for n in serve.engine.LOOP_WAITS))

    def test_a_pass_that_admits_many_is_an_iteration_a_prefill(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        try:
            engine.sweep_expired()
            # the scheduler's admission loop: a pool's worth between sweeps
            for n in (4, 6, 9):
                engine.launch_admit(serve.Request(
                    np.arange(n, dtype=np.int32), 3))
                engine.settle()
            engine.launch_step()
            engine.settle()
            engine.sweep_expired()
            loop = engine.stats()["loop"]
        finally:
            obs.spans._local.clock = None
        assert loop["iterations"] == {"prefill": 3, "decode": 0, "idle": 0}
        for rec in loop["longest"]["prefill"]:
            assert rec["by_phase"]["prefill.dispatch"] > 0
        # the last one carries the pass's decode step; the first its sweep
        assert sum("decode.dispatch" in r["by_phase"]
                   for r in loop["longest"]["prefill"]) == 1
        assert sum("sweep" in r["by_phase"]
                   for r in loop["longest"]["prefill"]) == 1

    def test_reset_stats_zeroes_the_loop_and_leaves_params(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        try:
            engine.sweep_expired()
            engine.admit(serve.Request(np.arange(4, dtype=np.int32), 3))
            engine.sweep_expired()
            before = engine.stats()
            assert before["loop"]["iterations"]["prefill"] == 1
            engine.reset_stats()
            after = engine.stats()
        finally:
            obs.spans._local.clock = None
        assert after["params"] == before["params"]
        loop = after["loop"]
        assert set(loop["iterations"].values()) == {0}
        assert loop["wall_s"] == loop["gc_s"] == loop["offcpu_s"] == 0.0
        assert loop["gc_collections"] == [0, 0, 0]
        assert all(v == [] for v in loop["longest"].values())
        assert all(h["count"] == 0 for h in loop["iteration"].values())

    def test_two_engines_keep_their_own_books(self, lm):
        model, params = lm
        a = serve.SlotEngine(model, params, num_slots=2)
        b = serve.SlotEngine(model, params, num_slots=2)
        done = []

        def drive(engine, steps):
            engine.sweep_expired()
            engine.admit(serve.Request(np.arange(5, dtype=np.int32),
                                       steps + 1))
            engine.sweep_expired()
            while not engine.idle():
                engine.step()
                engine.sweep_expired()
            done.append(engine)
        threads = [threading.Thread(target=drive, args=(e, n))
                   for e, n in ((a, 3), (b, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert len(done) == 2
        assert a.stats()["loop"]["iterations"]["decode"] == 3
        assert b.stats()["loop"]["iterations"]["decode"] == 6

    def test_prefill_attn_counts_reset_and_ride_the_wire(self, lm):
        """``stats()["prefill_attn"]`` for a model without a latent layer:
        its prefills counted, none on the kernel, no pairs (the counts of a
        latent model on either branch: tests/test_kimi_k2.py)."""
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        zero = {"prefills": 0, "kernel_prefills": 0, "pairs_needed": 0,
                "pairs_executed": 0}
        try:
            assert cli.stats()["prefill_attn"] == zero
            for n in (6, 11, 30):
                cli.generate(list(range(1, n)), max_new_tokens=2,
                             timeout=120.0)
            assert cli.stats()["prefill_attn"] == dict(zero, prefills=3)
            assert engine.stats()["prefill_attn"] == dict(zero, prefills=3)
            engine.reset_stats()
            assert cli.stats()["prefill_attn"] == zero
        finally:
            cli.close()
            fe.close()
            sched.close()

    def test_prefill_scan_counts_reset_and_ride_the_wire(self, lm):
        """``stats()["prefill_scan"]`` (ISSUE 42) for a model without a
        recurrent layer: its prefills counted, none on the scan kernel (the
        counts of a hybrid model on either branch: tests/test_qwen3_next.py,
        tests/test_kimi_linear.py)."""
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        zero = {"prefills": 0, "kernel_prefills": 0}
        try:
            assert cli.stats()["prefill_scan"] == zero
            for n in (6, 30):
                cli.generate(list(range(1, n)), max_new_tokens=2,
                             timeout=120.0)
            assert cli.stats()["prefill_scan"] == dict(zero, prefills=2)
            assert engine.stats()["prefill_scan"] == dict(zero, prefills=2)
            engine.reset_stats()
            assert cli.stats()["prefill_scan"] == zero
        finally:
            cli.close()
            fe.close()
            sched.close()

    def test_the_wire_stats_frame_carries_the_loop_as_json(self, lm):
        import json
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            cli.generate(list(range(1, 9)), max_new_tokens=3, timeout=120.0)
            time.sleep(0.05)
            engine.reset_stats()
            for n in (6, 11):
                cli.generate(list(range(1, n)), max_new_tokens=5,
                             timeout=120.0)
            time.sleep(0.12)
            stats = cli.stats()
            local = engine.stats()["loop"]
        finally:
            cli.close()
            fe.close()
            sched.close()
        loop = stats["loop"]
        assert json.loads(json.dumps(local)) == local
        assert loop["iterations"]["prefill"] == 2
        assert loop["iterations"]["decode"] >= 4
        assert loop["iterations"]["idle"] >= 1
        assert loop["covered_s"] + loop["unnamed_s"] == pytest.approx(
            loop["wall_s"], rel=1e-9)
        # the loop thread's whole time since the reset, but for the
        # iteration open when the frame was read
        assert 0 < loop["wall_s"] and loop["unnamed_s"] < 0.5 * loop["wall_s"]
        assert loop["iteration"]["decode"]["count"] \
            == loop["iterations"]["decode"]
        # an idle iteration is its sleep: the histogram leaves it out
        assert loop["iteration"]["idle"]["p50"] < 0.05
        for kind, kept in loop["longest"].items():
            assert len(kept) <= 8
            for r in kept:
                assert set(r) - {"switches", "voluntary_switches",
                                 "major_faults"} == {
                    "step", "at", "wall", "phase", "by_phase", "unnamed",
                    "wait", "cpu", "cpu_others", "offcpu", "gc"}


# -- scopes on the compiled path -----------------------------------------------

def _ddp(compute_dtype=jnp.bfloat16):
    if not dist.is_initialized():
        dist.init_process_group()
    model = TransformerLM(vocab_size=64, dim=32, depth=2, num_heads=2,
                          max_seq_len=16)
    return DistributedDataParallel(
        model, optimizer=optim.AdamW(lr=1e-2),
        loss_fn=nn.CrossEntropyLoss(), compute_dtype=compute_dtype)


def _batch(n=8):
    rng = np.random.default_rng(11)
    x = rng.integers(0, 64, (n, 16)).astype(np.int32)
    return x, np.roll(x, -1, axis=1)


def _scopes(lowered) -> set:
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


class TestScopes:
    def test_training_step_names_module_direction_and_phase(self):
        ddp = _ddp()
        state = ddp.init(seed=0)
        names = _scopes(ddp._build_train_step(state).lower(state, *_batch()))
        has = lambda part: any(part in n for n in names)
        for part in ("jvp(block0)/attn/", "transpose(jvp(block0))/attn/",
                     "jvp(block1)/mlp/1/", "transpose(jvp(block1))/ln2/",
                     "jvp(ln_f)/", "jvp(head)/", "jvp(cast_params)/",
                     "jvp(loss)/", "transpose(jvp(loss))/",
                     "grad_reduce/", "optimizer/"):
            assert has(part), part
        # the optimizer and the reduction are outside the differentiated
        # function: no direction wraps them
        assert not has("jvp(optimizer)") and not has("jvp(grad_reduce)")

    def test_train_dispatch_span_counts_steps_from_a_host_counter(self,
                                                                  tmp_path):
        ddp = _ddp()
        state = ddp.init(seed=0)
        x, y = _batch()
        obs.reset_phases(["train.dispatch"])
        state, _ = ddp.train_step(state, x, y)
        with _profile(tmp_path):
            for _ in range(2):
                state, m = ddp.train_step(state, x, y)
            xs, ys = np.stack([x, x, x]), np.stack([y, y, y])
            state, m = ddp.train_chunk(state, xs, ys)
            jax.block_until_ready(m["loss"])
        assert _counts(["train.dispatch"]) == {"train.dispatch": 4}
        fields = [f for name, f, *_ in _annotations(tmp_path)
                  if name == "train.dispatch"]
        assert fields == [{"step": 1}, {"step": 2}, {"step": 3, "steps": 3}]
        assert ddp._dispatched == 6

    def test_first_train_dispatch_span_carries_the_update_plan(self,
                                                               tmp_path):
        """What the weight update shards rides the first dispatch's span,
        once; ``update_plan()`` gives the same facts to the host."""
        ddp = _ddp()
        state = ddp.init(seed=0)
        x, y = _batch()
        with _profile(tmp_path):
            for _ in range(2):
                state, m = ddp.train_step(state, x, y)
            jax.block_until_ready(m["loss"])
        first, second = [f for name, f, *_ in _annotations(tmp_path)
                         if name == "train.dispatch"]
        plan = ddp.update_plan()
        assert first == dict(plan, step=0) and second == {"step": 1}
        assert plan["world"] == len(jax.devices())
        assert plan["sharded_leaves"] + plan["whole_leaves"] == len(
            jax.tree.leaves(state.params))

    def test_serving_programs_name_their_scopes(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        decode = _scopes(engine._decode.lower(
            engine.params, engine.cache, engine._moe["decode"],
            engine._slots, engine.active, False))
        has = lambda names, part: any(part in n for n in names)
        for part in ("decode/block0/attn/cache_update/",
                     "decode/block0/attn/attend/", "decode/block1/mlp/1/",
                     "decode/ln_f/", "decode/head/", "/sample"):
            assert has(decode, part), part
        prefill = _scopes(engine._prefill.lower(
            engine.params, engine.cache, engine._moe["prefill"],
            engine._slots, np.zeros(16, np.int32), np.int32(5), np.int32(0),
            np.float32(0), np.zeros(2, np.uint32), False))
        for part in ("prefill/block0/attn/cache_update/",
                     "prefill/block0/attn/attend/", "prefill/cache_write/",
                     "/sample"):
            assert has(prefill, part), part

    def test_outputs_are_bitwise_equal_without_the_scopes(self, lm,
                                                          monkeypatch):
        def run():
            ddp = _ddp()
            state = ddp.init(seed=0)
            losses = []
            for _ in range(2):
                state, m = ddp.train_step(state, *_batch())
                losses.append(np.asarray(m["loss"]))
            model, params = lm
            engine = serve.SlotEngine(model, params, num_slots=2)
            toks = []
            engine.admit(serve.Request(
                np.arange(1, 8, dtype=np.int32), 6,
                on_token=lambda r, t: toks.append(t)))
            while not engine.idle():
                engine.step()
            return (losses, jax.tree.map(np.asarray, state.params), toks,
                    jax.tree.map(np.asarray, engine.cache))

        with_scopes = run()
        entered = []

        def no_scope(name):
            entered.append(name)
            return contextlib.nullcontext()

        monkeypatch.setattr(jax, "named_scope", no_scope)
        without = run()
        assert {"block0", "attn", "optimizer", "grad_reduce", "loss",
                "decode", "attend", "cache_update", "cache_write"} \
            <= set(entered)
        a, b = jax.tree.leaves(with_scopes), jax.tree.leaves(without)
        assert len(a) == len(b)
        for u, v in zip(a, b):
            np.testing.assert_array_equal(u, v)
