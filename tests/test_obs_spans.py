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
        assert {phases[n]["count"] for n in PREFILL} == {10}
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
        assert all({"req", "slot", "bucket"} <= set(f)
                   for f, *_ in by_name["prefill.dispatch"])
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
