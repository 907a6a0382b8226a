"""The compile ledger (ISSUE 49): tpu_dist.obs.compiles joins JAX's three
stage events and its persistent-cache events into one record a program, with
the ``td/`` span and the loop iteration the program fell in; the loop clock
learns of a compile; ``SlotEngine.stats()["compiles"]``.

The listeners are installed once a process (``ensure_compile_cache()``) and
the tests of one worker share them: no test clears ``jax.monitoring``.  Each
test builds FRESH functions under names of its own and reads the records of
those names.  A CPU run checks names, counts and that seconds add up, never a
time on a device.
"""

import importlib
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

from tpu_dist import obs, serve
from tpu_dist.models import TransformerLM
from tpu_dist.obs import compiles as read_compiles
from tpu_dist.obs.compiles import (KEPT_RECORDS, TOTALS, _on_duration,
                                   _on_event, longest, totals, totals_since)
from tpu_dist.utils import ensure_compile_cache

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def ledger():
    ensure_compile_cache()      # as every entry path does first


def _named(fn, name):
    """``fn`` under a name no other test compiles."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _records(name, **interval):
    return [r for r in read_compiles(**interval)["records"]
            if r["name"] == name]


def _seconds(r):
    return r["trace_s"] + r["lower_s"] + r["backend_s"]


def test_import_registers_nothing_and_two_installs_leave_one_set():
    """``import tpu_dist`` is not what listens: only ensure_compile_cache()
    is, however often it is called."""
    ours = lambda fns: [f for f in fns
                        if getattr(f, "__module__", "") ==
                        "tpu_dist.obs.compiles"]
    ensure_compile_cache()
    ensure_compile_cache()
    assert len(ours(monitoring.get_event_duration_listeners())) == 1
    assert len(ours(monitoring.get_event_listeners())) == 1
    assert len(ours(monitoring.get_scalar_listeners())) == 1
    f = jax.jit(_named(lambda x: x * 3 + 1, "t_one_pair"))
    x = jnp.ones(3)             # (an eager program or two of its own)
    before = totals()["programs"]
    f(x)
    assert len(_records("t_one_pair")) == 1     # one record a compile
    assert totals()["programs"] == before + 1


def test_a_fresh_function_adds_one_record_and_its_second_call_none():
    f = jax.jit(_named(lambda x: jnp.tanh(x) @ x, "t_fresh"))
    x = jnp.ones((4, 4))
    t0 = time.monotonic()
    before = totals()
    f(x)
    mid = totals()
    f(x)
    t1 = time.monotonic()
    (r,) = _records("t_fresh")
    assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
    assert t0 <= r["at"] <= t1
    assert r["cache"] in ("hit", "miss", "off") and r["span"] == ""
    assert "step" not in r
    assert totals() == mid                      # the second call: nothing
    added = totals_since(before)
    assert added["programs"] == 1
    assert added["trace_s"] + added["lower_s"] + added["backend_s"] \
        == pytest.approx(_seconds(r))
    # a new shape is a new program under the same name
    f(jnp.ones((5, 5)))
    assert len(_records("t_fresh")) == 2
    # and the interval cuts on the instant the backend stage ended
    assert _records("t_fresh", since=t0, until=t1) == [r]
    assert _records("t_fresh", until=t0) == []


@pytest.fixture
def temporary_cache(tmp_path):
    """A persistent cache of this test's own that keeps everything."""
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    before = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), 0, -1, True)):
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    yield tmp_path
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_a_miss_then_a_hit_with_its_retrieval(temporary_cache):
    f = jax.jit(_named(lambda x: jnp.cumsum(x * 7), "t_cached"))
    x = jnp.ones(6)
    before = totals()
    f(x)
    (miss,) = _records("t_cached")
    assert (miss["cache"], miss["kept"]) == ("miss", 1)
    assert miss["retrieval_s"] == 0
    jax.clear_caches()
    f(x)
    hit, = [r for r in _records("t_cached") if r != miss]
    assert (hit["cache"], hit["kept"]) == ("hit", 0)
    assert hit["retrieval_s"] > 0 and hit["backend_s"] >= hit["retrieval_s"]
    added = totals_since(before)
    assert (added["hits"], added["misses"], added["kept"]) == (1, 1, 1)
    assert added["retrieval_s"] == pytest.approx(hit["retrieval_s"])
    got = read_compiles()
    assert got["cache_dir"] == str(temporary_cache)
    assert got["cache_entries"] >= 1 and got["cache_bytes"] > 0
    assert got["cache_max_bytes"] == -1


def test_the_cache_not_asked_reads_off(temporary_cache):
    jax.config.update("jax_enable_compilation_cache", False)
    f = jax.jit(_named(lambda x: x - 2, "t_cache_off"))
    x = jnp.ones(3)
    before = totals()
    f(x)
    (r,) = _records("t_cache_off")
    assert r["cache"] == "off"
    assert totals_since(before)["off"] == 1


def test_an_inner_functions_trace_is_kept_beside_and_counted_once():
    inner = jax.jit(_named(lambda x: jnp.sum(x * 2), "t_inner"))

    def outer(x):
        return inner(x) + inner(x + 1) + jnp.matmul(x, x).sum()

    f = jax.jit(_named(outer, "t_outer"))
    x = jnp.ones((4, 4))
    before = totals()
    f(x)
    (r,) = _records("t_outer")
    assert 0 < r["inner_trace_s"] <= r["trace_s"]
    assert _records("t_inner") == []        # no program of its own
    added = totals_since(before)
    assert added["programs"] == 1
    assert added["trace_s"] == pytest.approx(r["trace_s"])  # the outer's, once


def test_a_program_built_inside_a_trace_is_taken_out_of_it():
    def outer(x):
        with jax.ensure_compile_time_eval():
            k = jax.jit(_named(lambda n: jnp.arange(n.shape[0]) * 2,
                               "t_eager_inside"))(np.zeros(5))
        return x + k.sum()

    f = jax.jit(_named(outer, "t_around"))
    x = jnp.ones(())
    before = totals()
    t0 = time.perf_counter()
    f(x)
    wall = time.perf_counter() - t0
    (inside,) = _records("t_eager_inside")
    (around,) = _records("t_around")
    added = totals_since(before)
    assert added["programs"] >= 2
    # wall seconds once: the stages of everything built fit in the call
    assert added["trace_s"] + added["lower_s"] + added["backend_s"] <= wall
    assert around["trace_s"] + _seconds(inside) <= wall


def test_two_threads_compiling_at_once_do_not_cross_their_stages():
    """Stages are joined per thread: each program gets its own three, under
    its own name, whatever the other thread fires between them."""
    barrier = threading.Barrier(2)

    def work(name, pause):
        def body(x):
            barrier.wait(10.0)      # both are mid-trace at once
            time.sleep(pause)       # a's three stages end inside b's trace
            return jnp.sin(x) @ x
        jax.jit(_named(body, name))(x)

    threads = [threading.Thread(target=work, args=a)
               for a in (("t_thread_a", 0.0), ("t_thread_b", 0.5))]
    x = jnp.ones((8, 8))
    before = totals()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (a,) = _records("t_thread_a")
    (b,) = _records("t_thread_b")
    for r in (a, b):
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
    assert b["trace_s"] >= 0.5 > a["trace_s"]
    assert a["inner_trace_s"] < 0.5 and b["inner_trace_s"] < 0.5
    assert totals_since(before)["programs"] == 2


def test_the_records_stay_bounded_and_the_totals_exact():
    """300 distinct programs MORE than the worker's ledger holds, through the
    listeners themselves (a CPU compile of each would take minutes; stages
    of well under a microsecond, so that none reaches back over the one
    before it), at a bound of 100 more than it holds: every older record
    goes and 200 of the new, the deque holds its bound, the totals hold
    every one, and an interval that lost records says so."""
    # the module by name: ``obs.compiles`` is the function that shadows it
    ledger = importlib.import_module("tpu_dist.obs.compiles")
    before = totals()
    kept = ledger._records
    assert len(kept) <= KEPT_RECORDS and KEPT_RECORDS >= 1000
    many = len(kept) + 300
    t0 = time.monotonic()
    try:
        ledger.KEPT_RECORDS = len(kept) + 100
        for i in range(many):
            _on_duration(ledger._TRACE, 1e-8, fun_name=f"t_many_{i}")
            _on_duration(ledger._LOWER, 2e-8, fun_name=f"jit(t_many_{i})")
            _on_event(ledger._ASKED)
            _on_duration(ledger._BACKEND, 4e-8, fun_name=f"jit(t_many_{i})")
        assert len(kept) == ledger.KEPT_RECORDS
        added = totals_since(before)
        assert (added["programs"], added["misses"]) == (many, many)
        assert added["trace_s"] == pytest.approx(many * 1e-8, rel=1e-3)
        assert added["lower_s"] == pytest.approx(many * 2e-8, rel=1e-3)
        assert added["backend_s"] == pytest.approx(many * 4e-8, rel=1e-3)
        got = read_compiles(since=t0)
        # the newest, fewer than were built: the sum says it is short
        assert got["truncated"]
        assert got["programs"] == ledger.KEPT_RECORDS == many - 200
        assert {r["name"] for r in got["records"]} == {
            f"t_many_{i}" for i in range(200, many)}
        assert not read_compiles(since=time.monotonic())["truncated"]
        whole = read_compiles()
        assert whole["truncated"]
        assert whole["programs"] == totals()["programs"]    # exact all the same
        assert len(whole["records"]) == ledger.KEPT_RECORDS
    finally:
        ledger.KEPT_RECORDS = KEPT_RECORDS


def test_the_cost_benchmark_measures_the_listeners_and_a_second_install():
    """benchmarks/bench_loop_clock.py ``measure_ledger`` at a small count:
    both numbers exist and are positive, and its programs are on the ledger
    under its own name; what they are is a chip host's to say."""
    from benchmarks import bench_loop_clock
    before = totals()["programs"]
    got = bench_loop_clock.measure_ledger(20)
    assert got["n"] == 20
    assert got["program_ns"] > 0 and got["ensure_compile_cache_again_ns"] > 0
    built = totals()["programs"] - before
    assert built >= 20 and built % 20 == 0      # whole repeats of the loop
    assert {r["cache"] for r in _records("bench.cost")} == {"miss"}


# -- the loop clock learns of a compile ------------------------------------------

@pytest.fixture
def clock():
    c = obs.LoopClock("test loop", ("decode", "idle"), ("t.wait",),
                      sleep="t.wait")
    c.tick("idle")
    yield c
    obs.spans._local.clock = None


def test_a_compile_lands_in_its_iteration_and_in_the_stall_line(
        clock, capfd, monkeypatch):
    monkeypatch.setattr(obs.spans, "STALL_S", 0.0)
    f = jax.jit(_named(lambda x: jnp.exp(x) * 5, "t_on_the_loop"))
    with obs.span("t.dispatch"):
        f(jnp.ones(3))
    clock.tick("decode", step=8812)
    with obs.span("t.dispatch"):
        f(jnp.ones(3))              # compiled already: nothing
    clock.tick("decode", step=8813)
    (r,) = _records("t_on_the_loop")
    assert r["span"] == "t.dispatch" and r["step"] == 8812
    got = clock.stats()
    assert got["compiles"] == 1
    assert got["compile_s"] == pytest.approx(_seconds(r))
    assert got["covered_s"] + got["unnamed_s"] == pytest.approx(
        got["wall_s"], rel=1e-12)
    first, second = sorted(got["longest"]["decode"], key=lambda i: i["step"])
    assert first["compiled"] == ["t_on_the_loop"]
    assert first["by_phase"]["compile"] == pytest.approx(_seconds(r))
    # inside the span it fell in, so never the iteration's phase, and never
    # in ``covered``
    assert first["phase"] == "t.dispatch"
    assert first["by_phase"]["t.dispatch"] >= first["by_phase"]["compile"]
    assert first["wall"] - first["unnamed"] == pytest.approx(
        first["by_phase"]["t.dispatch"])
    assert "compiled" not in second and "compile" not in second["by_phase"]
    lines = [l for l in capfd.readouterr().err.splitlines()
             if "test loop stalled" in l]
    assert len(lines) == 1
    assert " at step 8812 in t.dispatch: " in lines[0]
    assert lines[0].endswith(
        f", compiling t_on_the_loop {_seconds(r):.3g} s (cache {r['cache']})")


def test_an_iteration_dropped_by_a_reset_leaves_its_record_without_a_step(
        clock):
    f = jax.jit(_named(lambda x: x / 3, "t_dropped"))
    f(jnp.ones(3))
    clock.reset()
    clock.tick("decode", step=5)
    g = jax.jit(_named(lambda x: x / 5, "t_not_dropped"))
    g(jnp.ones(3))
    clock.tick("decode", step=6)
    assert "step" not in _records("t_dropped")[0]
    assert _records("t_not_dropped")[0]["step"] == 6
    assert clock.stats()["compiles"] == 1


def test_a_compile_on_a_thread_without_a_clock_tells_no_clock(clock):
    def work():
        jax.jit(_named(lambda x: x * 11, "t_elsewhere"))(jnp.ones(3))
    t = threading.Thread(target=work)
    t.start()
    t.join()
    clock.tick("decode", step=1)
    assert "step" not in _records("t_elsewhere")[0]
    assert clock.stats()["compiles"] == 0
    assert clock.stats()["compile_s"] == 0.0


# -- the set-up phases of the training path ----------------------------------------

def test_the_training_path_names_its_set_up_and_its_steps_program():
    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.parallel import DistributedDataParallel

    names = ("setup.devices", "setup.init_state")
    count = lambda: {n: s["count"] for n, s in obs.phase_times(names).items()}
    was = dist.is_initialized()     # left as found for the worker's others
    if was:
        dist.destroy_process_group()
    before = count()
    pg = dist.init_process_group()
    try:
        assert count()["setup.devices"] == before["setup.devices"] + 1
        ddp = DistributedDataParallel(
            TransformerLM(vocab_size=83, dim=24, depth=2, num_heads=2,
                          max_seq_len=32),
            optimizer=optim.AdamW(lr=1e-3), loss_fn=nn.CrossEntropyLoss(),
            group=pg)
        t0 = time.monotonic()
        state = ddp.init(seed=0)
        assert count()["setup.init_state"] == before["setup.init_state"] + 1
        # a model's init runs eagerly: its programs fall in the phase
        built = read_compiles(since=t0)
        assert built["programs"] >= 1
        assert {r["span"] for r in built["records"]} == {"setup.init_state"}
        x = np.zeros((8, 16), np.int32)
        t1 = time.monotonic()
        ddp.train_step(state, x, x)
    finally:
        if not was:
            dist.destroy_process_group()
    (step,) = _records("local_step", since=t1)
    assert step["span"] == "train.dispatch" and "step" not in step
    assert step["trace_s"] > step["inner_trace_s"] > 0
    assert step["lower_s"] > 0 and step["backend_s"] > 0


# -- the serving engine ------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    # a width no other test module of this worker serves: the pool
    # programs below are compiled here
    model = TransformerLM(vocab_size=89, dim=24, depth=2, num_heads=2,
                          max_seq_len=64)
    return model, model.init(jax.random.key(0))


def test_an_engines_first_prefill_is_filed_under_prefill_dispatch(lm):
    model, params = lm
    t0 = time.monotonic()
    engine = serve.SlotEngine(model, params, num_slots=3)
    try:
        engine.sweep_expired()          # this thread owns the clock
        engine.admit(serve.Request(np.arange(1, 5, dtype=np.int32), 3))
        engine.step()
        engine.sweep_expired()
        stats = engine.stats()
    finally:
        obs.spans._local.clock = None
    (pre,) = _records("prefill", since=t0)
    (dec,) = _records("decode", since=t0)
    assert pre["span"] == "prefill.dispatch"
    assert dec["span"] == "decode.dispatch"
    assert pre["step"] == dec["step"] == 1
    loop = stats["loop"]
    assert loop["compiles"] == 2
    assert loop["compile_s"] == pytest.approx(_seconds(pre) + _seconds(dec))
    (it,) = loop["longest"]["prefill"]
    assert it["compiled"] == ["prefill", "decode"]
    assert loop["covered_s"] + loop["unnamed_s"] == pytest.approx(
        loop["wall_s"], rel=1e-12)
    # the set-up phases are samples of the span that exists, and
    # reset_stats() leaves them
    names = ("setup.place_params", "setup.init_cache", "setup.build_programs")
    counts = {n: s["count"] for n, s in obs.phase_times(names).items()}
    assert all(c >= 1 for c in counts.values())
    engine.reset_stats()
    assert {n: s["count"] for n, s in obs.phase_times(names).items()} \
        == counts


def test_stats_compiles_keeps_the_process_and_counts_since_the_reset(lm):
    model, params = lm
    engine = serve.SlotEngine(model, params, num_slots=3, min_bucket=8)
    try:
        engine.sweep_expired()
        engine.admit(serve.Request(np.arange(1, 5, dtype=np.int32), 3))
        engine.step()
        engine.sweep_expired()          # warmed: the bucket of 8, decode
        before = engine.stats()["compiles"]
        engine.reset_stats()
        warm = engine.stats()["compiles"]
        engine.admit(serve.Request(np.arange(1, 6, dtype=np.int32), 3))
        engine.step()
        engine.sweep_expired()
        same = engine.stats()["compiles"]
        # a prompt of a bucket nobody warmed: the server retraces
        engine.admit(serve.Request(np.arange(1, 20, dtype=np.int32), 3))
        engine.step()
        engine.sweep_expired()
        stats = engine.stats()
    finally:
        obs.spans._local.clock = None
    assert before["programs"] >= 2 and len(before["longest"]) >= 2
    assert {k: warm[k] for k in TOTALS} == {k: before[k] for k in TOTALS}
    assert warm["since_reset"]["programs"] == 0
    assert warm["since_reset"]["longest"] == []
    assert same["since_reset"]["programs"] == 0     # a warmed bucket
    after = stats["compiles"]
    assert after["since_reset"]["programs"] >= 1
    assert after["programs"] == before["programs"] \
        + after["since_reset"]["programs"]
    assert "prefill" in [r["name"] for r in after["since_reset"]["longest"]]
    assert stats["loop"]["compiles"] >= 1
    assert stats["loop"]["compile_s"] > 0
    assert set(after) == set(TOTALS) | {"longest", "since_reset"}
    assert len(after["longest"]) <= obs.spans.KEPT
    assert after["longest"] == longest(obs.spans.KEPT)
    json.dumps(stats)                   # it rides the wire frame
    json.dumps(read_compiles())
