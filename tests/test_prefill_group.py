"""Several prompts in one prefill program (ISSUE 48).

A prefill program of a bucket takes ``P`` prompts, ``P`` read off the pool's
shapes (``SlotEngine.prefill_width``), and reads every layer's weights once
for all of them; a row of length 0 is an absent prompt.  The scheduler hands
the oldest held request and the held requests of its bucket a program
together, and lets free slots wait for company only while company is queued
and only for ``num_slots`` idle slot-steps.

1. the grouped program against ``P`` single prefills, a small model of each
   family the engine serves;
2. an absent row touches no slot and is nobody's routed row;
3. the width, from ``(bucket, max_len)`` alone;
4. the scheduler's rule, over an engine whose two programs are stubs.
"""

import importlib
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_dist import serve
from tpu_dist.models import TransformerLM
from tpu_dist.serve.engine import pool_programs

pytestmark = pytest.mark.serve

SLOTS, MAX_LEN, BUCKET, WIDTH = 6, 64, 16, 4
LENGTHS = [5, 16, 9, 12]        # the group's true lengths, all in one bucket
INTO = [4, 0, 3, 1]             # and the slots they land in
# float32 on a CPU: a row of a batch of four and the same row alone differ
# by the order a matmul sums in, no more (each family's own prefill test
# holds its reference to 2e-5)
ATOL = 2e-5


def _dense():
    return TransformerLM(97, dim=32, depth=2, num_heads=2, max_seq_len=128)


def _routed():
    return TransformerLM(97, dim=32, depth=2, num_heads=2, max_seq_len=128,
                         num_experts=4, moe_top_k=2, moe_dispatch="dropless")


def _of(module):
    """The small model a family's own test file serves."""
    return lambda: importlib.import_module(module)._model()


FAMILIES = {
    "dense": _dense, "moe": _routed, "olmoe": _of("test_olmoe"),
    "qwen3next": _of("test_qwen3_next"), "kimik2": _of("test_kimi_k2"),
    "xing4": _of("test_xing4"), "kimilinear": _of("test_kimi_linear"),
    "falconh1": _of("test_falcon_h1"), "lfm2moe": _of("test_lfm2_moe"),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    """``(model, params, prefill)``: the pool program as served, jitted."""
    model = FAMILIES[request.param]()
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim == 1 else a, model.init(jax.random.key(3)))
    _, prefill = pool_programs(model)
    return model, params, jax.jit(prefill, static_argnums=(9,))


def _fresh(model, seed=0):
    """A pool, its counters and its slot state, every slot holding
    something: what an admission must leave alone is not zeros."""
    pool = model.init_slot_cache(SLOTS, MAX_LEN)
    leaves, tree = jax.tree.flatten(pool)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    pool = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape).astype(a.dtype)
        for a, k in zip(leaves, keys)])
    slots = {"tokens": jnp.arange(SLOTS, dtype=jnp.int32) + 7,
             "lengths": jnp.full(SLOTS, 3, jnp.int32),
             "steps": jnp.full(SLOTS, 2, jnp.int32),
             "temps": jnp.zeros(SLOTS, jnp.float32),
             "keys": jnp.zeros((SLOTS, 2), jnp.uint32)}
    return pool, model.init_moe_counters(), slots


def _prompts(model):
    rng = np.random.default_rng(11)
    return rng.integers(1, model.vocab_size, (WIDTH, BUCKET)).astype(np.int32)


def _call(prefill, params, state, prompts, rows):
    """The program over the prompts ``rows`` (indices into the group), the
    rest of its width absent."""
    pool, counters, slots = state
    n = len(rows)
    tokens = np.zeros((WIDTH, BUCKET), np.int32)
    lengths, into = np.zeros(WIDTH, np.int32), np.zeros(WIDTH, np.int32)
    tokens[:n] = prompts[rows]
    lengths[:n] = np.asarray(LENGTHS)[rows]
    into[:n] = np.asarray(INTO)[rows]
    toks, pool, counters, slots = prefill(
        params, pool, counters, slots, tokens, lengths, into,
        np.zeros(WIDTH, np.float32), np.zeros((WIDTH, 2), np.uint32), False)
    return np.asarray(toks)[:n], (pool, counters, slots)


def _close(a, b, atol=ATOL):
    for (path, x), (_, y) in zip(jax.tree.leaves_with_path(a),
                                 jax.tree.leaves_with_path(b)):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), rtol=0,
            atol=atol, err_msg=jax.tree_util.keystr(path))


def _same(a, b):
    for (path, x), (_, y) in zip(jax.tree.leaves_with_path(a),
                                 jax.tree.leaves_with_path(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jax.tree_util.keystr(path))


def _request_rows(counters):
    """The counters that count requests' rows, summed over the layers."""
    return {k: int(sum(np.asarray(c[k]).sum() for c in counters.values()))
            for k in ("rows", "held_rows", "pad_rows")} if counters else {}


# -- 1. a group against P single prefills --------------------------------------

def test_a_group_is_its_prompts_prefilled_one_by_one(family):
    """Each slot's cache rows and state, the first sampled token and the
    slot's row of the device state are what ``P`` programs of one present
    prompt each leave; and the requests' routed rows count the same."""
    model, params, prefill = family
    prompts = _prompts(model)
    together, grouped = _call(prefill, params, _fresh(model), prompts,
                              [0, 1, 2, 3])
    state, alone = _fresh(model), []
    for i in range(WIDTH):
        tok, state = _call(prefill, params, state, prompts, [i])
        alone.append(int(tok[0]))
    assert together.tolist() == alone
    _close(grouped[0], state[0])
    _same(grouped[2], state[2])
    ours, theirs = _request_rows(grouped[1]), _request_rows(state[1])
    assert [ours.get(k) for k in ("rows", "held_rows")] == \
        [theirs.get(k) for k in ("rows", "held_rows")]
    # the slots' rows as an admission leaves them
    lengths = np.asarray(grouped[2]["lengths"])
    assert lengths[INTO].tolist() == LENGTHS
    assert np.asarray(grouped[2]["steps"])[INTO].tolist() == [1] * WIDTH
    assert np.asarray(grouped[2]["tokens"])[INTO].tolist() == alone


def test_a_groups_logits_are_the_single_prefills(family):
    """``prefill_into_slot`` with ``(P, S)`` prompts gives each row the
    logits at its own last real token that the one-prompt call gives, and
    the same pool."""
    model, params, _ = family
    prompts = _prompts(model)
    pool, counters, _ = _fresh(model)
    run = jax.jit(model.prefill_into_slot)
    logits, grouped, _ = run(params, prompts, np.asarray(LENGTHS, np.int32),
                             np.asarray(INTO, np.int32), pool, counters)
    assert logits.shape == (WIDTH, model.vocab_size)
    one = pool
    for i in range(WIDTH):
        row, one, _ = run(params, prompts[i], np.int32(LENGTHS[i]),
                          np.int32(INTO[i]), one, counters)
        np.testing.assert_allclose(logits[i], row, rtol=0, atol=ATOL)
    _close(grouped, one)


def test_a_wide_group_with_absent_rows_is_its_prompts_one_by_one():
    """Eight prompts of a 16 bucket in a 128 pool, two of them absent,
    against one by one; the slots nobody was given are bit-identical, the
    one an absent row names among them."""
    model = _dense()
    params = model.init(jax.random.key(1))
    width = 8
    pool = jax.tree.map(lambda a: a + 1.0, model.init_slot_cache(10, 128))
    rng = np.random.default_rng(3)
    prompts = rng.integers(1, 97, (width, 16)).astype(np.int32)
    lengths = np.asarray([5, 16, 0, 9, 12, 3, 0, 7], np.int32)
    into = np.asarray([9, 0, 4, 3, 1, 8, 4, 6], np.int32)
    run = jax.jit(model.prefill_into_slot)
    logits, grouped, _ = run(params, prompts, lengths, into, pool, None)
    one = pool
    for i in np.flatnonzero(lengths):
        row, one, _ = run(params, prompts[i], lengths[i], into[i], one, None)
        np.testing.assert_allclose(logits[i], row, rtol=0, atol=ATOL)
    _close(grouped, one)
    kept = lambda tree: jax.tree.map(lambda a: np.asarray(a)[[2, 4, 5, 7]],
                                     tree)
    _same(kept(grouped), kept(pool))


def test_a_prefill_lands_its_buckets_columns_and_no_more():
    """The rows are built at the bucket's extent, not the pool's: a slot's
    columns past the bucket keep what its last request left (every decode
    step masks them), the other slots everything."""
    import tpu_dist.nn as nn
    model = _dense()
    params = model.init(jax.random.key(1))
    pool, _, _ = _fresh(model)
    before = jax.tree.map(np.asarray, pool)
    prompts = _prompts(model)
    _, after, _ = jax.jit(model.prefill_into_slot)(
        params, prompts[:1], np.asarray(LENGTHS[:1], np.int32),
        np.asarray([2], np.int32), pool, None)
    for path, entry in after.items():
        for name, leaf in entry.items():
            assert nn.cache.is_timed(name), (path, name)
            was, now = before[path][name], np.asarray(leaf)
            np.testing.assert_array_equal(now[[0, 1, 3, 4, 5]],
                                          was[[0, 1, 3, 4, 5]])
            np.testing.assert_array_equal(
                nn.cache.time_slice(now[2:3], BUCKET, MAX_LEN),
                nn.cache.time_slice(was[2:3], BUCKET, MAX_LEN))
            assert not np.array_equal(
                nn.cache.time_slice(now[2:3], 0, BUCKET),
                nn.cache.time_slice(was[2:3], 0, BUCKET))


# -- 2. an absent row -----------------------------------------------------------

def test_an_absent_row_touches_no_slot_and_counts_for_no_request(family):
    """One present prompt among three absent ones: every slot but its own is
    bit-identical, on the device state too, and the counters gain its
    picks alone (the absent rows' are ``pad_rows``)."""
    model, params, prefill = family
    prompts = _prompts(model)
    before = _fresh(model)
    kept = jax.tree.map(np.asarray, before)
    _, after = _call(prefill, params, before, prompts, [2])
    others = [s for s in range(SLOTS) if s != INTO[2]]
    _same(jax.tree.map(lambda a: a[others], kept[0]),
          jax.tree.map(lambda a: np.asarray(a)[others], after[0]))
    _same(jax.tree.map(lambda a: a[others], kept[2]),
          jax.tree.map(lambda a: np.asarray(a)[others], after[2]))
    assert int(after[2]["lengths"][INTO[2]]) == LENGTHS[2]
    counted = _request_rows(after[1])
    if counted:
        layers = len(after[1])
        k = next(iter(after[1].values()))["rows"]  # (router experts,)
        top_k = counted["rows"] // (layers * LENGTHS[2])
        assert counted["rows"] == layers * top_k * LENGTHS[2], k.shape
        assert counted["pad_rows"] == layers * top_k * (
            WIDTH * BUCKET - LENGTHS[2])


def test_a_prefix_hits_suffix_goes_alone():
    model = _dense()
    params = model.init(jax.random.key(0))
    _, rows, _ = model.prefill_rows(params, np.arange(1, 9, dtype=np.int32),
                                    8, 32)
    with pytest.raises(ValueError, match="prefilled alone"):
        model.prefill_rows(params, np.ones((2, 8), np.int32),
                           np.asarray([12, 12], np.int32), 32,
                           prefix_rows=rows, prefix_len=8)


# -- 3. the width ---------------------------------------------------------------

@pytest.mark.parametrize("bucket, max_len, want", [
    (256, 1024, 4), (2048, 4096, 2), (64, 1024, 16), (128, 1024, 8),
    (1024, 1024, 1), (4096, 4096, 1), (16, 64, 4), (32, 48, 1), (16, 48, 2)])
def test_the_width_comes_from_the_bucket_and_the_pools_extent(
        bucket, max_len, want):
    model = TransformerLM(31, dim=8, depth=1, num_heads=1,
                          max_seq_len=max_len)
    engine = serve.SlotEngine(model, model.init(jax.random.key(0)),
                              num_slots=1, max_len=max_len)
    assert engine.prefill_width(bucket) == want
    assert engine.prefill_width(bucket) * bucket <= max_len


def test_the_disaggregated_engine_takes_one_prompt_a_program():
    from tpu_dist.serve.disagg import DisaggSlotEngine
    model = _dense()
    def nothing_arrives(timeout=None):
        time.sleep(0.05)
        raise TimeoutError("empty")

    engine = DisaggSlotEngine(
        model, model.init(jax.random.key(0)),
        kv=SimpleNamespace(fetched_bytes=0),
        dispatch_ch=SimpleNamespace(put=lambda desc, timeout=None: None),
        arrive_ch=SimpleNamespace(get=nothing_arrives), num_slots=2, max_len=64, kv_timeout=0.3, rank=1)
    try:
        assert [engine.prefill_width(b) for b in engine.buckets] == \
            [1] * len(engine.buckets)
    finally:
        engine.close()


def test_the_sharded_engine_takes_one_prompt_a_program():
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.dist.store import TCPStore
    model = _dense()
    params = model.init(jax.random.key(0))
    store = TCPStore(is_master=True)
    try:
        decoder = serve.ShardedDecoder(
            model, serve.shard_params(model, params, 0, 1),
            DataPlane(store, 0, 1), 0, 1)
        engine = serve.ShardedSlotEngine(decoder, num_slots=2, max_len=64)
        assert [engine.prefill_width(b) for b in engine.buckets] == [1, 1, 1]
        out = []
        engine.admit(serve.Request([3, 1, 4, 1, 5], 3,
                                   on_token=lambda r, t: out.append(t)))
        while not engine.idle():
            engine.step()
        ref = model.generate(params, jnp.asarray([[3, 1, 4, 1, 5]]), 3)
        assert out == np.asarray(ref)[0, 5:].tolist()
        engine.close()
    finally:
        store.close()


# -- 4. the scheduler's rule ------------------------------------------------------

class StubEngine(serve.SlotEngine):
    """The engine's own bookkeeping over two programs that compute nothing:
    every token is 1, and ``log`` holds what was launched, in order:
    ``("prefill", [request ids])`` and ``("decode", busy rows)``.  The loop
    stands at its first boundary until ``gate`` is set."""

    def __init__(self, num_slots, width=None, fail_prefills=0,
                 step_seconds=0.0):
        self.log, self.gate = [], threading.Event()
        self._width, self._fail = width, fail_prefills
        self._step_seconds = step_seconds
        model = TransformerLM(31, dim=8, depth=1, num_heads=1,
                              max_seq_len=MAX_LEN)
        super().__init__(model, model.init(jax.random.key(0)),
                         num_slots=num_slots, max_len=MAX_LEN)

    def prefill_width(self, bucket):
        return self._width or super().prefill_width(bucket)

    def _build_programs(self):
        def prefill(params, cache, moe, slots, prompts, lengths, into, temps,
                    keys, sampling):
            if self._fail:
                self._fail -= 1
                raise RuntimeError("the prefill program failed")
            self.log.append(("prefill", [
                self.slot_req_of(prompts[i]) for i in np.flatnonzero(lengths)]))
            return np.ones(len(lengths), np.int32), cache, moe, slots

        def decode(params, cache, moe, slots, live, sampling):
            self.log.append(("decode", int(np.asarray(live).sum())))
            time.sleep(self._step_seconds)
            return np.ones(self.num_slots, np.int32), cache, moe, slots

        self._prefill, self._decode = prefill, decode

    @staticmethod
    def slot_req_of(prompt_row):
        return int(prompt_row[0])       # a request's first token is its id

    def sweep_expired(self):
        self.gate.wait(30.0)
        return super().sweep_expired()

    def prefills(self):
        return [ids for kind, ids in self.log if kind == "prefill"]


def _submit(sched, rid, n_out, prompt_len=5, **kw):
    """A request whose first token names it; it ends after ``n_out``."""
    prompt = np.full(prompt_len, rid, np.int32)
    return sched.submit(prompt, max_new_tokens=n_out, req_id=rid, **kw)


def _staged(sched, n, timeout=10.0):
    """Wait until ``n`` requests stand staged before the gated loop."""
    deadline = time.monotonic() + timeout
    while sched.snapshot()["staged"] < n:
        assert time.monotonic() < deadline, sched.snapshot()
        time.sleep(0.005)


def _run(engine, requests, then=None, **kw):
    """Stage ``requests`` (``_submit``'s arguments) before the loop's first
    boundary, call ``then(handles)``, open the gate and wait for every
    handle; returns the handles."""
    with serve.Scheduler(engine, batch_window=0.0, **kw) as sched:
        handles = [_submit(sched, *args) if isinstance(args, tuple)
                   else _submit(sched, **args) for args in requests]
        _staged(sched, len(handles))
        if then is not None:
            then(handles)
        engine.gate.set()
        for h in handles:
            try:
                h.wait_done(30.0)
            except serve.ServeError:
                pass
            except RuntimeError:
                pass
        assert sched.drain(10.0) and sched.fatal is None
    return handles


def test_a_lone_request_on_an_idle_pool_is_launched_at_once():
    engine = StubEngine(4)
    _run(engine, [(1, 3)])
    assert engine.log[0] == ("prefill", [1])
    p = engine.stats()["pipeline"]
    assert p["deferred_slot_steps"] == 0
    assert (p["prefill_prompts"], p["launches"]["prefill"],
            p["prefill_absent_rows"]) == (1, 1, WIDTH - 1)


def test_a_lone_arrival_beside_decoding_slots_never_waits():
    """Nothing else is queued: the group of one is as full as it can get."""
    engine = StubEngine(4, step_seconds=0.01)
    engine.gate.set()
    with serve.Scheduler(engine, batch_window=0.0) as sched:
        first = _submit(sched, 1, 55)
        deadline = time.monotonic() + 10.0
        while not first.tokens():
            assert time.monotonic() < deadline
            time.sleep(0.002)
        second = _submit(sched, 2, 2)
        second.wait_done(30.0)
        assert not first.done           # the pool was decoding throughout
        first.cancel()
        assert sched.drain(10.0)
    assert engine.prefills() == [[1], [2]]
    assert engine.stats()["pipeline"]["deferred_slot_steps"] == 0


def test_a_full_group_goes_at_once():
    engine = StubEngine(4)
    _run(engine, [(1, 2), (2, 2), (3, 2), (4, 2)])
    assert engine.log[0] == ("prefill", [1, 2, 3, 4])
    p = engine.stats()["pipeline"]
    assert (p["prefill_prompts"], p["launches"]["prefill"],
            p["prefill_absent_rows"], p["deferred_slot_steps"]) == (4, 1, 0, 0)


def test_free_slots_wait_for_company_and_go_after_a_pools_worth():
    """Four slots, a width of four, eight requests queued.  The first four
    share a program.  The fifth's slot frees first, alone: it waits, since
    three more are queued; a second slot frees and waits with it; the two
    go together once the idle slot-steps reach the pool's four, although
    the program is not full."""
    engine = StubEngine(4)
    _run(engine, [(1, 3), (2, 5), (3, 40), (4, 40),
                  (5, 2), (6, 2), (7, 2), (8, 2)])
    groups = engine.prefills()
    assert groups[0] == [1, 2, 3, 4]
    assert groups[1] == [5, 6], groups     # not [5] the moment a slot freed
    assert [i for g in groups for i in g] == list(range(1, 9))  # FIFO
    first = engine.log.index(("prefill", [5, 6]))
    waited = [n for kind, n in engine.log[:first] if kind == "decode"]
    deferred = engine.stats()["pipeline"]["deferred_slot_steps"]
    # the wait is bounded by the pool's slots in idle slot-steps, give or
    # take the one step's free slots that cross the bound
    assert engine.num_slots <= deferred
    assert len(waited) < 3 + 2 * engine.num_slots
    # 7 and 8 went as two slots had waited their bound again, or together
    # with nothing else queued: never one by one behind a queued mate
    assert groups[2:] == [[7, 8]]


def test_a_request_of_another_bucket_is_no_company():
    """A slot frees with one request of the 16 bucket held and requests of
    the 32 and the 64 bucket staged behind it: none of them could share its
    program, so it goes at once, and so does each of them in its turn."""
    engine = StubEngine(4, width=4)
    _run(engine, [(1, 3), (2, 40), (3, 40), (4, 40),
                  dict(rid=5, n_out=2, prompt_len=5),
                  dict(rid=6, n_out=2, prompt_len=20),
                  dict(rid=7, n_out=2, prompt_len=40)])
    assert engine.prefills() == [[1, 2, 3, 4], [5], [6], [7]]
    assert engine.stats()["pipeline"]["deferred_slot_steps"] == 0


def test_fifo_holds_within_a_bucket_and_a_group_is_one_bucket():
    """Held: 16, 32, 16, 32 (buckets).  The oldest takes its bucket's mates
    from behind the others; the others follow, in their order."""
    engine = StubEngine(4)
    _run(engine, [dict(rid=1, n_out=2, prompt_len=5),
                  dict(rid=2, n_out=2, prompt_len=20),
                  dict(rid=3, n_out=2, prompt_len=9),
                  dict(rid=4, n_out=2, prompt_len=30)])
    assert engine.prefills() == [[1, 3], [2, 4]]
    assert engine.stats()["pipeline"]["prefill_absent_rows"] == \
        (engine.prefill_width(16) - 2) + (engine.prefill_width(32) - 2)


def test_a_cancelled_and_an_expired_member_are_refused_by_name():
    engine = StubEngine(4)

    def then(handles):
        handles[1].cancel()             # staged already: the engine refuses
        time.sleep(0.12)                # the third's deadline passes

    handles = _run(engine, [dict(rid=1, n_out=2), dict(rid=2, n_out=2),
                            dict(rid=3, n_out=2, deadline_ms=100.0),
                            dict(rid=4, n_out=2)], then=then)
    assert engine.prefills() == [[1, 4]]
    assert isinstance(handles[1].error, serve.RequestCancelledError)
    assert isinstance(handles[2].error, serve.DeadlineExceededError)
    assert handles[0].tokens() == [1, 1] and handles[3].tokens() == [1, 1]
    assert engine.stats()["pipeline"]["prefill_prompts"] == 2


def test_a_failing_program_fails_its_group_by_name_and_the_loop_lives():
    engine = StubEngine(4, fail_prefills=1)
    with serve.Scheduler(engine, batch_window=0.0) as sched:
        doomed = [_submit(sched, i, 2) for i in (1, 2, 3)]
        _staged(sched, 3)
        engine.gate.set()
        for h in doomed:
            with pytest.raises(RuntimeError, match="prefill program failed"):
                h.wait_done(30.0)
        assert engine.free_slots() == 4     # nothing was occupied
        after = _submit(sched, 4, 3)
        assert after.wait_done(30.0) == [1, 1, 1]
        assert sched.fatal is None
    assert engine.prefills() == [[4]]


def _todays_loop(engine, requests):
    """The loop as it ran before groups, less its threads: every held
    request admitted the moment a slot is free, one program each, one
    program kept in flight."""
    pending = [serve.Request(np.full(n, rid, np.int32), n_out, req_id=rid)
               for rid, n_out, n in requests]
    engine.gate.set()
    while pending or not engine.idle():
        engine.sweep_expired()
        admitted = 0
        while (pending and engine.free_slots()
               and admitted < engine.num_slots):
            engine.launch_admit(pending.pop(0))
            engine.settle()
            admitted += 1
        if not engine.idle():
            if engine.launch_step():
                engine.settle()
            else:
                engine.collect_all()


@pytest.mark.parametrize("slots", [2, 3])
def test_at_a_width_of_one_the_launches_are_todays(slots):
    """A recorded arrival trace (ten requests of three buckets, their
    answers 1-9 tokens) through the scheduler at ``P`` = 1 launches what
    the loop launched before, in its order, and nothing waits."""
    rng = np.random.default_rng(5)
    trace = [(rid, int(rng.integers(1, 10)), int(rng.integers(3, 60)))
             for rid in range(1, 11)]
    want = StubEngine(slots, width=1)
    _todays_loop(want, trace)
    engine = StubEngine(slots, width=1)
    _run(engine, [dict(rid=rid, n_out=n_out, prompt_len=n)
                  for rid, n_out, n in trace])
    assert engine.log == want.log
    assert all(len(ids) == 1 for ids in engine.prefills())
    p = engine.stats()["pipeline"]
    assert p["deferred_slot_steps"] == p["prefill_absent_rows"] == 0
    assert p["prefill_prompts"] == p["launches"]["prefill"] == 10
