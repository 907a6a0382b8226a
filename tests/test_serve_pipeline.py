"""The serving loop keeps one program in flight (ISSUE 29).

Slot state lives on the device and the scheduler's loop launches the next
pool program before it collects the previous one.  The load-bearing
assertion: that is a SCHEDULING change — one scripted scenario run through
the serial composition (``admit()`` / ``step()``, launch immediately
followed by collect) and through the scheduler's launch-ahead loop gives
every request the same tokens, in the same order, with the same end.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import serve
from tpu_dist.models import TransformerLM
from tpu_dist.serve.engine import seed_key

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def dense():
    model = TransformerLM(vocab_size=97, dim=32, depth=2, num_heads=4,
                          max_seq_len=64)
    return model, model.init(jax.random.key(0))


@pytest.fixture(scope="module")
def routed():
    """tests/test_olmoe.py's block: 8 gated experts of 32, 2 a token."""
    model = TransformerLM(vocab_size=211, dim=64, depth=2, num_heads=4,
                          max_seq_len=128, num_experts=8, moe_top_k=2,
                          moe_hidden=32, moe_normalize_gates=False,
                          norm_eps=1e-5, norm="rmsnorm", rope=True,
                          qk_norm=True, attn_bias=False, moe_gated=True,
                          moe_dispatch="dropless")
    return model, model.init(jax.random.key(0))


# -- the scripted scenario ----------------------------------------------------
# requests: (prompt length, max_new_tokens, temperature, seed).  ``event``
# lands BETWEEN the launch of a decode step and its collection, at the
# collection that delivers request ``who``'s token number ``after`` + 1.

SCENARIOS = {
    "greedy": dict(slots=4, requests=[(5, 6, 0.0, 0), (9, 9, 0.0, 0),
                                      (12, 4, 0.0, 0), (3, 7, 0.0, 0)]),
    "sampled": dict(slots=4, requests=[(5, 8, 0.9, 11), (9, 6, 0.0, 0),
                                       (7, 9, 1.3, 2**31 + 5),
                                       (4, 5, 0.7, 11)]),
    "eos": dict(slots=3, requests=[(5, 12, 0.0, 0), (9, 12, 0.0, 0),
                                   (7, 12, 0.0, 0)],
                event=("eos", 1, 4)),
    "max_new_tokens": dict(slots=3, requests=[(5, 3, 0.0, 0), (9, 8, 0.0, 0),
                                              (7, 1, 0.0, 0)]),
    "cancel": dict(slots=3, requests=[(5, 12, 0.0, 0), (9, 12, 0.0, 0),
                                      (7, 12, 0.0, 0)],
                   event=("cancel", 0, 3)),
    "deadline": dict(slots=3, requests=[(5, 12, 0.0, 0), (9, 12, 0.6, 7),
                                        (7, 12, 0.0, 0)],
                     event=("deadline", 1, 5)),
    # more requests than slots: a slot is freed and prefilled again while a
    # step that still carries a row of it may be in flight
    "slot_reuse": dict(slots=2, requests=[(5, 3, 0.0, 0), (9, 5, 0.0, 0),
                                          (7, 2, 0.0, 0), (4, 6, 0.8, 3),
                                          (11, 4, 0.0, 0), (6, 1, 0.0, 0)]),
}


def _prompts(sc, vocab):
    rng = np.random.default_rng(29)
    return [rng.integers(1, vocab, n).astype(np.int32)
            for n, *_ in sc["requests"]]


class _Log:
    """What every request received, and how it ended."""

    def __init__(self):
        self.tokens, self.end = {}, {}

    def on_token(self, req, tok):
        assert req.id not in self.end, "a token after the request's end"
        self.tokens.setdefault(req.id, []).append(tok)

    def on_done(self, req, reason):
        self.end[req.id] = reason

    def on_error(self, req, exc):
        self.end[req.id] = type(exc).__name__

    def result(self, n):
        return {i: (self.tokens.get(i, []), self.end.get(i))
                for i in range(1, n + 1)}


def _arm(engine, sc):
    """Install the scenario's event on ``engine.collect`` (the cancel or the
    deadline lands between a decode step's launch and its collection)."""
    event = sc.get("event")
    if event is None or event[0] == "eos":
        return
    kind, who, after = event
    collect, fired = engine.collect, []

    def hooked():
        flight = engine._flight[0] if engine._flight else None
        if flight is not None and flight.kind == "decode" and not fired:
            for req in flight.reqs:
                if req.id == who + 1 and req.emitted == after:
                    fired.append(req)
                    if kind == "cancel":
                        req.cancel()
                    else:
                        req.deadline = 0.0      # long past, on any clock
        return collect()

    engine.collect = hooked


def _request_args(sc, vocab, eos_id):
    for i, (prompt, (_, n, temp, seed)) in enumerate(
            zip(_prompts(sc, vocab), sc["requests"])):
        event = sc.get("event")
        eos = eos_id if event and event[0] == "eos" and event[1] == i \
            else None
        yield prompt, dict(max_new_tokens=n, temperature=temp, seed=seed,
                           eos_id=eos, req_id=i + 1)


def run_serial(model, params, sc, eos_id=None):
    """The serial composition, as the loop ran it before this change: a
    sweep at every boundary, every admissible request admitted, one step."""
    engine = serve.SlotEngine(model, params, num_slots=sc["slots"])
    _arm(engine, sc)
    log = _Log()
    pending = [serve.Request(p, kw.pop("max_new_tokens"),
                             on_token=log.on_token, on_done=log.on_done,
                             on_error=log.on_error, **kw)
               for p, kw in _request_args(sc, model.vocab_size, eos_id)]
    n = len(pending)
    while pending or not engine.idle():
        engine.sweep_expired()
        while pending and engine.free_slots():
            engine.admit(pending.pop(0))
        engine.step()
    return log.result(n), engine


def run_pipelined(model, params, sc, eos_id=None, seal=False):
    """The scheduler's launch-ahead loop.  The window is long and ends when
    the pool's worth of requests is held, so the first wave is admitted
    together whatever the threads' timing."""
    engine = serve.SlotEngine(model, params, num_slots=sc["slots"])
    _arm(engine, sc)
    reads = _seal(engine) if seal else None
    log = _Log()
    with serve.Scheduler(engine, batch_window=30.0) as sched:
        handles = [sched.submit(p, on_token=log.on_token,
                                on_done=log.on_done, on_error=log.on_error,
                                **kw)
                   for p, kw in _request_args(sc, model.vocab_size, eos_id)]
        for h in handles:
            try:
                h.wait_done(120.0)
            except serve.ServeError:
                pass
        assert sched.drain(30.0)
        assert sched.fatal is None
    return log.result(len(handles)), engine, reads


def _eos_token(model, params, sc):
    """The token that ends request ``who`` at its token ``at`` + 1: the
    first of its greedy stream, from there on, that it has not produced
    before."""
    event = sc.get("event")
    if not event or event[0] != "eos":
        return None
    _, who, at = event
    stream = run_serial(model, params, dict(sc, event=None))[0][who + 1][0]
    assert stream[at] not in stream[:at], stream
    return stream[at]


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_launch_ahead_loop_gives_the_serial_loops_tokens(dense, name):
    model, params = dense
    sc = SCENARIOS[name]
    eos_id = _eos_token(model, params, sc)
    want, serial = run_serial(model, params, sc, eos_id)
    got, engine, _ = run_pipelined(model, params, sc, eos_id)
    assert got == want
    n = len(sc["requests"])
    event = sc.get("event")
    ended = {i: end for i, (_, end) in got.items()}
    if event is None:
        assert set(ended.values()) == {"length"}
        for i, (_, n_out, *_) in enumerate(sc["requests"]):
            assert len(got[i + 1][0]) == n_out
    else:
        kind, who, after = event
        assert ended[who + 1] == {"eos": "eos",
                                  "cancel": "RequestCancelledError",
                                  "deadline": "DeadlineExceededError"}[kind]
        # the collection the event landed in still delivered its token
        assert len(got[who + 1][0]) == after + 1
    # the serial composition never launches ahead and wastes nothing
    s = serial.stats()["pipeline"]
    assert s["launched_ahead"] == {"decode": 0, "prefill": 0}
    assert s["wasted_rows"] == 0
    p = engine.stats()["pipeline"]
    # every request went through a prefill program, the first wave sharing
    # theirs (a 16 bucket of a 64 pool takes four prompts a program)
    assert p["prefill_prompts"] == n == s["prefill_prompts"]
    assert s["launches"]["prefill"] == n
    assert p["launches"]["prefill"] < n
    width = engine.prefill_width(16)
    assert p["prefill_absent_rows"] == \
        p["launches"]["prefill"] * width - n
    assert p["launches"]["decode"] == engine.stats()["decode_steps"]
    assert p["launched_ahead"]["decode"] > 0
    if name == "slot_reuse":
        # a slot freed and re-admitted while the pool decodes: the prefill
        # was launched behind the step in flight
        assert p["launched_ahead"]["prefill"] > 0
    if event is None:
        # ended by max_new_tokens: known before the last token is read, so
        # no row is launched for nobody
        assert p["wasted_rows"] == 0
        assert engine.generated_tokens == sum(
            n_out for _, n_out, *_ in sc["requests"])
    else:
        # known one program late: the step in flight carried one row for it,
        # never emitted (_Log.on_token refuses a token after the end)
        assert p["wasted_rows"] == 1
    assert engine.idle() and engine.free_slots() == sc["slots"]


def test_routed_rows_count_requests_rows_only(routed):
    """A model with expert layers through both loops: the same tokens, and
    ``stats()["moe"]`` counts exactly the rows requests own — a prompt's
    positions and one row a decode step a request still due a token."""
    model, params = routed
    sc = dict(slots=3, requests=[(5, 4, 0.0, 0), (9, 7, 0.0, 0),
                                 (7, 1, 0.0, 0), (6, 5, 0.0, 0)])
    want, serial = run_serial(model, params, sc)
    got, engine, _ = run_pipelined(model, params, sc)
    assert got == want
    layers, top_k = 2, 2
    prompts = sum(n for n, *_ in sc["requests"])
    decoded = sum(n_out - 1 for _, n_out, *_ in sc["requests"])
    for eng in (serial, engine):
        moe = eng.stats()["moe"]
        assert moe["by_phase"]["prefill"]["rows"] == prompts * top_k * layers
        assert moe["by_phase"]["decode"]["rows"] == decoded * top_k * layers
        assert sum(moe["rows_per_expert"]) == moe["rows"]
    assert engine.stats()["moe"]["rows_per_expert"] == \
        serial.stats()["moe"]["rows_per_expert"]
    assert engine.stats()["pipeline"]["wasted_rows"] == 0


# -- the halves, driven by hand -----------------------------------------------

def test_halves_by_hand_count_what_was_launched_ahead(dense):
    """Launch, then collect: every launch made while an earlier program's
    result was uncollected counts as ahead, ``settle`` keeps the newest in
    flight, and the tokens are ``generate()``'s."""
    model, params = dense
    engine = serve.SlotEngine(model, params, num_slots=2)
    outs = {}
    prompts = _prompts(dict(requests=[(5,), (9,)]), 97)
    reqs = [serve.Request(p, 4, req_id=i + 1, on_token=lambda r, t:
                          outs.setdefault(r.id, []).append(t))
            for i, p in enumerate(prompts)]
    assert engine.launch_admit(reqs[0]) == 0
    assert engine.settle() == 0 and not outs        # the newest stays
    assert engine.launch_admit(reqs[1]) == 1
    assert engine.free_slots() == 0                 # occupied at launch
    assert engine.settle() == 1 and list(outs) == [1]
    steps = 0
    while engine.launch_step():
        steps += 1
        engine.settle()
    assert len(engine._flight) == 1 and not engine.idle()
    assert engine.collect_all() == 2 and engine.idle()
    assert engine.collect() == 0                    # nothing in flight
    assert steps == 3
    width = engine.prefill_width(16)
    assert engine.stats()["pipeline"] == {
        "launches": {"decode": 3, "prefill": 2},
        "launched_ahead": {"decode": 3, "prefill": 1}, "wasted_rows": 0,
        "prefill_prompts": 2, "prefill_absent_rows": 2 * (width - 1),
        "deferred_slot_steps": 0}
    for req, prompt in zip(reqs, prompts):
        ref = model.generate(params, jnp.asarray(prompt)[None, :], 4)
        assert outs[req.id] == np.asarray(ref)[0, len(prompt):].tolist()
    # reset_stats zeroes the counter and leaves the progress feed
    assert engine.steps_done == 3 and engine.stats()["decode_steps"] == 3
    engine.reset_stats()
    assert engine.stats()["pipeline"] == {
        "launches": {"decode": 0, "prefill": 0},
        "launched_ahead": {"decode": 0, "prefill": 0}, "wasted_rows": 0,
        "prefill_prompts": 0, "prefill_absent_rows": 0,
        "deferred_slot_steps": 0}
    assert engine.steps_done == 3


def test_histograms_are_charged_collection_to_collection(dense):
    """``hist_token`` + ``hist_prefill`` split the time between the first
    launch and the last collection and count none of it twice."""
    model, params = dense
    engine = serve.SlotEngine(model, params, num_slots=2)
    for prompt in _prompts(dict(requests=[(5,), (9,)]), 97):  # compile
        engine.admit(serve.Request(prompt, 3))
    while not engine.idle():
        engine.step()
    engine.reset_stats()
    t0 = serve.engine._now()
    for prompt in _prompts(dict(requests=[(5,), (9,)]), 97):
        engine.launch_admit(serve.Request(prompt, 5))
        engine.settle()
    while engine.launch_step():
        engine.settle()
    engine.collect_all()
    wall = serve.engine._now() - t0
    st = engine.stats()
    charged = sum(st[h]["mean"] * st[h]["count"]
                  for h in ("prefill", "decode_step"))
    assert st["prefill"]["count"] == 2 and st["decode_step"]["count"] == 4
    assert charged <= wall + 1e-9
    assert charged >= 0.5 * wall     # the loop never slept: most of it


# -- one wait on the device ---------------------------------------------------

class _Sealed:
    """A program's sampled tokens, readable inside ``_readback`` only."""

    def __init__(self, value, reading):
        self._value, self._reading = value, reading

    def __array__(self, *args, **kwargs):
        assert self._reading, "tokens read outside SlotEngine._readback"
        return np.asarray(self._value)

    def _refuse(self, *args, **kwargs):
        raise AssertionError("tokens read outside SlotEngine._readback")

    __int__ = __index__ = __bool__ = __iter__ = __getitem__ = _refuse


def _seal(engine):
    """Seal what both pool programs return for the host and count the
    reads; returns the counter."""
    reading, reads = [], []

    def sealed(program):
        def run(*args):
            out, *rest = program(*args)
            return (_Sealed(out, reading), *rest)
        return run

    engine._decode = sealed(engine._decode)
    engine._prefill = sealed(engine._prefill)
    readback = engine._readback

    def counted(out):
        reads.append(threading.current_thread().name)
        reading.append(True)
        try:
            return readback(out)
        finally:
            reading.pop()

    engine._readback = counted
    return reads


def test_loop_thread_waits_for_the_device_in_one_place(dense):
    model, params = dense
    got, engine, reads = run_pipelined(model, params, SCENARIOS["sampled"],
                                       seal=True)
    assert got == run_serial(model, params, SCENARIOS["sampled"])[0]
    launches = engine.stats()["pipeline"]["launches"]
    assert len(reads) == launches["decode"] + launches["prefill"]
    assert set(reads) == {"tpu_dist-serve-loop"}


# -- the sampling key is the host's -------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 - 1, 2**31 + 5, 2**32 - 1,
                                  2**32 + 3, -1, -5, 2**40 + 9])
def test_seed_key_is_jax_random_keys_data(seed):
    np.testing.assert_array_equal(
        seed_key(seed), np.asarray(jax.random.key_data(jax.random.key(seed))))
