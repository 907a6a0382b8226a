"""tpu_dist.serve — slot engine parity, scheduler semantics, socket layer,
obs spans, and the bench_serve smoke gate (ISSUE 12).

The load-bearing assertion family: continuous batching is a SCHEDULING
optimization — every token a slot emits must be identical to what offline
``generate()`` emits for that request, whatever else the pool is doing.
"""

import json
import os
import socket
import struct
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_dist import nn, serve
from tpu_dist.models import TransformerLM

pytestmark = pytest.mark.serve

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def lm():
    model = TransformerLM(vocab_size=97, dim=32, depth=2, num_heads=4,
                          max_seq_len=64)
    params = model.init(jax.random.key(0))
    return model, params


def _gen_ref(model, params, prompt, n, **kw):
    """Offline per-request ground truth (continuation only)."""
    out = model.generate(params, jnp.asarray(prompt)[None, :], n, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def _run_engine(model, params, reqs, slots=4, cache_dtype=None,
                interleave=True, **request_kw):
    """Drive the raw engine: admit mixed-length requests (interleaved with
    decoding when ``interleave``) and return each request's tokens.
    ``request_kw`` (``temperature``, ``seed``) goes to every request."""
    engine = serve.SlotEngine(model, params, num_slots=slots,
                              cache_dtype=cache_dtype)
    outs = {}
    order = []

    def on_token(req, tok):
        outs.setdefault(req.id, []).append(tok)

    pending = [serve.Request(p, n, on_token=on_token, **request_kw)
               for p, n in reqs]
    for r in pending:
        order.append(r.id)
    while pending or not engine.idle():
        # admissions happen BETWEEN decode iterations, one per boundary
        # when interleaving (maximally mixes prefills with decode states)
        while pending and engine.free_slots() > 0:
            engine.admit(pending.pop(0))
            if interleave:
                break
        engine.step()
    return [outs[rid] for rid in order], engine


class TestSlotParity:
    def test_batched_generate_equals_batch1(self, lm):
        # ISSUE satellite: generate() at batch B is token-identical to B
        # independent batch-1 decodes — the row-independence the slot
        # math depends on
        model, params = lm
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 97, (4, 7))
        batched = np.asarray(model.generate(params, jnp.asarray(prompt), 6))
        for b in range(4):
            single = np.asarray(
                model.generate(params, jnp.asarray(prompt[b:b + 1]), 6))
            np.testing.assert_array_equal(batched[b], single[0])

    def test_engine_matches_generate_mixed_lengths(self, lm):
        # THE continuous-batching correctness pin: requests of different
        # prompt lengths and max_new_tokens, admitted into a pool that is
        # already decoding, each reproduce their offline generate() tokens
        model, params = lm
        rng = np.random.default_rng(1)
        reqs = [(rng.integers(0, 97, rng.integers(3, 14)).astype(np.int32),
                 int(rng.integers(2, 9))) for _ in range(7)]
        outs, engine = _run_engine(model, params, reqs, slots=3)
        for (p, n), got in zip(reqs, outs):
            assert got == _gen_ref(model, params, p, n)
        assert engine.completed == len(reqs)
        assert engine.stats()["e2e"]["count"] == len(reqs)

    def test_padded_prefill_logits_bitwise(self, lm):
        # bucket padding must not perturb the last real token's logits
        # (causal mask: real positions never attend to the padding)
        model, params = lm
        rng = np.random.default_rng(2)
        prompt = rng.integers(0, 97, 5).astype(np.int32)
        cache = model.init_slot_cache(2, 64)
        padded = np.zeros(16, np.int32)
        padded[:5] = prompt
        logits, *_ = model.prefill_into_slot(params, padded, 5, 1, cache)
        # the prefill's rows hold the bucket's 16 columns (PR 48), so the
        # unpadded reference attends over a cache of as many
        ref_cache = model.init_cache(1, 16)
        ref_logits, _ = model.apply(params, jnp.asarray(prompt)[None, :],
                                    state=ref_cache)
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits)[0, -1])

    @pytest.mark.parametrize("dtype,tol", [
        (jnp.float32, 1e-5), (jnp.bfloat16, 0.05), (jnp.int8, 0.05)],
        ids=["float32", "bfloat16", "int8"])
    def test_slot_cache_logits_match_uncached_forward(self, lm, dtype, tol):
        # the stored layout (B, H, D, Tmax) and its in-place write: every
        # way the pool is written — whole-prompt prefill into a slot, slot
        # decode at UNEQUAL per-slot positions, and a t > 1 vector-index
        # write — must reproduce the uncached forward's logits position by
        # position (float32 at test_transformer's 1e-5; the rounded caches
        # at the int8 cache's 0.05)
        model, params = lm
        rng = np.random.default_rng(5)
        seqs = rng.integers(0, 97, (3, 20)).astype(np.int32)
        full = np.asarray(model.apply(params, jnp.asarray(seqs)))
        rows = np.arange(3)

        def close(got, want, what):
            np.testing.assert_allclose(np.asarray(got), want, atol=tol,
                                       rtol=tol, err_msg=what)

        lengths = np.array([4, 9, 6], np.int32)
        cache = model.init_slot_cache(3, 32, dtype)
        for slot, n in enumerate(lengths):
            padded = np.zeros(16, np.int32)
            padded[:n] = seqs[slot, :n]
            logits, cache, _ = model.prefill_into_slot(params, padded, n,
                                                       slot, cache)
            close(logits, full[slot, n - 1], f"prefill into slot {slot}")
        for step in range(3):
            logits, cache, _ = model.decode_step(
                params, seqs[rows, lengths], lengths, cache)
            close(logits, full[rows, lengths], f"slot decode step {step}")
            lengths = lengths + 1
        # t = 4 new tokens per slot, each slot at its own position
        t = 4
        toks = np.stack([seqs[b, n:n + t] for b, n in enumerate(lengths)])
        state = nn.cache.call_state(cache, jnp.asarray(lengths))
        logits, state = model.apply(params, jnp.asarray(toks),
                                    pos_offset=jnp.asarray(lengths),
                                    state=state)
        for b, n in enumerate(lengths):
            close(logits[b], full[b, n:n + t], f"t={t} write, slot {b}")
        # ... and the next decode step attends over what that write stored
        lengths = lengths + t
        cache, _ = nn.cache.split_state(state)
        logits, *_ = model.decode_step(params, seqs[rows, lengths], lengths,
                                       cache)
        close(logits, full[rows, lengths], "decode after the t>1 write")

    @pytest.mark.parametrize("cache_dtype", [None, jnp.bfloat16, jnp.int8],
                             ids=["float32", "bfloat16", "int8"])
    def test_generate_equals_uncached_greedy(self, lm, cache_dtype):
        # generate() through the cache picks, token for token, what
        # re-running the whole prefix without a cache picks
        model, params = lm
        prompt = np.random.default_rng(6).integers(0, 97, (2, 6))
        out = model.generate(params, jnp.asarray(prompt), 8,
                             cache_dtype=cache_dtype)
        seq = jnp.asarray(prompt)
        for _ in range(8):
            nxt = model.apply(params, seq)[:, -1].argmax(-1)
            seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(seq))

    def test_engine_int8_cache_matches_generate(self, lm):
        # the quantized-cache decode path has its own per-slot write logic
        # (k_scale/v_scale rows) — same parity contract
        model, params = lm
        rng = np.random.default_rng(3)
        reqs = [(rng.integers(0, 97, rng.integers(3, 10)).astype(np.int32),
                 int(rng.integers(2, 7))) for _ in range(4)]
        outs, _ = _run_engine(model, params, reqs, slots=2,
                              cache_dtype=jnp.int8)
        for (p, n), got in zip(reqs, outs):
            assert got == _gen_ref(model, params, p, n,
                                   cache_dtype=jnp.int8)

    def test_temperature_sampling_deterministic(self, lm):
        # sampling requests are reproducible per (seed, prompt) and stay
        # in-vocabulary; two engines agree token-for-token
        model, params = lm
        prompt = np.arange(4, dtype=np.int32)
        runs = []
        for _ in range(2):
            outs, _ = _run_engine(model, params, [(prompt, 6)], slots=2)
            runs.append(outs[0])
        assert runs[0] == runs[1]
        engine = serve.SlotEngine(model, params, num_slots=2)
        got = {}
        r = serve.Request(prompt, 6, temperature=0.8, seed=7,
                          on_token=lambda q, t: got.setdefault(
                              q.id, []).append(t))
        engine.admit(r)
        while not engine.idle():
            engine.step()
        toks = got[r.id]
        assert len(toks) == 6 and all(0 <= t < 97 for t in toks)
        engine2 = serve.SlotEngine(model, params, num_slots=2)
        got2 = {}
        r2 = serve.Request(prompt, 6, temperature=0.8, seed=7,
                           on_token=lambda q, t: got2.setdefault(
                               q.id, []).append(t))
        engine2.admit(r2)
        while not engine2.idle():
            engine2.step()
        assert got2[r2.id] == toks

    def test_eos_frees_slot(self, lm):
        model, params = lm
        prompt = np.arange(5, dtype=np.int32)
        ref = _gen_ref(model, params, prompt, 6)
        eos = ref[2]   # the third emitted token, declared EOS
        engine = serve.SlotEngine(model, params, num_slots=2)
        done = {}
        toks = []
        r = serve.Request(prompt, 6, eos_id=eos,
                          on_token=lambda q, t: toks.append(t),
                          on_done=lambda q, reason: done.setdefault(
                              "reason", reason))
        engine.admit(r)
        while not engine.idle():
            engine.step()
        assert done["reason"] == "eos"
        assert toks == ref[:3]          # EOS emitted, then the slot freed
        assert engine.free_slots() == 2

    def test_validate_rejects_oversized(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        with pytest.raises(ValueError, match="exceeds the slot capacity"):
            engine.validate(60, 10)
        with pytest.raises(ValueError, match="max_new_tokens"):
            engine.validate(4, 0)


class TestDecodeAttnCounter:
    """``stats()["decode_attn"]`` (ISSUE 26): the K/V time blocks the busy
    slots held, by host arithmetic on the lengths at every decode
    dispatch — what tpu_dist.ops.decode_attention reads on a TPU, and what
    it WOULD read here, where the decode program is built dense."""

    @pytest.fixture(scope="class")
    def long_lm(self):
        model = TransformerLM(vocab_size=97, dim=32, depth=1, num_heads=4,
                              max_seq_len=512)
        return model, model.init(jax.random.key(0))

    @staticmethod
    def _requests():
        # lengths at the three decode dispatches: (5, 254), (6, 255),
        # (7, 256); a slot reads ceil((len + 1) / 256) blocks of 256
        rng = np.random.default_rng(0)
        return [serve.Request(rng.integers(0, 97, n).astype(np.int32), 4)
                for n in (5, 254)]

    WANT = {"kv_blocks_read": 2 + 2 + 3, "kv_blocks_pool": 3 * 4 * 2,
            "steps": 3, "block": 256, "kernel": False}

    def test_counts_what_the_lengths_say_and_reset_zeroes(self, long_lm):
        model, params = long_lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        assert engine.stats()["decode_attn"] == {
            "kv_blocks_read": 0, "kv_blocks_pool": 0, "steps": 0,
            "block": 256, "kernel": False}
        for r in self._requests():
            engine.admit(r)
        while not engine.idle():
            engine.step()
        assert engine.stats()["decode_attn"] == self.WANT
        engine.reset_stats()
        zero = engine.stats()["decode_attn"]
        assert (zero["kv_blocks_read"], zero["kv_blocks_pool"],
                zero["steps"]) == (0, 0, 0)

    def test_is_on_the_wire_stats_frame(self, long_lm):
        model, params = long_lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            cli.generate(list(range(1, 6)), max_new_tokens=4, timeout=120.0)
            got = cli.stats()["decode_attn"]
        finally:
            cli.close()
            fe.close()
            sched.close()
        assert got == {"kv_blocks_read": 3, "kv_blocks_pool": 3 * 4 * 2,
                       "steps": 3, "block": 256, "kernel": False}


class TestParamsPlacement:
    """``serve.engine.place_params`` (ISSUE 31): the engine holds a
    gathered table row-major, whatever format the caller made it in, and
    every other leaf as it came.  The CPU's default IS row-major, so an
    engine here holds the caller's own tree; a caller-made column-major
    table exercises the placement itself (the CPU backend can lay a
    matrix either way, it only has no tiles)."""

    ZERO = {"placed_leaves": 0, "placed_bytes": 0, "leaves": 30}

    @staticmethod
    def _column_major(params, paths):
        from jax.experimental.layout import Format, Layout
        out = {path: dict(leaves) for path, leaves in params.items()}
        for path in paths:
            w = params[path]["weight"]
            out[path]["weight"] = jax.device_put(
                w, Format(Layout(major_to_minor=(1, 0)), w.sharding))
        return out

    @staticmethod
    def _requests():
        rng = np.random.default_rng(31)
        return [(rng.integers(0, 97, n).astype(np.int32), m)
                for n, m in ((3, 6), (17, 4), (9, 8), (30, 5))]

    def test_is_the_identity_where_the_default_suits(self, lm):
        from tpu_dist.serve.engine import gathered_tables, place_params
        model, params = lm
        assert gathered_tables(model) == [("tok", "weight"),
                                          ("pos", "weight")]
        placed, took = place_params(model, params)
        assert placed is params and took == self.ZERO
        engine = serve.SlotEngine(model, params, num_slots=2)
        assert engine.params is params

    def test_counter_survives_reset_and_rides_the_wire(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        assert engine.stats()["params"] == self.ZERO
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            cli.generate(list(range(1, 6)), max_new_tokens=4, timeout=120.0)
            got = cli.stats()["params"]
        finally:
            cli.close()
            fe.close()
            sched.close()
        assert got == self.ZERO
        engine.reset_stats()
        assert engine.stats()["params"] == self.ZERO

    @pytest.mark.parametrize("paths", [("tok",), ("pos",), ("tok", "pos")],
                             ids=["tok", "pos", "both"])
    def test_places_a_table_that_lies_otherwise(self, lm, paths):
        model, params = lm
        made = self._column_major(params, paths)
        engine = serve.SlotEngine(model, made, num_slots=3)
        nbytes = sum(params[p]["weight"].nbytes for p in paths)
        assert engine.stats()["params"] == {
            "placed_leaves": len(paths), "placed_bytes": nbytes,
            "leaves": 30}
        engine.reset_stats()
        assert engine.stats()["params"]["placed_leaves"] == len(paths)
        for path, leaves in engine.params.items():
            for name, leaf in leaves.items():
                if path in paths and name == "weight":
                    assert leaf.format.layout.major_to_minor == (0, 1)
                    np.testing.assert_array_equal(leaf, params[path][name])
                else:       # the caller's own array, not a copy
                    assert leaf is made[path][name]
        # the caller's tree is as it was made
        assert made[paths[0]]["weight"].format.layout.major_to_minor == (1, 0)

    @pytest.mark.parametrize("placed", [False, True],
                             ids=["callers-tree", "placed-table"])
    def test_each_pool_program_compiles_once(self, lm, placed):
        """A placed table is committed to its device, and so is every
        result of a program that takes it: the pool, the slot rows and the
        counters start committed beside it, or the second call of each
        program would compile again (on the chip: 16 s inside the window)."""
        model, params = lm
        tree = self._column_major(params, ("tok",)) if placed else params
        engine = serve.SlotEngine(model, tree, num_slots=2)
        assert all(leaf.committed == placed
                   for leaf in jax.tree_util.tree_leaves(
                       (engine.cache, engine._slots)))
        for _ in range(3):      # one bucket, three admissions, their steps
            engine.admit(serve.Request(np.arange(1, 6, dtype=np.int32), 3))
            while not engine.idle():
                engine.step()
        assert engine._prefill._cache_size() == 1
        assert engine._decode._cache_size() == 1

    @pytest.mark.parametrize("temperature", [0.0, 0.8],
                             ids=["greedy", "sampled"])
    def test_serves_the_same_tokens_bitwise(self, lm, temperature):
        """Fixed seeds: the engine over the caller's tree, as
        ``self.params = params`` served it before, and the engine over a
        tree it had to place, emit the same tokens; greedy ones are
        ``generate()``'s."""
        model, params = lm
        kw = dict(slots=3, temperature=temperature, seed=1031)
        before, engine = _run_engine(model, params, self._requests(), **kw)
        assert engine.params is params
        after, engine = _run_engine(
            model, self._column_major(params, ("tok", "pos")),
            self._requests(), **kw)
        assert engine.stats()["params"]["placed_leaves"] == 2
        assert after == before
        if not temperature:
            for (p, n), got in zip(self._requests(), before):
                assert got == _gen_ref(model, params, p, n)


class TestScheduler:
    def test_coalesced_admission_and_completion(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.05)
        try:
            prompt = np.arange(5, dtype=np.int32)
            handles = [sched.submit(prompt, max_new_tokens=5)
                       for _ in range(3)]
            ref = _gen_ref(model, params, prompt, 5)
            for h in handles:
                assert h.wait_done(60.0) == ref
            # the batching window coalesced the burst: (far) fewer decode
            # steps than 3 sequential runs would take
            assert engine.stats()["decode_steps"] <= 10
        finally:
            sched.close()

    def test_queue_full_is_named(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=1)
        sched = serve.Scheduler(engine, max_pending=1, stage_depth=1)
        try:
            prompt = np.arange(4, dtype=np.int32)
            handles = [sched.submit(prompt, max_new_tokens=50, timeout=5.0)]
            with pytest.raises(serve.QueueFullError):
                for _ in range(16):
                    handles.append(sched.submit(prompt, max_new_tokens=50,
                                                timeout=0.05))
            for h in handles:     # everything accepted still completes
                h.wait_done(120.0)
        finally:
            sched.close()

    def test_drain_finishes_inflight_rejects_queued(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=1)
        sched = serve.Scheduler(engine, batch_window=0.0)
        try:
            prompt = np.arange(4, dtype=np.int32)
            inflight = sched.submit(prompt, max_new_tokens=40)
            # in a slot before draining starts
            deadline = time.monotonic() + 30
            while not inflight.tokens() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert inflight.tokens(), "request never started decoding"
            queued = sched.submit(prompt, max_new_tokens=40)
            assert sched.drain(timeout=60.0)
            # in-flight finished with its full token budget
            assert len(inflight.wait_done(5.0)) == 40
            # queued-but-unadmitted failed with the NAMED drain error
            with pytest.raises(serve.SchedulerDrainingError):
                queued.wait_done(5.0)
            # new submits are refused by name
            with pytest.raises(serve.SchedulerDrainingError):
                sched.submit(prompt, max_new_tokens=2)
        finally:
            sched.close()

    def test_decode_loop_death_fails_everything_by_name(self, lm):
        # review finding: an engine that dies mid-decode (device error,
        # donated cache invalidated) must not leave a zombie scheduler —
        # every in-flight AND queued handle fails naming the cause, and
        # later submits are refused with the same diagnosis
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=1)
        sched = serve.Scheduler(engine)
        try:
            prompt = np.arange(4, dtype=np.int32)
            inflight = sched.submit(prompt, max_new_tokens=40)
            deadline = time.monotonic() + 30
            while not inflight.tokens() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert inflight.tokens(), "request never started decoding"
            queued = sched.submit(prompt, max_new_tokens=40)

            def boom():
                raise RuntimeError("device died")

            engine.launch_step = boom
            for h in (inflight, queued):
                with pytest.raises(serve.SchedulerClosedError,
                                   match="device died"):
                    h.wait_done(30.0)
            with pytest.raises(serve.SchedulerClosedError,
                               match="device died"):
                sched.submit(prompt, max_new_tokens=2)
        finally:
            sched.close()

    def test_close_fails_pending_by_name(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=1)
        sched = serve.Scheduler(engine)
        prompt = np.arange(4, dtype=np.int32)
        handles = [sched.submit(prompt, max_new_tokens=30)
                   for _ in range(4)]
        sched.close()
        outcomes = []
        for h in handles:
            try:
                h.wait_done(10.0)
                outcomes.append("done")
            except serve.SchedulerClosedError:
                outcomes.append("closed")
        # every handle TERMINATED (none hung); the ones the shutdown cut
        # off carry the named error
        assert len(outcomes) == 4 and "closed" in outcomes


class TestSocketLayer:
    @pytest.fixture()
    def stack(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=4)
        sched = serve.Scheduler(engine, batch_window=0.002)
        fe = serve.Frontend(sched, port=0)
        yield model, params, fe
        fe.close()
        sched.close()

    def test_stream_roundtrip_interleaved(self, stack, lm):
        model, params = lm
        _, _, fe = stack
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            rng = np.random.default_rng(5)
            reqs = [(rng.integers(0, 97, rng.integers(3, 12)),
                     int(rng.integers(2, 8))) for _ in range(6)]
            handles = [cli.submit(p.tolist(), max_new_tokens=n)
                       for p, n in reqs]
            for h, (p, n) in zip(handles, reqs):
                assert h.wait_done(120.0) == _gen_ref(model, params, p, n)
                assert h.reason == "length"
        finally:
            cli.close()

    def test_streaming_iterator(self, stack, lm):
        model, params = lm
        _, _, fe = stack
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            prompt = np.arange(6, dtype=np.int32)
            h = cli.submit(prompt.tolist(), max_new_tokens=5)
            streamed = list(h.iter_tokens(timeout=60.0))
            assert streamed == _gen_ref(model, params, prompt, 5)
        finally:
            cli.close()

    def test_a_steps_token_frames_leave_as_one_write(self, stack, lm):
        # a decode step emits a token frame a busy slot: the connection
        # holds them until the engine has emitted them all and writes
        # once; a done frame takes what is pending with it, so each
        # request's frames keep their order
        from tpu_dist.serve.frontend import (_HELLO, _MAGIC, _VERSION,
                                             read_frame, send_frame)
        model, params = lm
        _, _, fe = stack
        ours, theirs = socket.socketpair()

        class Counted:
            writes = 0

            def __getattr__(self, name):
                return getattr(theirs, name)

            def sendall(self, data):
                Counted.writes += 1
                return theirs.sendall(data)

            def sendmsg(self, parts):
                Counted.writes += 1
                return theirs.sendmsg(parts)

        t = threading.Thread(target=fe._serve_conn, args=(Counted(),),
                             daemon=True)
        t.start()
        try:
            ours.sendall(_HELLO.pack(_MAGIC, _VERSION))
            assert len(ours.recv(_HELLO.size)) == _HELLO.size
            hello_writes = Counted.writes
            prompts = [np.arange(3 + i, dtype=np.int32) for i in range(4)]
            for i, pr in enumerate(prompts):
                send_frame(ours, {"type": "submit", "id": i,
                                  "prompt": pr.tolist(),
                                  "max_new_tokens": 6})
            got, done, frames = {i: [] for i in range(4)}, {}, 0
            ours.settimeout(120.0)
            while len(done) < 4:
                f = read_frame(ours)
                frames += 1
                if f["type"] == "token":
                    assert f["id"] not in done
                    got[f["id"]].append(f["t"])
                else:
                    assert f["type"] == "done" and f["n"] == 6
                    done[f["id"]] = f["reason"]
            for i, pr in enumerate(prompts):
                assert got[i] == _gen_ref(model, params, pr, 6)
            assert frames == 28
            # 4 first tokens, then at most 5 + 3 staggered steps
            assert Counted.writes - hello_writes <= 12
            assert fe.scheduler.engine._flushers     # hooked while connected
        finally:
            ours.close()
            t.join(30.0)
        assert not t.is_alive() and not fe.scheduler.engine._flushers

    def test_invalid_request_error_frame(self, stack):
        _, _, fe = stack
        cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
        try:
            h = cli.submit(list(range(10)), max_new_tokens=500)
            with pytest.raises(serve.RequestFailedError) as ei:
                h.wait_done(30.0)
            assert ei.value.error == "ValueError"
        finally:
            cli.close()

    def test_gateway_proxies_and_names_backend_unavailable(self, stack,
                                                           lm):
        model, params = lm
        _, _, fe = stack
        gw = serve.Gateway(host="127.0.0.1", port=0, backend=fe.addr,
                           backend_timeout=10.0)
        cli = serve.ServeClient("127.0.0.1", gw.port, connect_retry=10)
        try:
            prompt = np.arange(5, dtype=np.int32)
            got = cli.generate(prompt.tolist(), max_new_tokens=4,
                               timeout=120.0)
            assert got == _gen_ref(model, params, prompt, 4)
        finally:
            cli.close()
            gw.close()
        # a gateway whose backend address is dead fails submits with the
        # NAMED availability error inside its bounded retry window
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
        s.close()
        gw2 = serve.Gateway(host="127.0.0.1", port=0,
                            backend=f"127.0.0.1:{dead_port}",
                            backend_timeout=1.0)
        cli2 = serve.ServeClient("127.0.0.1", gw2.port, connect_retry=10)
        try:
            h = cli2.submit([1, 2, 3], max_new_tokens=2)
            with pytest.raises(serve.RequestFailedError) as ei:
                h.wait_done(30.0)
            assert ei.value.error == "BackendUnavailableError"
        finally:
            cli2.close()
            gw2.close()

    def test_client_fails_inflight_on_server_death(self):
        # no-silent-drop from the client's side: a raw listener speaks the
        # hello then dies mid-request — the in-flight handle must
        # terminate with ServerGoneError, not hang
        from tpu_dist.serve.frontend import _HELLO, _MAGIC, _VERSION

        lst = socket.socket()
        lst.bind(("127.0.0.1", 0))
        lst.listen(1)
        port = lst.getsockname()[1]

        def server():
            conn, _ = lst.accept()
            conn.recv(_HELLO.size)
            conn.sendall(_HELLO.pack(_MAGIC, _VERSION))
            time.sleep(0.3)
            conn.close()

        t = threading.Thread(target=server, daemon=True)
        t.start()
        cli = serve.ServeClient("127.0.0.1", port, connect_retry=5)
        h = cli.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(serve.ServerGoneError):
            h.wait_done(30.0)
        lst.close()
        cli.close()


class TestCancellationAndDeadlines:
    """ISSUE 13 serve degradation: per-request deadlines + mid-decode
    cancellation (closes PR 10's 'no mid-decode cancellation' limit)."""

    def test_cancel_frees_slot_at_next_iteration_boundary(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        errs = []
        r = serve.Request(np.arange(4, dtype=np.int32), 30,
                          on_error=lambda q, e: errs.append(e))
        engine.admit(r)
        engine.step()
        assert engine.active_count() == 1
        r.cancel()
        assert engine.sweep_expired() == 1
        assert engine.idle() and engine.free_slots() == 2
        assert isinstance(errs[0], serve.RequestCancelledError)

    def test_deadline_frees_slot_mid_decode(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        errs = []
        r = serve.Request(np.arange(4, dtype=np.int32), 30,
                          deadline_ms=30,
                          on_error=lambda q, e: errs.append(e))
        engine.admit(r)
        engine.step()
        time.sleep(0.05)  # past the 30 ms budget
        assert engine.sweep_expired() == 1
        assert engine.idle()
        assert isinstance(errs[0], serve.DeadlineExceededError)

    def test_expired_request_is_shed_before_admission(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        r = serve.Request(np.arange(4, dtype=np.int32), 4, deadline_ms=1)
        time.sleep(0.01)
        with pytest.raises(serve.DeadlineExceededError):
            engine.admit(r)
        assert engine.idle()  # no slot was spent on the stale request

    def test_scheduler_handle_cancel_terminates_by_name(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        with serve.Scheduler(engine, batch_window=0.0) as sched:
            h = sched.submit(list(range(4)), max_new_tokens=50)
            # wait for the first token so the cancel lands MID-decode
            for _ in h.iter_tokens(timeout=30.0):
                break
            h.cancel()
            with pytest.raises(serve.RequestCancelledError):
                h.wait_done(10.0)
            deadline = time.monotonic() + 10.0
            while not engine.idle() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert engine.idle()  # the slot freed at a boundary, not at
            # max_new_tokens

    def test_client_disconnect_cancels_and_span_closes_cancelled(
            self, lm, monkeypatch):
        from tpu_dist.obs import recorder as rec_mod
        model, params = lm
        monkeypatch.setenv("TPU_DIST_OBS", "1")
        rec_mod.reset()
        engine = serve.SlotEngine(model, params, num_slots=2)
        sched = serve.Scheduler(engine, batch_window=0.0)
        fe = serve.Frontend(sched, port=0)
        try:
            cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
            h = cli.submit(list(range(4)), max_new_tokens=50)
            for _ in h.iter_tokens(timeout=30.0):
                break             # at least one token decoded
            cli.close()           # client vanishes mid-decode
            deadline = time.monotonic() + 10.0
            while not engine.idle() and time.monotonic() < deadline:
                time.sleep(0.005)
            assert engine.idle(), "slot not freed after client disconnect"
            assert engine.completed == 0  # cancelled, not decoded to 50
            rec = rec_mod.get_recorder()
            spans = [e for e in rec.snapshot()
                     if e.get("kind") == "serve"]
            assert spans and spans[-1]["outcome"] == "error:Cancelled"
        finally:
            fe.close()
            sched.close()
            rec_mod.reset()

    def test_deadline_ms_over_the_wire_names_the_error(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        sched = serve.Scheduler(engine, batch_window=0.0)
        fe = serve.Frontend(sched, port=0)
        try:
            cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
            h = cli.submit(list(range(4)), max_new_tokens=50,
                           deadline_ms=25)
            with pytest.raises(serve.RequestFailedError) as ei:
                h.wait_done(30.0)
            assert ei.value.error == "DeadlineExceededError"
            cli.close()
        finally:
            fe.close()
            sched.close()

    def test_explicit_cancel_frame_over_the_wire(self, lm):
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        sched = serve.Scheduler(engine, batch_window=0.0)
        fe = serve.Frontend(sched, port=0)
        try:
            cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
            h = cli.submit(list(range(4)), max_new_tokens=55)
            h.cancel()  # sends the cancel frame
            with pytest.raises(serve.RequestFailedError) as ei:
                h.wait_done(30.0)
            assert ei.value.error == "RequestCancelledError"
            cli.close()
        finally:
            fe.close()
            sched.close()


@pytest.mark.netchaos
class TestServeNetchaos:
    """Serve-wire cells of the ISSUE 13 chaos matrix that need the full
    stack (frame-level cells live in tests/test_netchaos.py)."""

    def test_corrupt_submit_fails_bounded_and_named(self, lm):
        from tpu_dist.resilience import netchaos
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        sched = serve.Scheduler(engine, batch_window=0.0)
        fe = serve.Frontend(sched, port=0)
        try:
            cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
            netchaos.install("corrupt:surface=serve,frame=1")
            h = cli.submit(list(range(4)), max_new_tokens=4)
            # the server's framing layer rejects the corrupt frame
            # (FrameCorruptError) and drops the connection; the client's
            # no-silent-drop contract converts that into a named terminal
            # error on the handle — bounded, never a hang
            with pytest.raises((serve.ServerGoneError,
                                serve.RequestFailedError)):
                h.wait_done(15.0)
        finally:
            netchaos.uninstall()
            fe.close()
            sched.close()

    def test_delayed_wire_still_completes(self, lm):
        from tpu_dist.resilience import netchaos
        model, params = lm
        engine = serve.SlotEngine(model, params, num_slots=2)
        sched = serve.Scheduler(engine, batch_window=0.0)
        fe = serve.Frontend(sched, port=0)
        try:
            cli = serve.ServeClient("127.0.0.1", fe.port, connect_retry=10)
            netchaos.install("delay:surface=serve,delay=0.002")
            prompt = np.arange(5, dtype=np.int32)
            got = cli.generate(prompt.tolist(), max_new_tokens=4,
                               timeout=60.0)
            assert got == _gen_ref(model, params, prompt, 4)
            cli.close()
        finally:
            netchaos.uninstall()
            fe.close()
            sched.close()


class TestObsIntegration:
    def test_request_span_fields_and_diagnose(self, lm, monkeypatch,
                                              tmp_path):
        from tpu_dist.obs import recorder as rec_mod
        from tpu_dist.obs import trace as trace_mod

        model, params = lm
        monkeypatch.setenv("TPU_DIST_OBS", "1")
        rec_mod.reset()
        try:
            engine = serve.SlotEngine(model, params, num_slots=2)
            outs = []
            r = serve.Request(np.arange(4, dtype=np.int32), 3,
                              on_token=lambda q, t: outs.append(t))
            serve.SlotEngine.obs_open(r)
            engine.admit(r)
            while not engine.idle():
                engine.step()
            # a second request left PENDING (queued, never admitted):
            # the stuck-request shape the diagnosis must name
            stuck = serve.Request(np.arange(5, dtype=np.int32), 4)
            serve.SlotEngine.obs_open(stuck)

            rec = rec_mod.get_recorder()
            evs = [e for e in rec.snapshot() if e.get("kind") == "serve"]
            assert len(evs) == 2
            done = next(e for e in evs if e["outcome"] == "ok")
            assert done["req"] == r.id and done["tokens"] == 3
            assert done["queue_ns"] >= 0 and done["prefill_ns"] > 0
            assert done["slot"] == 0

            path = rec.dump("test", dir=str(tmp_path))
            with open(path) as f:
                dump = json.load(f)
            diag = trace_mod.diagnose([dump])
            assert diag["stuck_requests"], diag
            sr = diag["stuck_requests"][0]
            assert sr["req"] == stuck.id and sr["phase"] == "queued"
            assert "stuck request" in trace_mod.render_diagnosis(diag)
        finally:
            rec_mod.reset()


# bench_serve --smoke IS a tier-1 test (ISSUE 12 CI gate): cross-checks
# the STREAMED continuous-batching tokens against offline generate()
def test_bench_serve_smoke():
    env = dict(os.environ,
               PYTHONPATH=_REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                              ""),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_serve", "--smoke"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    rows = [json.loads(line) for line in r.stdout.strip().splitlines()]
    modes = {row.get("mode"): row for row in rows
             if row.get("metric") == "serve_batching_mode"}
    assert modes["continuous"]["tokens_per_sec"] > 0
    assert modes["static"]["tokens_per_sec"] > 0
    assert modes["continuous"]["occupancy"] >= modes["static"]["occupancy"]
    assert any(row.get("metric") == "serve_continuous_vs_static_speedup"
               for row in rows)
