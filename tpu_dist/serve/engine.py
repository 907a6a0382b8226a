"""Slot-based continuous-batching decode engine.

The serving counterpart of ``TransformerLM.generate()`` (ROADMAP item 4):
where ``generate`` runs one batch to completion — every sequence occupies
its row until the LONGEST one finishes — the :class:`SlotEngine` owns a
fixed pool of ``num_slots`` KV-cache rows with *per-slot* lengths and
admits a new request into any free slot **between decode iterations**,
while the other slots keep decoding.  On a mixed-length workload (short
and long prompts, varied ``max_new_tokens``) that removes the
run-to-completion barrier that leaves most of a static batch idle
(measured ≥2x aggregate tokens/sec, ``benchmarks/bench_serve.py``).

Two compiled programs drive the pool (tpu_dist/models/transformer.py):

- ``prefill_into_slot``: a request's (bucket-padded) prompt fills ONE
  cache slot in a single forward — the other slots' rows are untouched,
  so admission never disturbs in-flight decodes.  One padded length = one
  XLA program; prompt lengths are padded to power-of-two buckets to bound
  retraces (padding K/V is masked or overwritten before it is ever
  attended — token-identical to the unpadded prefill, tested).  The
  program of a bucket takes SEVERAL prompts, each into its own slot, as
  many as the pool's largest bucket holds of that bucket
  (:func:`prefill_width`, from the two shapes alone): a layer's weights
  are read once for all of them.  A program with fewer requests than its
  width carries absent prompts (length 0), which touch no slot.
- ``decode_step``: ONE batched iteration over the whole pool — each slot
  appends at its own length and samples its next token on device.  This
  is the same method ``generate``'s scan runs, so serving output is
  token-identical to offline generation (the ``--smoke`` gate pins it).

The engine is deliberately single-threaded (the scheduler's loop thread
drives it); everything thread-sensitive (handles, queues) lives in
:mod:`tpu_dist.serve.scheduler`.

Each pool operation has two halves.  The LAUNCH half (``launch_admit`` /
``launch_step``) enqueues the program and returns: what the next program
needs of a slot (last token, length, step count, temperature, key) is an
argument and a result of both programs and stays on the device, so step
n + 1 can be launched while step n's tokens are still unread.  The
COLLECT half (``collect``) waits for the oldest program in flight — the
one place the loop thread blocks on the device — emits its tokens and does
its bookkeeping.  ``admit`` / ``step`` are launch immediately followed by
collect; the scheduler's loop launches one program ahead (``settle``).

Per-request observability: when the flight recorder is armed
(``TPU_DIST_OBS=1``) every request opens a ``serve`` span at submit and
stamps its queue / prefill / decode split onto it, so a crash dump (or
``python -m tpu_dist.obs diagnose``) names the request a stuck server was
working on — not just "the rank is busy".
"""

from __future__ import annotations

import collections
import time
from typing import Callable, List, Optional

import numpy as np

from ..nn import cache as kvcache
from ..obs.compiles import (longest as longest_compiles,
                            totals as compile_totals,
                            totals_since as compile_totals_since)
from ..obs.spans import KEPT, LoopClock, phase_times, reset_phases, span
from ..ops.decode_attention import kv_blocks
from ..utils.metrics import LatencyHistogram

__all__ = ["SlotEngine", "Request", "RequestHandle", "ServeError",
           "QueueFullError", "SchedulerDrainingError",
           "SchedulerClosedError", "DeadlineExceededError",
           "RequestCancelledError", "error_outcome", "sample_tokens",
           "SERVE_PHASES", "LOOP_KINDS", "LOOP_WAITS"]

# The serving loop's host phases (tpu_dist.obs.spans), in the order one
# iteration runs them: ``stats()["phases"]`` reports exactly these and
# ``reset_stats()`` zeroes them.  ``stage.put`` runs on the staging thread
# (inside ``prefill.prepare`` only when ``_admit`` finds nothing staged);
# ``sched.wait`` is the scheduler's, every other one is the loop thread's.
SERVE_PHASES = ("sweep", "sched.wait", "stage.put",
                "prefill.prepare", "prefill.dispatch", "prefill.readback",
                "prefill.emit",
                "decode.dispatch", "decode.readback", "decode.emit")
# What the loop clock (``stats()["loop"]``) calls an iteration, by what it
# launched, and the phases in which the loop thread waits by design: for
# the device, and (the scheduler's sleep) for work.
LOOP_KINDS = ("prefill", "decode", "idle")
LOOP_WAITS = ("prefill.readback", "decode.readback", "sched.wait")


class ServeError(RuntimeError):
    """Base class for named serving-layer failures — every request the
    layer cannot complete fails with a subclass of this (never silently)."""


class QueueFullError(ServeError):
    """The admission queue is at capacity: the caller should shed load or
    retry after a backoff (the bounded queue IS the backpressure)."""


class SchedulerDrainingError(ServeError):
    """The scheduler is draining (preemption notice): it finishes in-flight
    requests but admits no new ones."""


class SchedulerClosedError(ServeError):
    """The scheduler shut down with this request still queued or decoding:
    the request did not complete, and this names why."""


class DeadlineExceededError(ServeError):
    """The request's ``deadline_ms`` passed before it finished: queued
    requests are shed before staging (they would be stale on arrival),
    decoding requests free their slot at the next iteration boundary —
    load shedding by deadline instead of latency collapse."""


class RequestCancelledError(ServeError):
    """The request was cancelled (client disconnect, or an explicit
    ``cancel`` frame) — its slot was freed at the next iteration boundary
    instead of decoding to ``max_new_tokens`` for nobody."""


def error_outcome(exc: BaseException) -> str:
    """The obs-span outcome string for a failed request.  Cancellation is
    a first-class outcome (``error:Cancelled``) rather than an exception
    class name — the span vocabulary `obs diagnose` keys on."""
    if isinstance(exc, RequestCancelledError):
        return "error:Cancelled"
    return f"error:{type(exc).__name__}"


def _now() -> float:
    return time.perf_counter()


class RequestHandle:
    """Caller-side future for one request: the token stream plus terminal
    state.  Every submitted handle terminates — with ``done`` or with a
    named error — the layer never drops a request silently.

    Thread-safe.  ``wait_done(timeout)`` blocks for the terminal state and
    re-raises the captured error (deadline-bounded: a dead server turns
    into ``TimeoutError``, not a hang).  ``iter_tokens`` yields tokens as
    they stream in.
    """

    def __init__(self, req_id: int):
        import threading
        self.id = req_id
        self._cv = threading.Condition()
        self._tokens: List[int] = []
        self._reason: Optional[str] = None
        self._error: Optional[BaseException] = None
        self._cancel: Optional[Callable[[], None]] = None

    def cancel(self) -> None:
        """Request cancellation: the serving side frees the slot at the
        next iteration boundary and the handle terminates with
        :class:`RequestCancelledError`.  No-op when already terminal or
        when no cancel path is wired (bare handles)."""
        cb = self._cancel
        if cb is not None:
            cb()

    # -- producer side (engine/scheduler/client reader) ----------------------

    def _on_token(self, token: int) -> None:
        with self._cv:
            self._tokens.append(int(token))
            self._cv.notify_all()

    def _on_done(self, reason: str) -> None:
        with self._cv:
            self._reason = reason
            self._cv.notify_all()

    def _on_error(self, exc: BaseException) -> None:
        with self._cv:
            if self._reason is None and self._error is None:
                self._error = exc
            self._cv.notify_all()

    # -- consumer side -------------------------------------------------------

    @property
    def done(self) -> bool:
        with self._cv:
            return self._reason is not None or self._error is not None

    @property
    def reason(self) -> Optional[str]:
        """Terminal reason ('eos' | 'length'), None while running/failed."""
        with self._cv:
            return self._reason

    @property
    def error(self) -> Optional[BaseException]:
        with self._cv:
            return self._error

    def tokens(self) -> List[int]:
        """Snapshot of the tokens streamed so far."""
        with self._cv:
            return list(self._tokens)

    def wait_done(self, timeout: float) -> List[int]:
        """Block until the request terminates; returns the generated tokens
        or re-raises the named failure.  ``TimeoutError`` after ``timeout``
        seconds — never an unbounded hang."""
        deadline = _now() + timeout
        with self._cv:
            while self._reason is None and self._error is None:
                left = deadline - _now()
                if left <= 0:
                    raise TimeoutError(
                        f"request {self.id} not finished after "
                        f"{timeout:.1f}s ({len(self._tokens)} tokens so "
                        f"far)")
                self._cv.wait(left)
            if self._error is not None:
                raise self._error
            return list(self._tokens)

    def iter_tokens(self, timeout: float = 60.0):
        """Yield tokens as they stream in; raises the request's named error
        (or ``TimeoutError`` when ``timeout`` passes with no progress)."""
        i = 0
        while True:
            with self._cv:
                deadline = _now() + timeout
                while (i >= len(self._tokens) and self._reason is None
                       and self._error is None):
                    left = deadline - _now()
                    if left <= 0:
                        raise TimeoutError(
                            f"request {self.id}: no token progress in "
                            f"{timeout:.1f}s")
                    self._cv.wait(left)
                if i < len(self._tokens):
                    tok = self._tokens[i]
                else:
                    if self._error is not None:
                        raise self._error
                    return
            i += 1
            yield tok


class Request:
    """One decode request moving through the serving layer."""

    _ids = iter(range(1, 1 << 62))

    def __init__(self, prompt, max_new_tokens: int,
                 temperature: float = 0.0, eos_id: Optional[int] = None,
                 seed: int = 0, req_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 on_token: Optional[Callable] = None,
                 on_done: Optional[Callable] = None,
                 on_error: Optional[Callable] = None):
        self.id = req_id if req_id is not None else next(Request._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.seed = int(seed)
        self.on_token = on_token
        self.on_done = on_done
        self.on_error = on_error
        self.t_submit = _now()
        # absolute monotonic deadline: past it the request is shed (if
        # still queued) or its slot freed at the next iteration boundary
        self.deadline: Optional[float] = (
            None if deadline_ms is None
            else self.t_submit + float(deadline_ms) / 1000.0)
        self.cancelled = False      # single-writer flag (GIL-safe)
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.emitted = 0
        self.staged = None          # (padded device/np prompt, bucket len)
        self.obs_span = None        # armed flight-recorder span (or None)

    def cancel(self) -> None:
        self.cancelled = True

    def expired(self, now: Optional[float] = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else _now()) >= self.deadline)

    def emit(self, token: int) -> None:
        self.emitted += 1
        if self.t_first is None:
            self.t_first = _now()
        if self.on_token is not None:
            self.on_token(self, int(token))

    def finish(self, reason: str) -> None:
        if self.on_done is not None:
            self.on_done(self, reason)

    def fail(self, exc: BaseException) -> None:
        if self.on_error is not None:
            self.on_error(self, exc)


def sample_tokens(logits, temps, keys, steps, sampling: bool):
    """Per-slot next token from (B, vocab) logits: greedy argmax at
    temperature 0 (the parity mode the smoke gate cross-checks against
    ``generate``), categorical at temperature > 0 with a per-request key
    folded by step — the same ``fold_in(key, step)`` schedule ``generate``
    uses, so a single-request engine run with the same key reproduces it.
    ``sampling`` is a static flag: the all-greedy pool (the common case)
    compiles without the sampling branch at all.

    Module-level (traced) so the single-rank :class:`SlotEngine` and the
    tensor-parallel shards (tpu_dist/serve/sharded.py) run the SAME
    sampling math — every shard computes the identical next token from
    the identical post-all-reduce logits, which is what lets followers
    stay in lockstep without a per-step token broadcast."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not sampling:
        return greedy

    def one(key, step, row, temp):
        return jax.random.categorical(
            jax.random.fold_in(key, step),
            row / jnp.maximum(temp, 1e-6))

    sampled = jax.vmap(one)(keys, steps, logits, temps)
    return jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)


def _bucket_lengths(max_prompt: int, min_bucket: int = 16) -> List[int]:
    """Power-of-two padded-prompt lengths up to ``max_prompt`` (always
    includes ``max_prompt`` itself): one compiled prefill per bucket."""
    out = []
    b = min_bucket
    while b < max_prompt:
        out.append(b)
        b *= 2
    out.append(max_prompt)
    return out


def prefill_width(bucket: int, max_len: int) -> int:
    """Prompts ONE prefill program of ``bucket`` positions takes over a pool
    of ``max_len``: as many whole buckets as the pool's largest bucket
    (``max_len``) holds rows, rounded down to a power of two, so a prefill
    never holds more prompt rows than the one a legal request may need
    anyway.  From the two shapes alone."""
    return 1 << (max(1, int(max_len) // int(bucket)).bit_length() - 1)


def seed_key(seed: int) -> np.ndarray:
    """``jax.random.key_data(jax.random.key(seed))`` of the default
    (threefry) generator, computed on the host: the words of the seed, the
    high one kept only where 64-bit integers are enabled.  A request's
    sampling key never costs the loop thread a device round trip."""
    import jax

    high = (seed >> 32) & 0xFFFFFFFF if jax.config.jax_enable_x64 else 0
    return np.array([high, seed & 0xFFFFFFFF], np.uint32)


# -- slot state on the device -------------------------------------------------
# What the next pool program needs of every slot: {"tokens", "lengths",
# "steps", "temps", "keys"}, one row a slot.  An argument and a result of
# both programs, beside the cache and the routed-row counters.

def masked_rows(slots: dict, live):
    """``(tokens, lengths, temps)`` as a decode step feeds them to the
    model: a row the step does not carry reads as a free slot (token 0,
    length 0, greedy), whatever its device row still holds."""
    import jax.numpy as jnp

    return (jnp.where(live, slots["tokens"], 0),
            jnp.where(live, slots["lengths"], 0),
            jnp.where(live, slots["temps"], 0.0))


def advance_rows(slots: dict, live, nxt) -> dict:
    """The rows a decode step carried, after it: the sampled token, one
    more resident position, one more step of the sampling schedule."""
    import jax.numpy as jnp

    one = live.astype(jnp.int32)
    return dict(slots, tokens=jnp.where(live, nxt, slots["tokens"]),
                lengths=slots["lengths"] + one, steps=slots["steps"] + one)


def set_row(slots: dict, slot, token, length, temp, key) -> dict:
    """One slot's row as an admission leaves it: first token sampled,
    ``length`` positions resident, step 1 of the sampling schedule.  With
    vectors, row ``i`` of an admission of several; a ``slot`` past the pool
    (an absent prompt's) sets no row."""
    put = lambda name, value: slots[name].at[slot].set(value, mode="drop")
    return {"tokens": put("tokens", token), "lengths": put("lengths", length),
            "steps": put("steps", 1), "temps": put("temps", temp),
            "keys": put("keys", key)}


# -- where the parameters lie -------------------------------------------------
# A program reads a parameter in the format its operation wants and, handed
# another, converts it at EVERY call.  Only a gathered table can differ: the
# device's default format of a tall table whose width is no multiple of the
# 128 lanes (GPT-2 XL's bf16[50257, 1600]) is vocabulary-minor, and a row
# gather of it opened both pool programs with a copy of the whole table.

def gathered_tables(model) -> list:
    """``(path, name)`` of every parameter the programs of ``model`` gather
    rows from: the weight of each ``nn.Embedding`` (``tok``, ``pos``)."""
    from ..nn import Embedding

    return [(path, "weight") for path, module in model.named_modules()
            if isinstance(module, Embedding)]


def row_major(sharding):
    """The format a gathered table is read in: a row contiguous."""
    from jax.experimental.layout import Format, Layout

    return Format(Layout(major_to_minor=(0, 1)), sharding)


def place_params(model, params):
    """``params`` as the programs of ``model`` read them, and what that
    took: ``{"placed_leaves", "placed_bytes", "leaves"}``.  A gathered table
    (:func:`gathered_tables`) that is on a device in another format than
    :func:`row_major` is placed again, once, and ``jax.jit`` follows a
    committed argument's format; every other leaf is the caller's array.
    Where the default format is row-major already (a width that is a
    multiple of 128; every CPU run) the tree is returned as it came."""
    import jax

    moved = {}
    for path, name in gathered_tables(model):
        leaf = params.get(path, {}).get(name)
        layout = leaf.format.layout if isinstance(leaf, jax.Array) else None
        if layout is not None and layout.major_to_minor != (0, 1):
            moved[path, name] = jax.device_put(leaf, row_major(leaf.sharding))
    if moved:
        params = {path: {name: moved.get((path, name), leaf)
                         for name, leaf in leaves.items()}
                  for path, leaves in params.items()}
    return params, {
        "placed_leaves": len(moved),
        "placed_bytes": sum(int(leaf.nbytes) for leaf in moved.values()),
        "leaves": len(jax.tree_util.tree_leaves(params))}


def decode_need_facts(model, params) -> dict:
    """What ONE decode step needs of ``params``, from their shapes alone:
    ``fixed_bytes`` / ``fixed_params``, every leaf a step reads once
    whatever it routes (all but the gathered tables, of which it reads one
    row a slot, and the routed experts' stacked weights); ``experts``,
    ``{MoELayer path: (bytes, parameters) of ONE expert}``, read only where
    a row reaches it; ``attend_flops``, the operations one resident
    position costs the attention layers, summed over them (each layer's
    ``attend_flops_per_position``; a layer of whole state has none);
    ``state_flops``, the operations ONE row's update of its whole state
    costs the recurrent layers (each layer's ``state_flops_per_row``; an
    attention layer has none).  Host facts for ``stats()["decode_need"]``,
    fixed at construction."""
    from ..nn import MoELayer

    tables = set(gathered_tables(model))
    experts, routed = {}, set()
    attend_flops = state_flops = 0
    for path, module in model.named_modules():
        attend_flops += getattr(module, "attend_flops_per_position", 0)
        state_flops += getattr(module, "state_flops_per_row", 0)
        if isinstance(module, MoELayer) and path in params:
            own = {n: a for n, a in params[path].items()
                   if n in ("w1", "w2", "w3", "b1", "b2")}
            routed.update((path, n) for n in own)
            held = module.experts_held
            experts[path] = (sum(int(a.nbytes) for a in own.values()) // held,
                             sum(int(a.size) for a in own.values()) // held)
    fixed = [a for path, leaves in params.items()
             for n, a in leaves.items()
             if (path, n) not in tables and (path, n) not in routed]
    return {"fixed_bytes": sum(int(a.nbytes) for a in fixed),
            "fixed_params": sum(int(a.size) for a in fixed),
            "experts": experts, "attend_flops": int(attend_flops),
            "state_flops": int(state_flops)}


def served_dtype(model, params):
    """The type ``params`` serve ``model`` in: the embedding table's (what
    every activation of the two pool programs follows), float32 for a model
    that gathers from none."""
    tables = [params[path][name] for path, name in gathered_tables(model)
              if name in params.get(path, {})]
    return tables[0].dtype if tables else np.dtype(np.float32)


def residual_facts(model, params) -> dict:
    """What the residual between ``model``'s sublayers is made of, from the
    model's own answers: ``streams`` it keeps a token, ``sublayers`` that
    read and write them, and ``row_bytes``, what ONE row must move through
    the mixes of all of them in the type the embedding is served in
    (``residual_numbers_per_row``: 0 for ``x + f(x)``, whose add rides in
    the sublayer's own output).  Host facts for ``stats()["residual"]``,
    fixed at construction."""
    return {"streams": model.streams, "sublayers": 2 * model.depth,
            "row_bytes": (model.residual_numbers_per_row()
                          * served_dtype(model, params).itemsize)}


def short_conv_facts(model) -> dict:
    """The token mixers of ``model`` that are a short convolution and
    nothing else (each module's ``short_conv_params``;
    :class:`tpu_dist.nn.GatedShortConv`): ``layers``, how many, and
    ``params``, their matrices' and taps' parameters summed, of which one
    row costs twice that in operations.  Host facts for
    ``stats()["conv"]``, fixed at construction."""
    sizes = [module.short_conv_params for _, module in model.named_modules()
             if hasattr(module, "short_conv_params")]
    return {"layers": len(sizes), "params": int(sum(sizes))}


def beside(params, state):
    """``state`` committed where the committed leaves of ``params`` are, if
    they share one sharding; as it came if none is committed.  A program's
    results are committed as soon as one argument is (a placed table is: a
    format names its sharding), and the pool, the slot rows and the
    counters are results fed back as arguments: starting uncommitted they
    would compile each program a second time at its second call, inside a
    measured window.  No copy: the buffers are where they were."""
    import jax

    homes = {leaf.sharding for leaf in jax.tree_util.tree_leaves(params)
             if isinstance(leaf, jax.Array) and leaf.committed}
    return jax.device_put(state, homes.pop()) if len(homes) == 1 else state


def pool_programs(model):
    """The two pool programs of ``model``, unjitted: ``decode(params, cache,
    moe, slots, live, sampling)`` and ``prefill(params, cache, moe, slots,
    prompts (P, bucket), lengths (P,), into (P,), temps (P,), keys (P, 2),
    sampling)``, ``P`` prompts in one forward, row ``i`` into slot
    ``into[i]``; a row of length 0 is an absent prompt and touches no slot.
    Each returns its sampled tokens, then the cache, the counters and the
    slot state it was given, updated."""
    import jax
    import jax.numpy as jnp

    def decode(params, cache, moe, slots, live, sampling):
        tokens, lengths, temps = masked_rows(slots, live)
        with jax.named_scope("decode"):
            logits, cache, moe = model.decode_step(params, tokens, lengths,
                                                   cache, moe)
        with jax.named_scope("sample"):
            nxt = sample_tokens(logits, temps, slots["keys"], slots["steps"],
                                sampling)
            return nxt, cache, moe, advance_rows(slots, live, nxt)

    def prefill(params, cache, moe, slots, prompts, lengths, into, temps,
                keys, sampling):
        with jax.named_scope("prefill"):
            logits, cache, moe = model.prefill_into_slot(
                params, prompts, lengths, into, cache, moe)
        with jax.named_scope("sample"):
            toks = sample_tokens(logits, temps, keys,
                                 jnp.zeros(lengths.shape, jnp.int32),
                                 sampling)
            rows = jnp.where(lengths > 0, into, slots["tokens"].shape[0])
            return toks, cache, moe, set_row(slots, rows, toks, lengths,
                                             temps, keys)

    return decode, prefill


class _Flight:
    """One pool program launched and not yet collected: its sampled
    token(s) still on the device, the slots it carried and whose request
    each row was when it was launched."""

    __slots__ = ("kind", "out", "slots", "reqs", "t_launch", "ids")

    def __init__(self, kind, out, slots, reqs, t_launch, ids):
        self.kind, self.out, self.slots, self.reqs = kind, out, slots, reqs
        self.t_launch, self.ids = t_launch, ids


class SlotEngine:
    """Fixed pool of ``num_slots`` KV-cache slots with per-slot lengths.

    Drive it from ONE thread (the scheduler loop): ``admit(request)``
    prefills a free slot between decode iterations, ``step()`` decodes
    every active slot one token.  EOS and per-request ``max_new_tokens``
    free slots immediately — the freed slot is admissible on the very next
    iteration.  Both are a launch half followed by :meth:`collect`; a
    caller that launches ahead (``launch_step`` / ``launch_admit``, then
    :meth:`settle`) gets every request the same tokens in the same order.
    """

    def __init__(self, model, params, num_slots: int = 8,
                 max_len: Optional[int] = None, cache_dtype=None,
                 min_bucket: int = 16):
        import jax
        import jax.numpy as jnp

        from ..utils.compile_cache import ensure_compile_cache
        ensure_compile_cache()  # before the pool programs compile
        self.model = model
        # placed once, in the format the pool programs read (host facts for
        # stats(), fixed here)
        with span("setup.place_params"):
            self.params, self._placed = place_params(model, params)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len if max_len is not None
                           else model.max_seq_len)
        if self.max_len > model.max_seq_len:
            raise ValueError(f"max_len {self.max_len} exceeds the model's "
                             f"max_seq_len {model.max_seq_len}")
        self.cache_dtype = cache_dtype or jnp.float32
        self.buckets = _bucket_lengths(self.max_len, min_bucket)
        self._jnp = jnp
        with span("setup.init_cache"):
            self.cache = beside(self.params, model.init_slot_cache(
                self.num_slots, self.max_len, self.cache_dtype))

        # host-side slot table — THE source of truth for occupancy, and the
        # host's mirror of the device's slot state: lengths / steps / temps /
        # keys as of the last LAUNCH, tokens as of the last COLLECTION
        self.lengths = np.zeros(self.num_slots, np.int32)
        self.tokens = np.zeros(self.num_slots, np.int32)
        self.temps = np.zeros(self.num_slots, np.float32)
        self.keys = np.zeros((self.num_slots, 2), np.uint32)
        self.steps = np.ones(self.num_slots, np.int32)
        self.active = np.zeros(self.num_slots, bool)
        self.slot_req: List[Optional[Request]] = [None] * self.num_slots
        # tokens of each slot's request no program has been launched for
        # yet: a request that ends by max_new_tokens leaves the steps
        # launched ahead of its last token's collection
        self._left = np.zeros(self.num_slots, np.int32)
        # (copies: the CPU backend may keep the host buffer it is handed)
        self._slots = beside(self.params, jax.device_put(
            {"tokens": self.tokens.copy(), "lengths": self.lengths.copy(),
             "steps": self.steps.copy(), "temps": self.temps.copy(),
             "keys": self.keys.copy()}))
        # programs launched and not yet collected, oldest first; the live
        # mask of the last decode launch, uploaded again only when it changes
        self._flight: collections.deque = collections.deque()
        self._flushers: list = []     # add_flusher
        self._live = (None, None)
        self._t_collected = 0.0
        # decode steps ever collected (reset_stats leaves it): the
        # scheduler's progress feed
        self.steps_done = 0
        self._pipeline = self._fresh_pipeline()

        # latency split (shared streaming histograms, utils.metrics)
        self.hist_queue = LatencyHistogram()
        self.hist_prefill = LatencyHistogram()
        self.hist_ttft = LatencyHistogram()
        self.hist_token = LatencyHistogram()
        self.hist_e2e = LatencyHistogram()
        self.completed = 0
        self.generated_tokens = 0
        self._occupied_slot_steps = 0
        self._decode_steps = 0
        self._iterations = 0    # decode iterations ever run: spans' step=
        # the loop thread's clock: ticked at every iteration boundary
        # (sweep_expired), it closes the iteration that just ended as the
        # kind _launched() noted
        self._loop = LoopClock("serve loop", LOOP_KINDS, LOOP_WAITS,
                               sleep="sched.wait")
        self._loop_kind = "idle"
        # routed-row counters per pool program (_build_programs fills them
        # for a model with expert layers) and their reading at the last
        # reset_stats()
        self._moe: dict = {"prefill": {}, "decode": {}}
        self._moe_base: dict = {}
        # decode attention: K/V time blocks the busy slots held, summed
        # over decode iterations (host arithmetic on self.lengths), and
        # whether a decode step over this pool takes the Pallas kernel,
        # asked where MultiheadSelfAttention._decode asks
        self._kv_blocks_read = 0
        self._attn_kernel = model.slot_decode_kernel(self.cache)
        # the two kinds of cache a decode step touches for its busy slots
        # (nn/cache.py): a slot's whole state, read and written once a step
        # whatever its length, and the K/V columns it holds; bytes summed
        # over decode iterations, host arithmetic like the blocks above
        self._slot_bytes = kvcache.slot_bytes(self.cache)
        self._state_bytes = self._kv_bytes = 0
        # decode steps launched, and those whose program computes every
        # recurrent layer's one-token update with the Pallas kernel (the
        # model's answer, asked when the decode program is first launched,
        # under whatever attention_impl it is traced under)
        self._state_kernel = None
        self._state_steps = 0
        # what a decode step needs of the parameters (host facts), and the
        # busy rows and resident positions summed over decode iterations
        self._need = decode_need_facts(model, self.params)
        self._need_rows = self._need_positions = 0
        # what the residual mixes must move a row (the model's answer: 0
        # for x + f(x)), and the rows each pool program carried
        self._residual = residual_facts(model, self.params)
        self._program_rows = self._fresh_program_rows()
        # the layers whose whole mixer is a short convolution (host facts)
        self._short_conv = short_conv_facts(model)
        # what each bucket's prefill program builds its attention on (the
        # model's answer, asked when the bucket's program is first
        # launched, under whatever attention_impl it is traced under), and
        # the prefills counted by it
        self._prefill_attn_facts: dict = {}
        self._prefill_attn = self._fresh_prefill_attn()
        # and whether a bucket's program computes every recurrent layer's
        # scan with the Pallas kernel (asked the same way), with the
        # prefills counted by it
        self._prefill_scan_kernel: dict = {}
        self._prefill_scan = self._fresh_prefill_scan()
        # the compile ledger's totals and the instant of the last
        # reset_stats() (stats()["compiles"])
        self._compiles_base = (compile_totals(), 0.0)

        with span("setup.build_programs"):
            self._build_programs()

    def _build_programs(self) -> None:
        """Compile the two pool programs (``self._decode`` /
        ``self._prefill``).  The tensor-parallel engine
        (:class:`tpu_dist.serve.sharded.ShardedSlotEngine`) overrides this
        ONE hook to substitute its per-shard segment pipeline — every
        other line of slot bookkeeping is shared, so the two engines
        cannot drift on admission/finish semantics."""
        import jax

        model = self.model
        # routed-row counters of a model with expert layers ({} without):
        # one set per pool program, on the device, beside the pool.  Each
        # program takes its set next to the cache and returns it with the
        # call's rows added; nothing is read back until stats().  NOT
        # donated: stats() and reset_stats() may read a set from another
        # thread while the loop thread steps.
        fresh = getattr(model, "init_moe_counters", dict)
        self._moe = beside(self.params,
                           {"prefill": fresh(), "decode": fresh()})

        # the cache is donated (the pool buffer is updated in place instead
        # of copied every token); ``sampling`` is STATIC — jit caches by
        # shape, so whether any slot samples must key the program cache,
        # not be read from host state at trace time
        decode, prefill = pool_programs(model)
        self._decode = jax.jit(decode, donate_argnums=(1,),
                               static_argnums=(5,))
        self._prefill = jax.jit(prefill, donate_argnums=(1,),
                                static_argnums=(9,))

    # -- introspection -------------------------------------------------------

    @property
    def fatal_error(self):
        """Non-None when the engine is unusable as a whole (not just one
        request) — the scheduler checks it after an admit failure and
        shuts down with this cause instead of serving a dead pool.  The
        sharded engine reports its poisoned-lockstep state here."""
        return None

    def free_slots(self) -> int:
        return int(self.num_slots - self.active.sum())

    def active_count(self) -> int:
        return int(self.active.sum())

    def idle(self) -> bool:
        """No slot occupied and no program in flight."""
        return not self.active.any() and not self._flight

    def add_flusher(self, flush: Callable[[], None]) -> Callable[[], None]:
        """Call ``flush`` each time a program's tokens have all been emitted
        (inside its ``*.emit`` phase): how a frontend connection sends a
        decode step's token frames as one write.  Returns the call that
        takes it out again."""
        self._flushers.append(flush)

        def remove() -> None:
            if flush in self._flushers:
                self._flushers.remove(flush)
        return remove

    def _flush(self) -> None:
        for flush in tuple(self._flushers):
            flush()

    def occupancy(self) -> float:
        """Mean fraction of slots busy per decode step."""
        if self._decode_steps == 0:
            return 0.0
        return (self._occupied_slot_steps
                / (self._decode_steps * self.num_slots))

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        raise ValueError(f"prompt length {prompt_len} exceeds the pool's "
                         f"max_len {self.max_len}")

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        if prompt_len < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot capacity "
                f"({self.max_len})")

    def prefill_width(self, bucket: int) -> int:
        """Prompts ONE prefill program of ``bucket`` positions takes
        (:func:`prefill_width` over this pool's ``max_len``): the scheduler
        asks here how many held requests of a bucket may share a program
        (:meth:`launch_group`).  An engine whose admission is not a program
        of its own (a plan on the wire for each, rows that arrive
        prefilled) answers 1."""
        return prefill_width(bucket, self.max_len)

    def stage(self, req: Request):
        """Bucket-pad a request's prompt — the work the scheduler's
        background staging thread runs off the decode loop.  The padded row
        stays on the host: a prefill program takes its group's rows as one
        array (:meth:`_admit`)."""
        bucket = self.bucket_for(len(req.prompt))
        with span("stage.put", req=req.id, bucket=bucket):
            padded = np.zeros(bucket, np.int32)
            padded[:len(req.prompt)] = req.prompt
            req.staged = padded
        return req.staged

    # -- the two pool operations --------------------------------------------

    def admit(self, req: Request) -> int:
        """Prefill ``req`` into a free slot and emit its first token.
        Returns the slot index; raises ``RuntimeError`` when no slot is
        free (callers check :meth:`free_slots` first).  Cancelled or
        past-deadline requests are refused by name BEFORE the prefill —
        shedding stale load instead of spending a compiled program on it."""
        slot = self.launch_admit(req)
        self.collect_all()
        return slot

    def step(self) -> int:
        """One decode iteration over the pool; returns tokens emitted."""
        self.launch_step()
        return self.collect_all()

    # -- launch: enqueue a program, return without its result ---------------

    def launch_admit(self, req: Request) -> int:
        """The launch half of :meth:`admit`: the refusals, the slot choice
        and the prefill's launch — :meth:`launch_group` of one.  The slot
        is occupied from here on; its first token is emitted by the
        :meth:`collect` of this program."""
        return self.launch_group([req])[0]

    def launch_group(self, reqs: List[Request],
                     refuse: Optional[Callable] = None) -> List[int]:
        """Launch ONE prefill program for ``reqs``: requests of one bucket,
        at most :meth:`prefill_width` of it and no more than the free
        slots, in order.  A member that is cancelled, past its deadline or
        does not fit is refused by name BEFORE the program:
        ``refuse(req, error)`` is told and the others go on (with no
        ``refuse`` the error is raised and nothing is launched).  Returns
        the slots of the members launched, in order; an error of the
        program itself is raised with none of them occupied."""
        group = []
        for req in reqs:
            try:
                group.append((req, self._admission_slot(req, len(group))))
            except Exception as e:
                if refuse is None:
                    raise
                refuse(req, e)
        if not group:
            return []
        if self._loop_kind == "prefill":
            # the scheduler admits up to a pool's worth between two sweeps
            # (a pool's first filling is ONE pass of seconds): an iteration
            # of the loop clock holds one prefill
            self._tick()
        for req, slot in group:
            self._pre_admit(req, slot)
        self._admit(group)
        return [slot for _, slot in group]

    def _admission_slot(self, req: Request, taken: int = 0) -> int:
        """All admission pre-checks + the deterministic slot choice (the
        lowest free index past the ``taken`` lowest, which earlier members
        of the group have).  Split from :meth:`_admit` so the sharded
        engine can broadcast its admission plan AFTER every refusal path
        has passed — a follower must never prefill a slot the leader then
        refuses."""
        if req.cancelled:
            raise RequestCancelledError(
                f"request {req.id} was cancelled before admission")
        if req.expired():
            raise DeadlineExceededError(
                f"request {req.id} missed its deadline before admission "
                f"(deadline_ms elapsed in the queue) — shed")
        free = np.flatnonzero(~self.active)
        if len(free) <= taken:
            raise RuntimeError("no free slot (check free_slots() first)")
        self.validate(len(req.prompt), req.max_new_tokens)
        return int(free[taken])

    def _pre_admit(self, req: Request, slot: int) -> None:
        """Hook between the (passed) admission checks and the prefill —
        the sharded engine's plan broadcast point."""

    def _admit(self, group: List[tuple]) -> None:
        """The unconditional admission half: ONE prefill program for the
        ``(request, slot)`` pairs of ``group`` and the slots' occupation
        (every refusal already ruled out by :meth:`_admission_slot`).  The
        program is as wide as the bucket's :meth:`prefill_width`; the rows
        past the group are absent prompts (length 0).  Uploads the group's
        own rows and scalars and nothing of the other slots."""
        reqs = [req for req, _ in group]
        bucket = self.bucket_for(len(reqs[0].prompt))
        width = self.prefill_width(bucket)
        if len(group) > width or any(
                self.bucket_for(len(r.prompt)) != bucket for r in reqs):
            raise ValueError(
                f"a prefill program of bucket {bucket} takes {width} "
                f"prompt(s) of that bucket, not these {len(group)}")
        # req / slot: the group's first, what a program of one always
        # carried; reqs / slots: every member's, in the rows' order (no
        # comma: the profiler's annotation separates its fields by them)
        ids = {"req": reqs[0].id, "slot": group[0][1],
               "prompts": len(group),
               "reqs": "/".join(str(r.id) for r in reqs),
               "slots": "/".join(str(slot) for _, slot in group)}
        now = _now()
        with span("prefill.prepare", bucket=bucket, **ids):
            prompts = np.zeros((width, bucket), np.int32)
            lengths, into = (np.zeros(width, np.int32) for _ in range(2))
            temps = np.zeros(width, np.float32)
            keys = np.zeros((width, 2), np.uint32)
            for i, (req, slot) in enumerate(group):
                req.t_admit = now
                self.hist_queue.observe(now - req.t_submit)
                prompts[i] = (req.staged if req.staged is not None
                              else self.stage(req))
                lengths[i], into[i] = len(req.prompt), slot
                temps[i], keys[i] = req.temperature, seed_key(req.seed)
        with span("prefill.dispatch", bucket=bucket, **ids):
            self._count_prefill_attn(bucket, lengths)
            self._count_prefill_scan(bucket, len(group))
            toks_dev, self.cache, self._moe["prefill"], self._slots = \
                self._prefill(
                    self.params, self.cache, self._moe["prefill"],
                    self._slots, prompts, lengths, into, temps, keys,
                    bool(np.any(temps > 0)))
            for i, (req, slot) in enumerate(group):
                self._occupy(req, slot, keys[i])
            self._program_rows["prefill"] += int(lengths.sum())
            self._pipeline["prefill_prompts"] += len(group)
            self._pipeline["prefill_absent_rows"] += width - len(group)
        self._launched(_Flight("prefill", toks_dev,
                               [slot for _, slot in group], reqs, now, ids))

    def _occupy(self, req: Request, slot: int, key) -> None:
        """The host's rows of a slot whose admission was launched; its
        first token follows at collection."""
        self.lengths[slot] = len(req.prompt)
        self.temps[slot] = req.temperature
        self.keys[slot] = key
        self.steps[slot] = 1
        self.active[slot] = True
        self.slot_req[slot] = req
        self._left[slot] = req.max_new_tokens - 1

    def launch_step(self) -> bool:
        """The launch half of :meth:`step`: one decode iteration over the
        rows whose requests still have a token to come that no program in
        flight computes already.  False when there is none (nothing was
        launched).  Uploads the live mask when it changed, nothing else."""
        import jax

        live = self.active & (self._left > 0)
        if not live.any():
            return False
        self._pre_step()
        self._iterations += 1
        rows = np.flatnonzero(live)
        ids = {"step": self._iterations, "active": len(rows)}
        t0 = _now()
        with span("decode.dispatch", **ids):
            self._kv_blocks_read += kv_blocks(
                np.where(live, self.lengths, 0), self.max_len)[0]
            state, per_pos = self._slot_bytes
            self._state_bytes += 2 * state * len(rows)
            if self._state_kernel is None:
                self._state_kernel = self.model.slot_state_kernel(self.cache)
            self._state_steps += 1
            positions = int(self.lengths[rows].sum()) + len(rows)
            self._kv_bytes += per_pos * positions
            self._need_rows += len(rows)
            self._need_positions += positions
            self._program_rows["decode"] += len(rows)
            if not np.array_equal(live, self._live[0]):
                self._live = (live, jax.device_put(live))
            nxt_dev, self.cache, self._moe["decode"], self._slots = \
                self._decode(self.params, self.cache, self._moe["decode"],
                             self._slots, self._live[1],
                             bool(np.any(self.temps[rows] > 0)))
            self.lengths[rows] += 1
            self.steps[rows] += 1
            self._left[rows] -= 1
        self._launched(_Flight("decode", nxt_dev, rows,
                               [self.slot_req[s] for s in rows], t0, ids))
        return True

    def _pre_step(self) -> None:
        """Hook before a decode step's launch, once it is certain — the
        sharded engine's plan broadcast point."""

    def _launched(self, flight: _Flight) -> None:
        count = self._pipeline
        count["launches"][flight.kind] += 1
        if self._flight:
            count["launched_ahead"][flight.kind] += 1
        self._flight.append(flight)
        if self._loop_kind != "prefill":    # a prefill names the iteration
            self._loop_kind = flight.kind

    # -- collect: the one wait on the device, then the host's share ----------

    def settle(self) -> int:
        """Collect every program in flight but the newest: what the loop
        thread calls after a launch, so one program is always enqueued
        behind the one it waits for.  An engine whose launch must stay
        glued to its collection (a plan already on the wire) makes this
        :meth:`collect_all`.  Returns tokens emitted."""
        return self._collect_to(1)

    def collect_all(self) -> int:
        """Collect every program in flight; returns tokens emitted."""
        return self._collect_to(0)

    def _collect_to(self, keep: int) -> int:
        emitted = 0
        while len(self._flight) > keep:
            emitted += self.collect()
        return emitted

    def _readback(self, out) -> np.ndarray:
        """THE place the loop thread waits for the device."""
        return np.asarray(out)

    def collect(self) -> int:
        """Wait for the oldest program in flight, emit its tokens and do
        its bookkeeping; returns tokens emitted (0 with nothing in flight).
        A row whose request ended since the launch (EOS, cancel, deadline)
        is dropped: its token is never emitted."""
        if not self._flight:
            return 0
        flight = self._flight.popleft()
        prefill = flight.kind == "prefill"
        with span(flight.kind + ".readback", **flight.ids):
            out = self._readback(flight.out)
        # charged collection to collection (from its own launch where that
        # is later: an idle pool), so the two histograms split the loop's
        # busy time and count nothing twice
        now = _now()
        dt = now - max(self._t_collected, flight.t_launch)
        self._t_collected = now
        if prefill:
            self.hist_prefill.observe(dt)
        else:
            self._decode_steps += 1
            self.steps_done += 1
            self.hist_token.observe(dt)
        tokens = out.reshape(-1) if prefill else out[flight.slots]
        emitted = wasted = 0
        with span(flight.kind + ".emit", **flight.ids):
            for slot, req, tok in zip(flight.slots, flight.reqs, tokens):
                slot, tok = int(slot), int(tok)
                if self.slot_req[slot] is not req:   # ended since the launch
                    wasted += 1
                    continue
                self.tokens[slot] = tok
                if prefill:
                    self._obs_admit(req, slot, now)
                req.emit(tok)
                if prefill:
                    self.hist_ttft.observe(_now() - req.t_submit)
                self.generated_tokens += 1
                emitted += 1
                self._maybe_finish(slot, tok)
            self._flush()
        if not prefill:
            self._occupied_slot_steps += emitted
            self._pipeline["wasted_rows"] += wasted
        return emitted

    # -- completion / failure ------------------------------------------------

    def _maybe_finish(self, slot: int, token: int) -> None:
        req = self.slot_req[slot]
        if req.eos_id is not None and token == req.eos_id:
            self._finish(slot, "eos")
        elif req.emitted >= req.max_new_tokens:
            self._finish(slot, "length")

    def _finish(self, slot: int, reason: str) -> None:
        req = self.slot_req[slot]
        self._free(slot)
        self.completed += 1
        self.hist_e2e.observe(_now() - req.t_submit)
        self._obs_end(req, "ok", reason=reason)
        req.finish(reason)

    def fail_slot(self, slot: int, exc: BaseException) -> None:
        """Free a slot whose request failed; the request is notified with
        the named error (scheduler error paths)."""
        req = self.slot_req[slot]
        self._free(slot)
        if req is not None:
            self._obs_end(req, error_outcome(exc))
            req.fail(exc)

    def fail_all(self, exc: BaseException) -> None:
        """Shutdown: every occupied slot fails with ``exc``, and what is
        still in flight is dropped uncollected."""
        for slot in np.flatnonzero(self.active):
            self.fail_slot(int(slot), exc)
        self._flight.clear()

    def sweep_expired(self) -> int:
        """Free slots whose requests were cancelled (client disconnect /
        explicit cancel) or ran past their ``deadline_ms`` — called by the
        scheduler loop at EVERY iteration boundary, so a cancelled request
        stops occupying a slot after at most one decode step instead of
        decoding to ``max_new_tokens`` for nobody (a step launched ahead of
        the boundary still carries its row: two steps at most, the second's
        token dropped at collection).  The request terminates
        with the named error and its obs span closes ``error:Cancelled`` /
        ``error:DeadlineExceededError``.  Returns the slots freed.

        The boundary is also where the loop clock ticks, before ``sweep``
        opens: the calling thread's iteration that just ended is closed
        into ``stats()["loop"]`` (:meth:`launch_admit` ticks too, at an
        admission that follows another inside one pass)."""
        self._tick()
        with span("sweep", step=self._iterations + 1):
            expired = self._sweep_candidates()
            if expired:
                self._pre_free([slot for slot, _ in expired])
            for slot, exc in expired:
                self.fail_slot(slot, exc)
        return len(expired)

    def _tick(self) -> None:
        """Close the loop clock's iteration as what it launched."""
        self._loop.tick(self._loop_kind, self._iterations)
        self._loop_kind = "idle"

    def _sweep_candidates(self) -> List[tuple]:
        """``(slot, named_error)`` for every active slot whose request was
        cancelled or ran past its deadline — the decision half of
        :meth:`sweep_expired`, taken on the LEADER's clock only (the
        sharded engine broadcasts the resulting slot list so followers
        free the same slots without consulting their own clocks)."""
        out = []
        now = _now()
        for slot in np.flatnonzero(self.active):
            slot = int(slot)
            req = self.slot_req[slot]
            if req is None:
                continue
            if req.cancelled:
                out.append((slot, RequestCancelledError(
                    f"request {req.id} cancelled after {req.emitted} "
                    f"token(s); slot {slot} freed at the iteration "
                    f"boundary")))
            elif req.expired(now):
                out.append((slot, DeadlineExceededError(
                    f"request {req.id} exceeded its deadline_ms after "
                    f"{req.emitted} token(s); slot {slot} freed at the "
                    f"iteration boundary")))
        return out

    def _pre_free(self, slots: List[int]) -> None:
        """Hook before a sweep frees ``slots`` — the sharded engine's
        free-plan broadcast point."""

    def _free(self, slot: int) -> None:
        self.active[slot] = False
        self.lengths[slot] = 0
        self.tokens[slot] = 0
        self.temps[slot] = 0.0
        self.slot_req[slot] = None
        self._left[slot] = 0

    # -- per-request obs spans ----------------------------------------------

    @staticmethod
    def obs_open(req: Request) -> None:
        """Open the request's flight-recorder span (armed runs only) —
        called at SUBMIT time so queue time is on the span from the start;
        a request stuck in the queue is still a named pending span."""
        from ..obs.recorder import call_site, get_recorder
        rec = get_recorder()
        if rec is None:
            return
        req.obs_span = rec.begin("serve", "request", req=req.id,
                                 prompt_len=int(len(req.prompt)),
                                 max_new_tokens=req.max_new_tokens,
                                 site=call_site())

    def _obs_admit(self, req: Request, slot: int, t_prefill_done) -> None:
        if req.obs_span is None:
            return
        from ..obs.recorder import get_recorder
        rec = get_recorder()
        if rec is None:
            return
        rec.update_event(
            req.obs_span, slot=slot,
            queue_ns=int((req.t_admit - req.t_submit) * 1e9),
            prefill_ns=int((t_prefill_done - req.t_admit) * 1e9))

    def _obs_end(self, req: Request, outcome: str, **fields) -> None:
        if req.obs_span is None:
            return
        from ..obs.recorder import get_recorder
        rec = get_recorder()
        if rec is None:
            return
        decode_ns = 0
        if req.t_first is not None:
            decode_ns = int((_now() - req.t_first) * 1e9)
        rec.end(req.obs_span, outcome=outcome, tokens=req.emitted,
                decode_ns=decode_ns, **fields)

    # -- aggregate stats -----------------------------------------------------

    def reset_stats(self) -> None:
        """Zero the histograms/counters (benchmarks: exclude warmup
        compiles from the measured window), the serving loop's phases
        (:data:`SERVE_PHASES`, process-wide) and the loop clock among them
        (an iteration open across the call is dropped, not split).  Slot
        state is untouched."""
        self.hist_queue = LatencyHistogram()
        self.hist_prefill = LatencyHistogram()
        self.hist_ttft = LatencyHistogram()
        self.hist_token = LatencyHistogram()
        self.hist_e2e = LatencyHistogram()
        self.completed = 0
        self.generated_tokens = 0
        self._occupied_slot_steps = 0
        self._decode_steps = 0
        self._kv_blocks_read = 0
        self._state_bytes = self._kv_bytes = 0
        self._state_steps = 0
        self._need_rows = self._need_positions = 0
        self._program_rows = self._fresh_program_rows()
        self._prefill_attn = self._fresh_prefill_attn()
        self._prefill_scan = self._fresh_prefill_scan()
        self._pipeline = self._fresh_pipeline()
        reset_phases(SERVE_PHASES)
        self._loop.reset()
        self._compiles_base = (compile_totals(), time.monotonic())
        # the device counters are never zeroed (a step in flight would
        # carry the old count on): stats() reports them past this reading
        self._moe_base = self._moe_read()

    @staticmethod
    def _fresh_pipeline() -> dict:
        """``stats()["pipeline"]``: programs launched, those launched while
        an earlier one's result was still uncollected, and the rows decode
        steps carried for requests that had already ended;
        ``prefill_prompts``, the requests launched in prefill programs
        (over ``launches["prefill"]``: prompts a program),
        ``prefill_absent_rows``, the absent prompts those programs carried
        to their width, and ``deferred_slot_steps``, the idle slot-steps
        free slots spent waiting for company (:meth:`count_deferred`).
        Host arithmetic, no device read."""
        kinds = lambda: {"decode": 0, "prefill": 0}
        return {"launches": kinds(), "launched_ahead": kinds(),
                "wasted_rows": 0, "prefill_prompts": 0,
                "prefill_absent_rows": 0, "deferred_slot_steps": 0}

    def count_deferred(self, slot_steps: int) -> None:
        """The scheduler's report, at a decode launch, of the free slots it
        kept waiting for a fuller prefill group over that step."""
        self._pipeline["deferred_slot_steps"] += int(slot_steps)

    @staticmethod
    def _fresh_program_rows() -> dict:
        return {"prefill": 0, "decode": 0}

    def _residual_stats(self) -> dict:
        """``stats()["residual"]``: the model's ``streams`` and
        ``sublayers`` (:func:`residual_facts`), and by pool program since
        ``reset_stats()`` the ``rows`` it carried for requests (a prefill's
        true prompt tokens, a decode step's busy slots) and ``bytes``, what
        those rows had to move through the residual mixes of all
        sublayers (read the streams and write the sublayer's input; read
        them and its output and write them back).  Host arithmetic inside
        ``prefill.dispatch`` / ``decode.dispatch``; 0 bytes for a model
        whose residual is ``x + f(x)``."""
        facts = self._residual
        return {"streams": facts["streams"], "sublayers": facts["sublayers"],
                **{kind: {"rows": rows, "bytes": rows * facts["row_bytes"]}
                   for kind, rows in self._program_rows.items()}}

    def _conv_stats(self) -> dict:
        """``stats()["conv"]``: the ``layers`` whose whole mixer is a short
        convolution and their ``params`` (:func:`short_conv_facts`), and by
        pool program since ``reset_stats()`` the ``rows`` it carried for
        requests through every one of them (a prefill's true prompt tokens,
        a decode step's busy slots) and the ``calls`` of the program (each
        reads every such layer's matrices once).  Host arithmetic inside
        ``prefill.dispatch`` / ``decode.dispatch``."""
        launches = self._pipeline["launches"]
        return {**self._short_conv,
                **{kind: {"rows": rows, "calls": launches[kind]}
                   for kind, rows in self._program_rows.items()}}

    @staticmethod
    def _fresh_prefill_attn() -> dict:
        return {"prefills": 0, "kernel_prefills": 0, "pairs_needed": 0,
                "pairs_executed": 0}

    def _count_prefill_attn(self, bucket: int, lengths) -> None:
        """``stats()["prefill_attn"]``: one prefill program of ``bucket``
        positions a row, ``lengths`` its rows' true tokens (0: an absent
        prompt).  ``prefills``, the whole prompts prefilled;
        ``kernel_prefills``, those whose program's attention is the causal
        flash forward kernel (``model.prefill_attention_facts``: a model
        without a layer that says reports 0); ``pairs_needed``, ``n (n +
        1) / 2`` (query, key) pairs a head a layer of those; and
        ``pairs_executed``, what the program executed for them (an absent
        prompt's row is ``prefill_absent_rows``' to count, not this one's):
        ``tile_plan``'s sub-tiles on the kernel, ``bucket ** 2`` dense.
        Host arithmetic inside ``prefill.dispatch``."""
        facts = self._prefill_attn_facts.get(bucket)
        if facts is None:
            facts = self._prefill_attn_facts[bucket] = \
                self.model.prefill_attention_facts(
                    bucket, served_dtype(self.model, self.params))
        count = self._prefill_attn
        real = [int(n) for n in lengths if n]
        count["prefills"] += len(real)
        count["kernel_prefills"] += len(real) * int(facts["kernel"])
        count["pairs_needed"] += facts["heads"] * sum(
            n * (n + 1) // 2 for n in real)
        count["pairs_executed"] += len(real) * facts["pairs_executed"]

    @staticmethod
    def _fresh_prefill_scan() -> dict:
        return {"prefills": 0, "kernel_prefills": 0}

    def _count_prefill_scan(self, bucket: int, prompts: int) -> None:
        """``stats()["prefill_scan"]``: ``prompts`` whole prompts in one
        program of ``bucket`` positions.  ``prefills``; ``kernel_prefills``,
        those whose program computes every recurrent layer's scan with
        tpu_dist.ops.delta_scan (``model.prefill_scan_kernel``, asked once
        a bucket: a model without such a layer reports 0).  Host arithmetic
        inside ``prefill.dispatch``."""
        kernel = self._prefill_scan_kernel.get(bucket)
        if kernel is None:
            kernel = self._prefill_scan_kernel[bucket] = \
                self.model.prefill_scan_kernel(self.cache, bucket)
        self._prefill_scan["prefills"] += prompts
        self._prefill_scan["kernel_prefills"] += prompts * int(kernel)

    def _moe_read(self) -> dict:
        """The routed-row counters per pool program, each stacked over the
        expert layers in the order of their paths, as numpy int64 (one
        fetch per program; {} for a model with no expert layer or an engine
        that keeps none)."""
        import jax

        out = {}
        for phase, sets in dict(self._moe).items():
            if sets:
                layers = jax.device_get([sets[path] for path in sorted(sets)])
                out[phase] = {k: np.stack([np.asarray(l[k], np.int64)
                                           for l in layers])
                              for k in layers[0]}
        return out

    def _moe_since(self) -> dict:
        """The counters of :meth:`_moe_read` since ``reset_stats()``."""
        base = self._moe_base
        # int32 on the device: differences are right modulo 2**32
        return {phase: {k: (v - base.get(phase, {}).get(k, 0)) % (1 << 32)
                        for k, v in c.items()}
                for phase, c in self._moe_read().items()}

    def _moe_stats(self, since: dict) -> Optional[dict]:
        """``stats()["moe"]``: routed rows since ``reset_stats()``, per
        router expert (a request's picks only, summed over layers and both
        pool programs) and per program — ``rows`` a request's picks,
        ``held_rows`` those on an expert the model holds and ``absent_rows``
        the others (a model that holds a share of its experts), ``pad_rows``
        the picks of free slots (decode) and bucket padding (prefill),
        ``computed_rows`` the rows the expert matmuls ran over,
        ``combined_rows`` the rows the combine gathered (every pick of a
        call, or the row buffer's where a call is combined by those),
        ``calls`` of an expert layer, ``experts_hit`` summed over calls
        (``MoELayer.init_counters``)."""
        if not since:
            return None
        per_expert = sum(c["rows"].sum(0) for c in since.values())
        by_phase = {phase: {k: int(v.sum()) for k, v in c.items()}
                    for phase, c in since.items()}
        for c in by_phase.values():
            c["absent_rows"] = c["rows"] - c["held_rows"]
        total = lambda k: sum(c[k] for c in by_phase.values())
        return {"rows_per_expert": [int(r) for r in per_expert],
                **{k: total(k) for k in ("rows", "held_rows", "absent_rows",
                                         "pad_rows", "computed_rows",
                                         "combined_rows", "calls")},
                "by_phase": by_phase}

    def _decode_need_stats(self, since: dict) -> dict:
        """``stats()["decode_need"]``: what the decode steps since
        ``reset_stats()`` NEEDED of the chip by the mathematics, summed over
        steps.  ``weight_bytes``: every parameter a step reads once (all
        but the gathered tables and the routed experts) plus one expert's
        weights for each held expert a request's row reached
        (``experts_hit``); ``cache_bytes``: the resident columns of the
        busy slots, the one written included, and twice their whole state;
        ``flops``: 2 x the parameters a row uses (the fixed ones, and an
        expert's for each pick on a held expert) + the attention's
        operations a resident position + the recurrent layers' a row's
        state update.  Rows of free slots, the kernels'
        padding and a dense branch's reading of the whole pool are nobody's
        need.  ``steps`` are those launched, ``rows`` their busy slots and
        ``positions`` the columns those held, the one written included;
        host arithmetic but for the expert counters ``stats()["moe"]``
        reads anyway."""
        need, steps = self._need, self._pipeline["launches"]["decode"]
        routed = since.get("decode", {})
        per_expert = [need["experts"][path]
                      for path in sorted(need["experts"])] if routed else []
        hit = sum(int(n) * nbytes for n, (nbytes, _) in
                  zip(routed.get("experts_hit", ()), per_expert))
        picks = sum(int(n) * size for n, (_, size) in
                    zip(routed.get("held_rows", ()), per_expert))
        return {"steps": steps, "rows": int(self._need_rows),
                "positions": int(self._need_positions),
                "weight_bytes": steps * need["fixed_bytes"] + hit,
                "cache_bytes": int(self._kv_bytes + self._state_bytes),
                "flops": 2 * (need["fixed_params"] * int(self._need_rows)
                              + picks)
                + need["attend_flops"] * int(self._need_positions)
                + need["state_flops"] * int(self._need_rows)}

    def _decode_attn_stats(self) -> dict:
        """``stats()["decode_attn"]``: how far the decode step's K/V traffic
        follows the occupancy.  ``kv_blocks_read``: time blocks of
        ``block`` columns the busy slots held, ``ceil((len + 1) / block)``
        each, summed over the ``steps`` decode iterations since
        ``reset_stats()``; ``kv_blocks_pool``: the blocks of the whole
        pool over the same iterations.  With ``kernel`` true the decode
        program reads the former (tpu_dist.ops.decode_attention); with it
        false, on the dense branch, it reads the latter and the former is
        what the kernel WOULD read."""
        _, per_step, block = kv_blocks(self.lengths, self.max_len)
        return {"kv_blocks_read": int(self._kv_blocks_read),
                "kv_blocks_pool": self._decode_steps * per_step,
                "steps": self._decode_steps, "block": block,
                "kernel": self._attn_kernel}

    def stats(self) -> dict:
        """Everything since ``reset_stats()``.  ``"state"``: bytes of the
        two kinds of cache the decode steps had to touch for their busy
        slots, summed over steps: ``state_bytes`` (whole state, read and
        written) and ``kv_bytes`` (the K/V columns held, the new one
        included); ``steps``, the decode steps launched, and
        ``kernel_steps``, those whose program computes every recurrent
        layer's one-token update with tpu_dist.ops.delta_step (the
        model's ``slot_state_kernel``; 0 for a model without such a
        layer).  ``"decode_need"``: :meth:`_decode_need_stats`.
        ``"residual"``: :meth:`_residual_stats`.  ``"conv"``:
        :meth:`_conv_stats`, for a model with such layers.  ``"prefill_attn"``:
        :meth:`_count_prefill_attn`.  ``"prefill_scan"``:
        :meth:`_count_prefill_scan`.
        ``"params"``: what :func:`place_params` did at construction;
        ``reset_stats()`` leaves it.  ``"loop"``: the loop thread's clock
        (:meth:`tpu_dist.obs.spans.LoopClock.stats`): every iteration the
        thread that calls :meth:`sweep_expired` closed, by phase, CPU,
        garbage collection and time off the CPU, and the longest ones
        whole."""
        since = self._moe_since()
        moe = self._moe_stats(since)
        return {
            **({"moe": moe} if moe else {}),
            "decode_attn": self._decode_attn_stats(),
            "decode_need": self._decode_need_stats(since),
            "residual": self._residual_stats(),
            **({"conv": self._conv_stats()}
               if self._short_conv["layers"] else {}),
            "prefill_attn": dict(self._prefill_attn),
            "prefill_scan": dict(self._prefill_scan),
            "state": {"state_bytes": int(self._state_bytes),
                      "kv_bytes": int(self._kv_bytes),
                      "steps": self._state_steps,
                      "kernel_steps": (self._state_steps
                                       if self._state_kernel else 0)},
            "pipeline": {k: dict(v) if isinstance(v, dict) else v
                         for k, v in self._pipeline.items()},
            "params": dict(self._placed),
            "completed": self.completed,
            "generated_tokens": self.generated_tokens,
            "decode_steps": self._decode_steps,
            "occupancy": round(self.occupancy(), 4),
            "queue": self.hist_queue.summary(),
            "prefill": self.hist_prefill.summary(),
            "ttft": self.hist_ttft.summary(),
            "decode_step": self.hist_token.summary(),
            "e2e": self.hist_e2e.summary(),
            "phases": phase_times(SERVE_PHASES),
            "loop": self._loop.stats(),
            "compiles": self._compile_stats(),
        }

    def _compile_stats(self) -> dict:
        """``stats()["compiles"]``: the compile ledger
        (:mod:`tpu_dist.obs.compiles`) of the whole PROCESS, which
        ``reset_stats()`` leaves — programs by what the persistent cache
        said and seconds by stage — with the :data:`~tpu_dist.obs.spans.KEPT`
        longest records, and under ``since_reset`` the same over what was
        built since the last ``reset_stats()``: 0 programs in a warmed
        server; 1 is a shape no bucket was warmed for."""
        base, at = self._compiles_base
        return {**compile_totals(),
                "longest": longest_compiles(KEPT),
                "since_reset": {**compile_totals_since(base),
                                "longest": longest_compiles(KEPT, since=at)}}
