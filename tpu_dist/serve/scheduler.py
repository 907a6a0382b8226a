"""Admission scheduler — the thread layer between frontends and the engine.

Requests land in a bounded admission queue; a background *staging* thread
bucket-pads and device-stages each prompt (the ``DeviceLoader`` discipline:
input prep overlaps the decode loop instead of stalling it); the *loop*
thread drives the :class:`~tpu_dist.serve.engine.SlotEngine` — admit
staged requests into free slots between decode iterations, then run one
``decode_step`` over the pool.  It launches each program BEFORE it collects
the previous one, so the chip always has its next program enqueued while
the host reads back, sends token frames and keeps its books
(``_run_loop``).

Admission coalescing: when the engine is IDLE and a request arrives, the
loop holds admission for up to ``batch_window`` seconds so closely-spaced
arrivals prefill as one admission group instead of paying a lone-slot
decode step each (the bucketer's coalescing discipline, applied to
requests).  While slots are decoding there is nothing to wait for — new
arrivals are admitted at the next iteration boundary for free.

Every blocking wait in this module is deadline-bounded (tpudlint TD004):
a dead engine thread or a stuck queue turns into a named timeout, never a
silent hang.  Every request that cannot complete fails with a named
:class:`~tpu_dist.serve.engine.ServeError` subclass — on ``close()`` the
queued and in-flight requests are failed with
:class:`~tpu_dist.serve.engine.SchedulerClosedError`, not dropped.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Optional

from ..obs.spans import span
from .engine import (DeadlineExceededError, QueueFullError, Request,
                     RequestCancelledError, RequestHandle,
                     SchedulerClosedError, SchedulerDrainingError,
                     SlotEngine, error_outcome)

__all__ = ["Scheduler"]


def _now() -> float:
    return time.perf_counter()


class Scheduler:
    """Owns the admission queue, the staging thread, and the decode loop.

    ``submit()`` is thread-safe (frontends call it from per-connection
    reader threads) and returns a :class:`RequestHandle` that ALWAYS
    terminates — tokens then ``done``, or a named error.  ``drain()``
    implements the preemption protocol: stop admitting, finish in-flight
    decodes, report when empty (``--exit-on-preempt`` in
    examples/serve_lm.py exits 117 after it).
    """

    def __init__(self, engine: SlotEngine, batch_window: float = 0.004,
                 max_pending: int = 4096, stage_depth: int = 16,
                 step_hook: Optional[Callable[[int], None]] = None):
        self.engine = engine
        self.batch_window = float(batch_window)
        self.step_hook = step_hook
        self._pending: "queue.Queue[Request]" = queue.Queue(max_pending)
        self._staged: "queue.Queue[Request]" = queue.Queue(stage_depth)
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._idle_cv = threading.Condition()
        self._steps = 0
        self._fatal: Optional[BaseException] = None
        self._stage_thread = threading.Thread(
            target=self._stage_loop, daemon=True, name="tpu_dist-serve-stage")
        self._loop_thread = threading.Thread(
            target=self._run_loop, daemon=True, name="tpu_dist-serve-loop")
        self._stage_thread.start()
        self._loop_thread.start()

    # -- submission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16,
               temperature: float = 0.0, eos_id: Optional[int] = None,
               seed: int = 0, req_id: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               on_token: Optional[Callable] = None,
               on_done: Optional[Callable] = None,
               on_error: Optional[Callable] = None,
               timeout: float = 5.0) -> RequestHandle:
        """Queue one request; returns its handle (stream + terminal state).

        ``deadline_ms`` is an end-to-end budget from submit: a request
        still queued past it is shed by name before staging, one still
        decoding frees its slot at the next iteration boundary — both
        terminate the handle with :class:`DeadlineExceededError`.  The
        handle's :meth:`~tpu_dist.serve.engine.RequestHandle.cancel`
        releases the slot the same way (``RequestCancelledError``).

        Raises :class:`SchedulerDrainingError` while draining,
        :class:`SchedulerClosedError` after close, :class:`QueueFullError`
        when the admission queue stays full for ``timeout`` seconds (the
        bounded queue is the backpressure), and ``ValueError`` for
        requests that can never fit the slot capacity."""
        if self._stop.is_set():
            raise self._closed_error()
        if self._draining.is_set():
            raise SchedulerDrainingError(
                "scheduler is draining (preemption): in-flight requests "
                "finish, new ones are not admitted")
        self.engine.validate(len(prompt), max_new_tokens)
        handle = RequestHandle(req_id if req_id is not None else 0)

        def _tok(req, token):
            handle._on_token(token)
            if on_token is not None:
                on_token(req, token)

        def _done(req, reason):
            handle._on_done(reason)
            if on_done is not None:
                on_done(req, reason)

        def _err(req, exc):
            handle._on_error(exc)
            if on_error is not None:
                on_error(req, exc)

        req = Request(prompt, max_new_tokens, temperature=temperature,
                      eos_id=eos_id, seed=seed, req_id=req_id,
                      deadline_ms=deadline_ms,
                      on_token=_tok, on_done=_done, on_error=_err)
        handle.id = req.id
        handle._cancel = req.cancel  # frees the slot at the next boundary
        SlotEngine.obs_open(req)
        try:
            self._pending.put(req, timeout=timeout)
        except queue.Full:
            exc = QueueFullError(
                f"admission queue full ({self._pending.maxsize} pending); "
                f"shed load or retry")
            self.engine._obs_end(req, error_outcome(exc))
            raise exc
        if self._stop.is_set():
            # close() may have drained the queues while this put was
            # blocked in the backpressure wait — the request would land in
            # a queue nobody reads.  Fail it by name (idempotent if the
            # close-side drain already did) and refuse the submit.
            exc = self._closed_error()
            self._fail(req, exc)
            raise exc
        return handle

    def _closed_error(self) -> SchedulerClosedError:
        if self._fatal is not None:
            return SchedulerClosedError(
                f"scheduler is closed: the decode loop died with "
                f"{type(self._fatal).__name__}: {self._fatal}")
        return SchedulerClosedError("scheduler is closed")

    # -- preemption drain ----------------------------------------------------

    def drain(self, timeout: float = 60.0) -> bool:
        """Stop admitting; True once the queue is empty and every in-flight
        decode finished (False if ``timeout`` expired first).  Queued
        requests that were never admitted are failed with
        :class:`SchedulerDrainingError` — named, not dropped."""
        self._draining.set()
        deadline = _now() + timeout
        while _now() < deadline:
            if self._quiesced():
                return True
            with self._idle_cv:
                self._idle_cv.wait(0.05)
        return self._quiesced()

    def _quiesced(self) -> bool:
        """No request anywhere in the pipeline.  ``unfinished_tasks``
        (decremented by ``task_done`` only after a pop is fully handled)
        rather than ``empty()``: a request in the staging thread's HANDS —
        popped from pending, not yet placed — is in neither queue, and
        ``empty()`` would let ``drain()`` report quiesced while it is
        about to surface."""
        return (self._pending.unfinished_tasks == 0
                and self._staged.unfinished_tasks == 0
                and self.engine.idle())

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    @property
    def fatal(self) -> Optional[BaseException]:
        """The error that killed the decode loop, or None while healthy.
        A serving worker polls this: a fatal engine death (a shard peer
        SIGKILLed mid-collective surfaces here as the leader's
        ``PeerGoneError``) must turn into a nonzero exit so the supervisor
        gang-restarts the shard group instead of leaving a zombie frontend
        refusing every submit."""
        return self._fatal

    def snapshot(self) -> dict:
        """Queue-side load counters for the wire ``stats`` frame (engine
        aggregates ride :meth:`SlotEngine.stats`)."""
        return {"pending": self._pending.qsize(),
                "staged": self._staged.qsize(),
                "draining": self._draining.is_set(),
                "steps": self._steps}

    @property
    def steps(self) -> int:
        """Decode iterations run so far (heartbeat progress feed)."""
        return self._steps

    # -- lifecycle -----------------------------------------------------------

    def close(self, timeout: float = 10.0) -> None:
        """Stop both threads; every request still queued or decoding fails
        with :class:`SchedulerClosedError`."""
        if self._stop.is_set():
            return
        self._stop.set()
        self._loop_thread.join(timeout)
        self._stage_thread.join(timeout)
        exc = SchedulerClosedError("scheduler closed with the request "
                                   "still pending")
        self.engine.fail_all(exc)
        self._fail_queued(exc, count=False)

    def __enter__(self) -> "Scheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- background threads --------------------------------------------------

    def _stage_loop(self) -> None:
        while not self._stop.is_set():
            try:
                req = self._pending.get(timeout=0.1)
            except queue.Empty:
                continue
            try:
                shed = self._shed_stale(req)
                if shed is not None:
                    self._fail(req, shed)
                    continue
                try:
                    self.engine.stage(req)
                except Exception as e:   # bad request: not a stage killer
                    self._fail(req, e)
                    continue
                placed = False
                while not self._stop.is_set():
                    try:
                        self._staged.put(req, timeout=0.1)
                        placed = True
                        break
                    except queue.Full:
                        continue
                if not placed:
                    # shutdown caught the request in this thread's hands —
                    # it still terminates with the named error, never
                    # silently
                    exc = self._closed_error()
                    self._fail(req, exc)
            finally:
                # the pending pop is fully handled (staged OR failed) —
                # this is what lets drain()'s quiesced predicate see a
                # request that is in this thread's hands
                self._pending.task_done()
        if self._fatal is not None:
            # the loop thread died mid-flight: it swept the queues, but a
            # put of ours may have raced past that sweep — as the ONLY
            # producer into _staged, our exit sweep is the last word
            self._fail_queued(self._closed_error(), count=False)

    def _shed_stale(self, req: Request):
        """The named shed error for a queued request that should never
        reach the engine (cancelled, or past its deadline), else None."""
        if req.cancelled:
            return RequestCancelledError(
                f"request {req.id} cancelled while queued — shed before "
                f"staging")
        if req.expired():
            return DeadlineExceededError(
                f"request {req.id} spent its whole deadline_ms in the "
                f"admission queue — shed before staging (overload)")
        return None

    def _drain_failed(self, req: Request) -> None:
        exc = SchedulerDrainingError("request rejected: scheduler started "
                                     "draining before it was admitted")
        self._fail(req, exc)

    def _reject_queued(self) -> None:
        """Drain mode: everything accepted but not yet admitted fails with
        a NAMED error (clients resubmit elsewhere); in-flight slots finish."""
        for q in (self._staged, self._pending):
            while True:
                try:
                    req = q.get_nowait()
                except queue.Empty:
                    break
                self._drain_failed(req)
                q.task_done()

    def _fail_queued(self, exc: BaseException, count: bool = True) -> None:
        """Terminal sweep: fail everything still queued with ``exc``.
        ``count=False`` on post-stop sweeps — double-failing a handle is
        idempotent, but a second ``task_done`` for one pop would raise."""
        for q in (self._staged, self._pending):
            while True:
                try:
                    req = q.get_nowait()
                except queue.Empty:
                    break
                self._fail(req, exc)
                if count:
                    q.task_done()

    def _fail_fatal(self, exc: BaseException) -> bool:
        """A dead engine (device error mid-decode, donated cache
        invalidated, a shard peer gone) strands every request: record the
        cause, stop the scheduler, and fail everything BY NAME in the loop's
        epilogue — a zombie loop accepting submits it can never serve is
        the one shape this layer forbids.  Returns False (the loop's
        "stop" answer)."""
        self._fatal = exc
        self._stop.set()
        return False

    def _fail(self, req: Request, exc: BaseException) -> None:
        """One request that will not be served fails by name."""
        self.engine._obs_end(req, error_outcome(exc))
        req.fail(exc)

    def _next_group(self, held: list) -> tuple:
        """``(group, wanted)``: the oldest held request and the held
        requests of ITS bucket behind it, in order, up to the width of that
        bucket's prefill program (``engine.prefill_width``); and how many
        the program would carry if the free slots waited for company: the
        width, or as many requests as wait for a prefill of THAT bucket
        (the held ones, and those staged behind them: a request of another
        bucket is no company, however long the slots wait)."""
        of = lambda req: self.engine.bucket_for(len(req.prompt))
        bucket = of(held[0])
        width = self.engine.prefill_width(bucket)
        mates = [req for req in held if of(req) == bucket]
        if len(mates) >= width:
            return mates[:width], width
        with self._staged.mutex:
            staged = list(self._staged.queue)
        return mates, min(width, len(mates) + sum(
            of(req) == bucket for req in staged))

    def _admit(self, group: list) -> bool:
        """Launch one prefill program for ``group`` ahead of the program in
        flight, then collect what came before it; False = fatal engine
        death (stop set).  A member the engine refuses fails by its own
        named error and the others are served; a program that fails fails
        the members it carried, each by name."""
        left = list(group)

        def refuse(req, exc):
            left.remove(req)
            self._fail(req, exc)

        try:
            self.engine.launch_group(group, refuse)
        except Exception as e:   # a bad request must not kill the loop
            for req in left:
                self._fail(req, e)
            fatal = getattr(self.engine, "fatal_error", None)
            if fatal is not None:
                # the failure poisoned the ENGINE, not just the request
                # (a sharded leader whose admit plan was broadcast before
                # its prefill died): shut down with the cause — the loop
                # epilogue fails everything by name, exactly like a
                # fatal step
                return self._fail_fatal(fatal)
            return True
        finally:
            for _ in group:
                self._staged.task_done()
        return self._collect(self.engine.settle)

    def _sweep_once(self) -> bool:
        """One expiry sweep; False = fatal engine death (stop set).  The
        sweep can fail for real on a multi-rank engine — the sharded
        leader broadcasts its free plan AND its idle-liveness probe here,
        so a dead follower's ``PeerGoneError`` surfaces at the iteration
        boundary; it must take the same cause-naming shutdown as a fatal
        step, not kill the loop thread silently."""
        try:
            self.engine.sweep_expired()
        except Exception as e:
            return self._fail_fatal(e)
        return True

    def _step_once(self) -> bool:
        """Launch the next decode step ahead of the program in flight and
        collect what came before it — or, with no row left to launch,
        collect everything.  False = fatal engine death (stop set)."""
        try:
            launched = self.engine.launch_step()
        except Exception as e:
            return self._fail_fatal(e)
        return self._collect(self.engine.settle if launched
                             else self.engine.collect_all)

    def _collect(self, collect: Callable[[], int]) -> bool:
        """Run one of the engine's collecting calls, then count the decode
        steps it finished; False = fatal engine death (stop set)."""
        try:
            collect()
        except Exception as e:
            return self._fail_fatal(e)
        while self._steps < self.engine.steps_done:
            self._steps += 1
            if self.step_hook is not None:
                try:
                    self.step_hook(self._steps)
                except Exception:
                    pass
        with self._idle_cv:
            self._idle_cv.notify_all()
        return True

    def _run_loop(self) -> None:
        # One program is kept enqueued behind the one the thread waits for:
        # each iteration sweeps, decides admissions from the engine's host
        # mirror, LAUNCHES the next program (a held request's prefill, else
        # the next decode step) and only then COLLECTS the one launched
        # before it (engine.settle) — the host reads back step n and sends
        # its tokens while the chip runs step n + 1.  What the mirror
        # cannot know yet arrives one program late: a request that ended by
        # EOS, cancel or deadline has one more row in the step in flight,
        # whose token is dropped at collection.
        held = []            # staged requests inside the coalescing window
        window_start = None
        # free slots wait for a fuller prefill group only so long: the idle
        # slot-steps spent since the waiting began (free slots x decode
        # steps launched), against one step's worth of the pool
        deferred = 0
        while not self._stop.is_set():
            if self._draining.is_set():
                # drain mode: NOTHING new reaches the engine — reject the
                # window + both queues by name (including anything the
                # staging thread surfaces later), and only finish the
                # slots already decoding
                for req in held:
                    self._drain_failed(req)
                    self._staged.task_done()
                held, window_start = [], None
                self._reject_queued()
                # cancelled slots free even while draining — the drain
                # must not wait on them
                if not self._sweep_once():
                    break
                if not self.engine.idle():
                    if not self._step_once():
                        break
                else:
                    with self._idle_cv:
                        self._idle_cv.notify_all()
                    with span("sched.wait", why="drain"):
                        time.sleep(0.01)
                continue
            # -- the iteration boundary: cancelled / past-deadline slots
            # free HERE, before admission sees the free-slot count — a
            # disconnected client's request stops costing decode steps
            # after at most two iterations (the step launched ahead of
            # this boundary still carries its row)
            if not self._sweep_once():
                break
            # -- admission, between decode iterations ------------------------
            # a busy pool admits at the iteration boundary; an idle pool
            # holds the first prefill for up to batch_window so
            # closely-spaced arrivals group up.  One prefill program takes
            # the oldest held request and those of its bucket behind it
            # (_next_group).  It goes at once when it is as full as it can
            # get (the program's width, or every request that waits), and
            # when no slot is active; otherwise the free slots wait for
            # company, for at most num_slots idle slot-steps.  Each
            # admission's collection may free slots: staged arrivals are
            # pulled again for them, a pool's worth at most per iteration
            admitted = 0
            company = False     # free slots are waiting for company
            while not self._stop.is_set():
                # pull staged arrivals (never beyond the free slots)
                while len(held) < self.engine.free_slots():
                    try:
                        held.append(self._staged.get_nowait())
                    except queue.Empty:
                        break
                if held and window_start is None:
                    window_start = _now()
                window_over = window_start is not None and (
                    _now() - window_start >= self.batch_window
                    or len(held) >= self.engine.free_slots())
                go = (not self.engine.idle() or window_over
                      or self.batch_window <= 0)
                if not (held and go) or admitted >= self.engine.num_slots:
                    break
                group, wanted = self._next_group(held)
                if (len(group) < wanted and self.engine.active_count()
                        and deferred < self.engine.num_slots):
                    company = True
                    break
                deferred = 0
                admitted += len(group)
                held = [req for req in held if req not in group]
                if not self._admit(group):
                    break
            if self._stop.is_set():
                break
            if not held:
                window_start = None
                deferred = 0
            # -- one decode iteration over the pool --------------------------
            if not self.engine.idle():
                if company:
                    waiting = self.engine.free_slots()
                    deferred += waiting
                    self.engine.count_deferred(waiting)
                if not self._step_once():
                    break
            elif held:
                # inside the coalescing window: short bounded nap
                with span("sched.wait", why="coalesce"):
                    time.sleep(min(self.batch_window / 4, 0.002))
            else:
                with self._idle_cv:
                    self._idle_cv.notify_all()
                try:
                    with span("sched.wait", why="idle"):
                        arrived = self._staged.get(timeout=0.05)
                    held.append(arrived)
                    window_start = _now()
                except queue.Empty:
                    pass
        # loop exit: requests still held in the window are not dropped
        exc = self._closed_error()
        for req in held:
            self._fail(req, exc)
            self._staged.task_done()
        if self._fatal is not None:
            # fatal engine death: close() early-returns once _stop is set,
            # so THIS thread owns the terminal sweep — decoding slots and
            # queued requests all fail with the cause-naming error (the
            # stage thread's exit sweep catches a racing late put)
            self.engine.fail_all(exc)
            self._fail_queued(exc)
            with self._idle_cv:
                self._idle_cv.notify_all()
