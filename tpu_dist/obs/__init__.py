"""tpu_dist.obs — collective flight recorder, cross-rank trace timeline,
and hang diagnosis.

The standing observability surface for the eager/distributed stack
(docs/observability.md).  Three pieces:

1. **Flight recorder** (:mod:`.recorder`): a lock-cheap per-rank ring
   buffer of structured events — every eager host collective (with its
   lockstep sequence number, reduce op, payload digest, transport path,
   start/end monotonic ns, user call-site and outcome), p2p send/recv,
   store client op, and heartbeat beat.  Armed with ``TPU_DIST_OBS=1``
   (launcher ``--flight-recorder``); disarmed cost is one env lookup per
   hook.  The per-(op, transport) byte/latency counters that
   ``tpu_dist.utils.metrics`` exposes are fed by the same ingestion point.
2. **Crash/hang dump + store tails** (:mod:`.hooks`): unhandled
   exceptions (``RankLostError``, ``CollectiveMismatchError``,
   ``PeerGoneError``, ...), SIGTERM and process exit flush the buffer to
   ``TPU_DIST_OBS_DIR``; each heartbeat re-posts a compact tail under the
   generation-scoped store key ``tpu_dist/g{gen}/obs/{rank}`` so even a
   SIGKILLed rank leaves its last known position behind — the supervisor
   prints the per-rank table before restarting, and the resilience /
   transport errors attach the lost peer's tail to their messages.
3. **Timeline + diagnosis** (:mod:`.trace`, CLI ``python -m
   tpu_dist.obs``): merge the per-rank dumps into one Chrome
   ``trace_event`` timeline (a track per rank, collectives aligned by
   sequence number) and name the hang: which rank is behind, at which
   collective seq and call-site, and which ranks were already waiting.

Beside them, and sharing nothing with them but a request's id,
:mod:`.spans` puts the host phases of the compiled path (the decode loop,
the scheduler's wait, the training step's dispatch) on the profiler's
clock and into :func:`phase_times` — always on, for time on the chip
rather than hangs of the host collectives.  A :class:`LoopClock` closes the
books on every iteration of one loop thread over those spans: by phase,
CPU, garbage collection (``td/gc``) and time off the CPU.  And
:mod:`.compiles` keeps the compile ledger: one record a program JAX traced,
lowered and compiled or loaded from the persistent cache, with the span and
the loop iteration it fell in (:func:`compiles` reads it; the function
shadows the module's name here, so reach the module's other names with
``from tpu_dist.obs.compiles import ...``).
"""

from . import hooks, recorder, spans, trace
from .hooks import (collective_span, fetch_tail, install_from_env, note_path,
                    post_tail, render_tail)
from .recorder import (FlightRecorder, default_dump_dir, dump_now, dump_path,
                       enabled, get_recorder, obs_key, record_transport,
                       reset, reset_transport_counters, transport_counters)
from .spans import LoopClock, phase_times, reset_phases, span
from .compiles import compiles
from .trace import diagnose, merge_trace, read_dumps, render_diagnosis

__all__ = [
    "recorder", "hooks", "trace", "spans",
    "span", "phase_times", "reset_phases", "LoopClock", "compiles",
    "FlightRecorder", "enabled", "get_recorder", "reset", "dump_now",
    "record_transport", "transport_counters", "reset_transport_counters",
    "obs_key", "default_dump_dir", "dump_path",
    "collective_span", "note_path", "install_from_env", "post_tail",
    "fetch_tail", "render_tail",
    "read_dumps", "merge_trace", "diagnose", "render_diagnosis",
]
