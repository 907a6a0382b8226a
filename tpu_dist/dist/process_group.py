"""Process groups over TPU device meshes — the c10d equivalent (L1).

The reference initializes torch.distributed process groups
(``init_process_group('nccl', 'env://', world_size, rank)`` at
/root/reference/mpspawn_dist.py:49-54, /root/reference/launch_dist.py:49,
``tcp://`` at /root/reference/example_mp.py:37-42) where **one process drives
one GPU**, so *rank*, *process* and *device* are the same thing.

On TPU the natural topology is different and this module embraces it:

- **one process per host** drives all local cores (SPMD);
- a :class:`ProcessGroup` is a set of *devices* wrapped in a
  :class:`jax.sharding.Mesh`; collectives ride the ICI torus between them;
- cross-host coordination happens over DCN via JAX's coordination service
  (the TCPStore/NCCL-bootstrap analogue).

Terminology used throughout the framework:

===================  ========================================================
``world_size``       number of **devices** (cores) in the group — the DDP
                     replica count (what the reference calls total GPUs,
                     ``gpus × nodes``, /root/reference/mpspawn_dist.py:136)
``rank``             this **process**'s rank (0..num_processes-1) — what the
                     launcher env contract calls ``RANK``
``num_processes``    host processes participating (= nnodes on TPU)
``local_world_size`` devices addressable by this process
===================  ========================================================

Usage (single host, 8 cores — the ``mp.spawn`` scenario collapsed into one
process)::

    import tpu_dist.dist as dist
    dist.init_process_group(backend="tpu")   # raises unless JAX is on a TPU
    dist.get_world_size()   # 8  (devices)
    dist.get_rank()         # 0  (process)

Multi-host (launched via ``python -m tpu_dist.launch`` or manually with the
MASTER_ADDR/PORT env contract)::

    dist.init_process_group(backend="tpu", init_method="env://")
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import numpy as np

from . import rendezvous as _rdzv

__all__ = [
    "ProcessGroup",
    "BackendMismatchError",
    "resolve_backend",
    "init_process_group",
    "destroy_process_group",
    "is_initialized",
    "get_default_group",
    "get_world_size",
    "get_rank",
    "get_local_rank",
    "get_local_world_size",
    "get_num_processes",
    "new_group",
    "barrier",
    "monitored_barrier",
    "abort",
    "DATA_AXIS",
]

# Default mesh axis name for data parallelism; parallel/ and collectives/
# assume this unless a group was built with custom axes.
DATA_AXIS = "data"

_DEFAULT_GROUP: Optional["ProcessGroup"] = None
_lock = threading.Lock()


class ProcessGroup:
    """A set of devices + the mesh over them.

    The torch analogue is the opaque ``ProcessGroup`` handle returned by
    ``init_process_group``/``new_group`` (/root/reference/README.md:38-43);
    here the handle *is* the mesh, and every collective or parallel wrapper
    takes it (or defaults to the global group).
    """

    def __init__(self, devices: Sequence, axis_names: Sequence[str] = (DATA_AXIS,),
                 mesh_shape: Optional[Sequence[int]] = None,
                 parent: Optional["ProcessGroup"] = None):
        import jax
        from jax.sharding import Mesh

        devices = tuple(devices)
        if not devices:
            raise ValueError("ProcessGroup needs at least one device")
        if mesh_shape is None:
            mesh_shape = (len(devices),)
        if int(np.prod(mesh_shape)) != len(devices):
            raise ValueError(
                f"mesh_shape {tuple(mesh_shape)} does not cover {len(devices)} devices")
        if len(axis_names) != len(mesh_shape):
            raise ValueError("axis_names and mesh_shape must have equal length")
        self._devices = devices
        self._axis_names = tuple(axis_names)
        self._mesh = Mesh(np.array(devices).reshape(tuple(mesh_shape)),
                          self._axis_names)
        self._parent = parent
        self._process_index = jax.process_index()
        self._num_processes = jax.process_count()
        self._destroyed = False

    # -- topology ------------------------------------------------------------
    @property
    def mesh(self):
        """The :class:`jax.sharding.Mesh` over this group's devices."""
        self._check_alive()
        return self._mesh

    @property
    def devices(self):
        return self._devices

    @property
    def axis_name(self) -> str:
        """Primary (data) axis name."""
        return self._axis_names[0]

    @property
    def axis_names(self):
        return self._axis_names

    def size(self) -> int:
        """Device count — DDP replica count."""
        return len(self._devices)

    @property
    def world_size(self) -> int:
        return self.size()

    @property
    def rank(self) -> int:
        """Process rank (the launcher-env ``RANK``)."""
        return self._process_index

    @property
    def num_processes(self) -> int:
        return self._num_processes

    def local_devices(self):
        """Devices of this group addressable by the current process."""
        import jax
        local = set(d.id for d in jax.local_devices())
        return tuple(d for d in self._devices if d.id in local)

    def local_device_ranks(self):
        """Global (group-wise) ranks of this process's devices — what the
        reference computes per worker as ``nr*gpus+gpu``
        (/root/reference/mpspawn_dist.py:47)."""
        import jax
        local = set(d.id for d in jax.local_devices())
        return tuple(i for i, d in enumerate(self._devices) if d.id in local)

    @property
    def local_world_size(self) -> int:
        return len(self.local_devices())

    # -- lifecycle -----------------------------------------------------------
    def _check_alive(self):
        if self._destroyed:
            raise RuntimeError(
                "ProcessGroup used after destroy_process_group()")

    def destroy(self):
        self._destroyed = True

    def __repr__(self):
        return (f"ProcessGroup(world_size={len(self._devices)}, "
                f"rank={self._process_index}/{self._num_processes}, "
                f"axes={dict(zip(self._axis_names, self._mesh.devices.shape))})")


class BackendMismatchError(RuntimeError):
    """The platform JAX resolved is not the backend that was asked for, or
    a TPU backend was asked for from a process that shares its host's chips
    with sibling processes."""


def _normalize_backend(backend: Optional[str]) -> Optional[str]:
    """``None`` (no demand) | ``'tpu'`` | ``'cpu'`` from the torch-style
    backend strings (/root/reference/README.md:133)."""
    if backend is None:
        return None
    backend = backend.lower()
    if backend == "gloo":
        return "cpu"
    # mpi: the reference name-checks it as an alternative accelerator
    # backend; on TPU the accelerator data plane is XLA collectives either
    # way
    if backend in ("nccl", "xla", "mpi"):
        return "tpu"
    if backend not in ("tpu", "cpu"):
        raise ValueError(f"Unknown backend {backend!r}; use 'tpu' or 'cpu'")
    return backend


def _refuse_shared_chips() -> None:
    local = int(os.environ.get("LOCAL_WORLD_SIZE", "1") or 1)
    if local > 1:
        raise BackendMismatchError(
            f"TPU backend with LOCAL_WORLD_SIZE={local}: one process per "
            f"host drives all local chips, and a chip belongs to one "
            f"process at a time — launch with --nproc_per_node=1 (the "
            f"in-process device world is dist.get_world_size())")


def resolve_backend(backend: Optional[str] = None) -> str:
    """The device gate: the platform JAX really resolved (``'tpu'``,
    ``'cpu'``, ...), after checking it against what was asked for.

    An explicit ``backend`` (``'tpu'``/``'cpu'`` or the torch aliases) is a
    demand: a different resolved platform raises
    :class:`BackendMismatchError` naming both and ``JAX_PLATFORMS`` — with
    libtpu installed and no usable chip JAX falls back to the CPU after one
    log line, and everything keyed on ``jax.default_backend()`` (Pallas
    interpret mode, attention auto-dispatch) would degrade silently behind
    it.  ``None`` asks for nothing and reports truthfully.  A TPU platform
    is refused when the launcher spawned sibling processes on this host
    (``LOCAL_WORLD_SIZE > 1``); for an explicit TPU ask that check runs
    BEFORE JAX touches the chip, so the refused process never holds it.
    """
    want = _normalize_backend(backend)
    if want == "tpu":
        _refuse_shared_chips()
    import jax
    have = jax.devices()[0].platform
    if want is not None and have != want:
        raise BackendMismatchError(
            f"backend={backend!r} asks for platform {want!r} but JAX "
            f"resolved {have!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}, devices "
            f"{jax.devices()[:2]}); fix the environment or ask for the "
            f"platform that is there")
    if have == "tpu":
        _refuse_shared_chips()
    return have


def init_process_group(backend: Optional[str] = None,
                       init_method: Optional[str] = None,
                       world_size: int = -1,
                       rank: int = -1,
                       timeout: Optional[float] = None,
                       axis_names: Sequence[str] = (DATA_AXIS,),
                       mesh_shape: Optional[Sequence[int]] = None) -> ProcessGroup:
    """Bring up the default process group (c10d ``init_process_group`` parity).

    ``backend``: ``None`` (default) takes whatever platform JAX resolved
    and reports it truthfully (:func:`get_backend`); ``'tpu'`` (XLA
    collectives over ICI/DCN — the NCCL equivalent; aliases nccl/xla/mpi)
    and ``'cpu'`` (host-platform devices — the gloo equivalent; needs
    ``JAX_PLATFORMS=cpu`` in the environment before the first jax import)
    are demands checked by :func:`resolve_backend`, which raises
    :class:`BackendMismatchError` rather than train on the wrong device.

    ``init_method``: ``None`` (single process), ``'env://'`` (read
    MASTER_ADDR/MASTER_PORT/WORLD_SIZE/RANK — /root/reference/launch_dist.py:49),
    or ``'tcp://host:port'`` (explicit coordinator —
    /root/reference/example_mp.py:37-42).  ``world_size``/``rank`` here are
    **process** counts, exactly the launcher env contract; they override env
    values when given.

    Blocks (like the NCCL rendezvous barrier) until all processes join,
    then builds the group over every device in the slice.
    """
    global _DEFAULT_GROUP
    with _lock:
        if _DEFAULT_GROUP is not None and not _DEFAULT_GROUP._destroyed:
            raise RuntimeError(
                "Default process group already initialized; call "
                "destroy_process_group() first.")

        _normalize_backend(backend)  # an unknown string fails before the join

        _rdzv.rendezvous(init_method, world_size=world_size, rank=rank,
                         timeout=timeout)

        from ..obs.spans import span
        from ..utils.compile_cache import ensure_compile_cache
        ensure_compile_cache()
        import jax
        # the runtime's start, where this is the first touch of the backend
        with span("setup.devices"):
            resolve_backend(backend)
            devices = jax.devices()
        group = ProcessGroup(devices, axis_names=axis_names,
                             mesh_shape=mesh_shape)
        _DEFAULT_GROUP = group
        return group


def is_initialized() -> bool:
    return _DEFAULT_GROUP is not None and not _DEFAULT_GROUP._destroyed


def get_default_group() -> ProcessGroup:
    if not is_initialized():
        raise RuntimeError(
            "Default process group has not been initialized; call "
            "tpu_dist.dist.init_process_group() first.")
    return _DEFAULT_GROUP


def _group(group: Optional[ProcessGroup]) -> ProcessGroup:
    return group if group is not None else get_default_group()


def get_world_size(group: Optional[ProcessGroup] = None) -> int:
    """Device count of the group — the DDP replica count.

    NOTE: on TPU this counts *cores*, not processes; the reference's
    ``world_size = gpus × nodes`` (/root/reference/mpspawn_dist.py:136) counts
    the same thing because there one process == one GPU.
    """
    return _group(group).size()


def get_rank(group: Optional[ProcessGroup] = None) -> int:
    """This process's rank (launcher ``RANK`` env)."""
    return _group(group).rank


def get_backend(group: Optional[ProcessGroup] = None) -> str:
    """torch ``dist.get_backend`` parity: the platform the group's devices
    really are (``'tpu'``, ``'cpu'``) — read from the devices, never from
    the string that was asked for at init."""
    return _group(group).devices[0].platform


def get_num_processes(group: Optional[ProcessGroup] = None) -> int:
    return _group(group).num_processes


def get_local_world_size(group: Optional[ProcessGroup] = None) -> int:
    return _group(group).local_world_size


def get_local_rank() -> int:
    """Local rank from the launcher env (``LOCAL_RANK``,
    /root/reference/launch_dist.py:46); 0 when not launched."""
    return int(os.environ.get("LOCAL_RANK", 0))


def new_group(ranks: Optional[Sequence[int]] = None,
              axis_names: Sequence[str] = (DATA_AXIS,),
              mesh_shape: Optional[Sequence[int]] = None) -> ProcessGroup:
    """Sub-group over a subset of *device ranks* (c10d ``new_group``,
    /root/reference/README.md:27-28,39).

    Every process must call this collectively with identical ``ranks``.  The
    sub-group's mesh spans only those devices; collectives over it ride the
    sub-torus.
    """
    default = get_default_group()
    if ranks is None:
        ranks = range(default.size())
    devices = [default.devices[r] for r in ranks]
    return ProcessGroup(devices, axis_names=axis_names,
                        mesh_shape=mesh_shape, parent=default)


def barrier(group: Optional[ProcessGroup] = None) -> None:
    """Block until all processes in the group reach the barrier.

    Implemented as a tiny psum over one device per process (the TPU analogue
    of a store-based barrier); a no-op single-process.
    """
    g = _group(group)
    if g.num_processes <= 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("tpu_dist.barrier")


_MB_SEQ = [0]  # per-process monitored_barrier call counter (all processes
               # must call it in the same order, like every collective)
_MB_PASSED = [-1]  # last seq THIS process passed successfully — gates GC of
                   # the previous generation's keys (below)


def monitored_barrier(group: Optional[ProcessGroup] = None,
                      timeout: float = 300.0) -> None:
    """Barrier that NAMES the ranks that failed to arrive (torch
    ``dist.monitored_barrier`` parity — its debugging use-case is finding
    the hung rank in a deadlocked job).

    Each process posts an arrival key on the control-plane store; process
    0 collects them under ``timeout`` seconds and raises ``RuntimeError``
    listing every missing process rank (c10d's ``wait_all_ranks=True``
    behavior — all stragglers, not just the first), then publishes the
    release key the others wait on.  No-op single-process; raises
    ``RuntimeError`` when the job has no control-plane store (pure
    ``tcp://``-less bring-up) — fall back to :func:`barrier` there.

    Default-group only (like :func:`barrier`'s global sync): a subgroup's
    process membership is not tracked against store keys, so passing one
    raises rather than produce a wrong diagnosis.
    """
    g = _group(group)
    if g._parent is not None:
        raise ValueError("monitored_barrier supports the default group "
                         "only (a subgroup diagnosis would misname "
                         "non-member ranks as missing)")
    if g.num_processes <= 1:
        return
    store = _rdzv.get_store()
    if store is None:
        raise RuntimeError(
            "monitored_barrier needs the control-plane store (launcher or "
            "env:// / tcp:// bring-up); use dist.barrier() instead")
    seq = _MB_SEQ[0]
    _MB_SEQ[0] += 1
    rank = get_rank()
    n = g.num_processes
    prefix = f"__monitored_barrier__/{seq}"
    if seq > 0 and _MB_PASSED[0] == seq - 1:
        # GC this rank's previous-generation arrival key so periodic calls
        # (per-epoch debugging) don't grow the store without bound.  Safe
        # only because this rank PASSED seq-1 (rank 0 finished reading
        # arrived/* before publishing the /go we saw) — a rank that timed
        # out on seq-1 and retried must NOT delete: rank 0 may still be
        # polling seq-1 and would falsely name this rank missing (that
        # error path leaks one key, which is fine).  The seq-1 /go key
        # itself must not be deleted yet either — a straggler may still be
        # waiting on it (rank 0 returns the moment it sets /go); it is
        # GC'd below once rank 0 has seen every rank arrive at THIS
        # barrier, which proves all left the previous one.
        store.delete_key(f"__monitored_barrier__/{seq - 1}/arrived/{rank}")
    store.set(f"{prefix}/arrived/{rank}", b"1")
    import time as _time
    deadline = _time.monotonic() + timeout
    if rank == 0:
        missing = list(range(1, n))
        while True:  # poll at least once: timeout=0 must not misdiagnose
            missing = [r for r in missing
                       if not store.check(f"{prefix}/arrived/{r}")]
            if not missing or _time.monotonic() >= deadline:
                break
            _time.sleep(0.01)
        if missing:
            raise RuntimeError(
                f"monitored_barrier timed out after {timeout}s; process "
                f"rank(s) {missing} did not reach the barrier")
        if seq > 0:
            # Everyone arrived here, so everyone left barrier seq-1: its
            # release key has no remaining readers and can be GC'd.
            store.delete_key(f"__monitored_barrier__/{seq - 1}/go")
        store.set(f"{prefix}/go", b"1")
        _MB_PASSED[0] = seq
    else:
        try:
            store.wait([f"{prefix}/go"],
                       timeout=max(deadline - _time.monotonic(), 0.0))
        except TimeoutError:
            raise RuntimeError(
                f"monitored_barrier timed out after {timeout}s waiting "
                f"for process 0's release") from None
        _MB_PASSED[0] = seq


def abort(exit_code: int = 1, reason: str = "") -> None:
    """Terminate this process IMMEDIATELY without distributed teardown
    (torch ``ProcessGroup.abort`` / NCCL error-handling parity).

    Why it exists: ``sys.exit`` after a distributed failure can HANG —
    jax.distributed's atexit shutdown runs a peer barrier, so a process
    exiting because a *peer* is hung blocks on that same hung peer, the
    launcher sees every child still alive, and fail-fast never fires
    (measured: a worker that raised on :func:`monitored_barrier` timeout
    then ``sys.exit(7)``-ed kept the whole world up for the coordination
    service's multi-minute shutdown timeout).  ``abort`` flushes stdio and
    ``os._exit``-s, so the launcher reaps the exit code at once and kills
    the rest of the world.  Use it in except-handlers around collectives::

        try:
            dist.monitored_barrier(timeout=60)
        except RuntimeError as e:
            print(e, file=sys.stderr)
            dist.abort(7)
    """
    import sys as _sys

    if reason:
        print(f"tpu_dist.abort: {reason}", file=_sys.stderr)
    try:
        # os._exit skips atexit, so the flight recorder (if armed) must
        # flush here — the abort path IS the interesting crash dump
        from ..obs import recorder as _obs_recorder
        _obs_recorder.dump_now(f"abort:{exit_code}")
    except Exception:
        pass
    try:
        _sys.stdout.flush()
        _sys.stderr.flush()
    except Exception:
        pass
    os._exit(exit_code)


def destroy_process_group(group: Optional[ProcessGroup] = None) -> None:
    """Tear down the group (c10d parity, /root/reference/README.md:43).

    Destroying the default group also shuts down the JAX distributed client
    when one was started.
    """
    global _DEFAULT_GROUP
    with _lock:
        g = group if group is not None else _DEFAULT_GROUP
        if g is None:
            return
        g.destroy()
        if g is _DEFAULT_GROUP:
            _DEFAULT_GROUP = None
            _rdzv.shutdown()
