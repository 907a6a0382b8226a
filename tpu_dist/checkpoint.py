"""Checkpoint / resume — torch.save/load parity (SURVEY.md §5: absent in the
reference, listed as the natural extension).

Self-contained format (no torch pickle, no framework lock-in): each
checkpoint is a directory holding

- ``tree.json`` — the pytree structure: flattened key paths + leaf metadata
  (shape/dtype), plus user metadata;
- ``arrays.npz`` — the leaf arrays, keyed by flattened path.

Writes are atomic (tmp dir + rename), step-numbered
(``<root>/step_00000100/``), and multi-host safe: only process 0 writes,
every process restores.  ``latest_step`` finds the newest checkpoint for
resume.

Sharded state is handled on both sides:

- **save**: leaves that are not fully addressable (multi-host shardings)
  are all-gathered across processes before process 0 writes — so every
  process MUST call :func:`save` (it is a collective in that case);
  fully-addressable sharded leaves (e.g. single-host ZeRO-1 opt_state)
  gather locally via ``np.asarray``.
- **restore**: pass ``sharding=`` to re-place leaves;
  :meth:`tpu_dist.parallel.DistributedDataParallel.state_shardings` builds
  the matching pytree for a TrainState (params and opt_state sharded leaf
  by leaf where the weight update is: the default over a group of more
  than one) so such a state round-trips with its placement intact; a
  state saved whole restores under it the same way.

Works on any pytree of arrays — :class:`tpu_dist.parallel.TrainState`
included (its PRNG key is stored as key *data*, a plain uint32 array).
"""

from __future__ import annotations

import json
import os
import re
import shutil
import tempfile
from typing import Any, Dict, Optional

import numpy as np

__all__ = ["save", "restore", "latest_step", "all_steps", "shard_root",
           "prune_sharded", "DigestError", "AsyncCheckpointer",
           "GracefulShutdown"]

_STEP_DIR = re.compile(r"^step_(\d{8})$")


class DigestError(ValueError):
    """A checkpoint (or a single shard fragment, on the elastic reshard
    path) failed sha256 verification against the digest recorded at save
    time: truncated, bit-rotted, or tampered — refusing to load is always
    better than resuming divergent."""


def _flatten(tree):
    import jax
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in leaves}


def _materialize(leaf) -> np.ndarray:
    """Bring a leaf fully to host.

    Non-fully-addressable jax.Arrays (multi-host shardings, incl. multi-host
    ZeRO-1 opt_state) are all-gathered across processes — a COLLECTIVE, so
    every process must reach this point; fully-addressable leaves (host
    arrays, replicated or single-host-sharded device arrays) convert
    directly.
    """
    import jax

    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
    return np.asarray(leaf)


def _participate_in_gather(tree) -> None:
    """Non-zero processes' half of the save collective: join the allgather
    of every non-fully-addressable leaf, write nothing.  Must mirror the
    leaf order of the writing process (both iterate ``_flatten``)."""
    import jax

    for leaf in _flatten(tree).values():
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            _materialize(leaf)


def shard_root(root: str, rank: int) -> str:
    """The per-rank checkpoint root for rank-sharded state (ZeRO optimizer
    shards): ``<root>/shard_r{rank:03d}``.  Each rank owns its directory
    outright, so the atomic tmp+rename machinery applies unchanged and
    ranks never race on one ``arrays.npz``."""
    return os.path.join(root, f"shard_r{int(rank):03d}")


def save(root: str, tree: Any, step: int, metadata: Optional[Dict] = None,
         keep: Optional[int] = None,
         shard: Optional[tuple] = None) -> str:
    """Write checkpoint ``root/step_{step:08d}``; returns its path.

    ``keep=N`` prunes to the newest N step dirs after a successful write.
    Only process 0 writes, but when the tree holds non-fully-addressable
    (multi-host-sharded) leaves EVERY process must call save — the gather
    of those leaves is a collective.  Non-zero processes return the target
    path without touching disk (call :func:`tpu_dist.dist.barrier` after if
    you need completion before proceeding).

    ``shard=(rank, world)`` writes **rank-sharded** state (per-rank ZeRO
    optimizer shards, tpu_dist/parallel/zero.py): EVERY rank writes its own
    tree — which differs per rank by design — under
    :func:`shard_root`, with the shard coordinates recorded in the
    metadata.  When the tree carries ZeRO layout meta (leaf sizes +
    dtypes), a **reshard manifest** is embedded too — which saved arrays
    are sharded along the group axis, per-fragment sha256 digests — so a
    later restore at a *different* world size is self-describing and
    digest-verified per fragment (tpu_dist/resilience/reshard.py).
    :func:`restore` itself still refuses a shard-coordinate mismatch;
    elastic restores go through ``resilience.TrainState.resume`` or
    ``reshard.reshard_restore``.
    """
    import jax

    if shard is not None:
        rank, world = int(shard[0]), int(shard[1])
        sroot = shard_root(root, rank)
        path = os.path.join(sroot, f"step_{step:08d}")
        meta = dict(metadata or {})
        meta["shard_rank"], meta["shard_world"] = rank, world
        arrays = {k: _materialize(v) for k, v in _flatten(tree).items()}
        try:
            from .resilience.reshard import manifest_from_arrays
            manifest = manifest_from_arrays(arrays)
        except Exception as e:
            # manifest is additive; never fail the save — but a silent
            # omission leaves a world-size-pinned checkpoint that only
            # surfaces when the old-world gang is already gone, so make
            # the loss of portability visible while it is still fixable
            manifest = None
            try:
                from .utils.logging import log_event
                log_event("reshard-manifest-failed", step=step,
                          shard=f"r{rank}/w{world}", error=repr(e))
            except Exception:
                pass
        if manifest is not None:
            meta["reshard"] = manifest
        _write(sroot, path, arrays, step, meta, keep)
        return path

    path = os.path.join(root, f"step_{step:08d}")
    if jax.process_index() != 0:
        _participate_in_gather(tree)
        return path
    # materialize (the collective part) BEFORE any fallible filesystem op:
    # a proc-0 I/O error must raise, not strand peers inside the allgather
    arrays = {k: _materialize(v) for k, v in _flatten(tree).items()}
    _write(root, path, arrays, step, metadata, keep)
    return path


def _fsync_path(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _sha256_file(path: str) -> str:
    import hashlib
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _write(root: str, path: str, arrays: Dict[str, np.ndarray], step: int,
           metadata: Optional[Dict], keep: Optional[int]) -> None:
    """Serialize already-host-side arrays to ``path`` (atomic tmp+rename),
    then prune to the newest ``keep`` step dirs.  Pure host I/O — safe to
    run off-thread (the AsyncCheckpointer's worker).

    Durability: both files and the tmp dir are fsync'd before the rename,
    and the parent dir after — without that, a host crash can surface a
    "committed" (renamed) checkpoint whose data blocks never hit disk,
    i.e. a truncated arrays.npz behind a valid-looking directory.  The
    npz's sha256 rides in tree.json so :func:`restore` can verify."""
    os.makedirs(root, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=root, prefix=".tmp_ckpt_")
    try:
        npz_path = os.path.join(tmp, "arrays.npz")
        np.savez(npz_path, **arrays)
        meta = {
            "step": step,
            "leaves": {k: {"shape": list(a.shape), "dtype": str(a.dtype)}
                       for k, a in arrays.items()},
            "metadata": metadata or {},
            "arrays_sha256": _sha256_file(npz_path),
            "format_version": 1,
        }
        with open(os.path.join(tmp, "tree.json"), "w") as f:
            json.dump(meta, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        _fsync_path(npz_path)
        _fsync_path(tmp)
        if os.path.exists(path):
            shutil.rmtree(path)
        os.rename(tmp, path)
        _fsync_path(root)  # persist the rename itself
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    if keep is not None:
        for s in all_steps(root)[:-keep]:
            shutil.rmtree(os.path.join(root, f"step_{s:08d}"),
                          ignore_errors=True)


class AsyncCheckpointer:
    """Background checkpoint writer — the step loop never blocks on disk.

    ``save()`` splits the work at the only boundary that matters on TPU:
    the device→host transfer (which must see the live arrays, and is the
    collective part under multi-host shardings) runs synchronously in the
    caller, then serialization + atomic rename + pruning run on a single
    worker thread.  The train loop reclaims the save latency that matters
    (disk I/O); the host copy it still pays is the same one the optimizer
    barrier already forces.

    One write in flight at a time: a new ``save`` first joins the previous
    one (bounded memory — at most two host copies of the state alive), and
    any worker exception re-raises there, in ``wait()``, or in ``close()``.
    Use as a context manager to guarantee the last write lands::

        with AsyncCheckpointer(root, keep=3) as ckpt:
            for step in range(n):
                state, _ = ddp.train_step(state, x, y)
                if step % 100 == 0:
                    ckpt.save(jax.device_get(state), step=step)

    torch parity note: torch.save has no async form; this plays the role
    orbax's AsyncCheckpointer plays in the JAX ecosystem, over the same
    self-contained directory format as :func:`save` (restore with
    :func:`restore`, fully interchangeable).
    """

    def __init__(self, root: str, keep: Optional[int] = None):
        from concurrent.futures import ThreadPoolExecutor
        self.root = root
        self.keep = keep
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="tpu_dist-ckpt")
        self._inflight = None

    def save(self, tree: Any, step: int,
             metadata: Optional[Dict] = None) -> str:
        """Queue ``root/step_{step:08d}``; returns its (future) path.

        Blocks only for (a) the previous write, if still running, and
        (b) the device→host materialization of ``tree``.  Under multi-host
        shardings every process must call this (the gather is collective);
        non-zero processes return without queuing I/O, like :func:`save`.
        """
        import jax

        path = os.path.join(self.root, f"step_{step:08d}")
        if self._pool is None:
            raise RuntimeError("AsyncCheckpointer is closed")
        # tpudlint: disable=TD004  # local async-write join, no remote peer
        self.wait()  # one in-flight write; surfaces previous write errors
        if jax.process_index() != 0:
            _participate_in_gather(tree)
            return path

        def snapshot(v):
            a = _materialize(v)
            # the async write must OWN its data: np.asarray is a no-copy
            # view both for host numpy leaves (caller may mutate after
            # save() returns) and for CPU-backend jax Arrays (the next
            # donated train step overwrites the buffer in place while the
            # worker is still serializing it)
            if a is v or not a.flags.owndata:
                a = a.copy()
            return a

        arrays = {k: snapshot(v) for k, v in _flatten(tree).items()}
        self._inflight = self._pool.submit(
            _write, self.root, path, arrays, step, metadata, self.keep)
        return path

    def wait(self) -> None:
        """Join the in-flight write; re-raises its exception if it failed."""
        if self._inflight is not None:
            fut, self._inflight = self._inflight, None
            fut.result()

    def close(self) -> None:
        """Finish the in-flight write and shut the worker down."""
        if self._pool is not None:
            try:
                # tpudlint: disable=TD004  # local async-write join
                self.wait()
            finally:
                self._pool.shutdown(wait=True)
                self._pool = None

    def __enter__(self) -> "AsyncCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def all_steps(root: str):
    """Sorted list of checkpointed step numbers under ``root``."""
    if not os.path.isdir(root):
        return []
    steps = []
    for name in os.listdir(root):
        m = _STEP_DIR.match(name)
        if m and os.path.exists(os.path.join(root, name, "tree.json")):
            steps.append(int(m.group(1)))
    return sorted(steps)


def latest_step(root: str) -> Optional[int]:
    steps = all_steps(root)
    return steps[-1] if steps else None


def prune_sharded(root: str, keep: int) -> list:
    """Prune a sharded checkpoint *tree* (replicated root + every
    ``shard_r*`` root) to the newest ``keep`` **complete** steps; returns
    the pruned step numbers.

    Per-root ``keep=`` pruning is wrong for sharded trees: each root prunes
    on its own cadence, so under skew (one rank saving behind the others,
    or a mid-save kill) a root can delete the one older step that is still
    complete *everywhere* — exactly the step the intersection-based resume
    agreement would pick — leaving the gang nothing to resume from.  This
    prunes on the **tree** invariant instead: a step is deletable only
    when at least ``keep`` newer steps are complete — replicated checkpoint
    present and, at the world each step's own shard metadata records, every
    shard 0..world-1 present (:func:`~tpu_dist.resilience.reshard.resumable_steps`,
    so mixed-world trees left behind by elastic shrink/grow prune
    correctly too).  Incomplete steps newer than the cutoff are left for
    their writers to finish; any step older than the cutoff goes,
    complete or not.

    Safe to call from every rank (deletions are idempotent; a racing rank
    that still sees an in-flight step as incomplete merely prunes less).

    Assumes the shared checkpoint root :class:`~tpu_dist.resilience.TrainState`
    documents (every shard root visible on this filesystem).  On a rig
    with per-host private disks the local view can never prove a step
    complete, so this deliberately prunes NOTHING there (safe, but the
    operator must prune externally) — deleting on a partial view could
    destroy the one step the gang's resume agreement needs.
    """
    from .resilience.reshard import local_visibility, resumable_steps
    complete = sorted(resumable_steps([local_visibility(root)]))
    if keep is None or len(complete) <= max(int(keep), 0):
        return []
    cutoff = complete[-int(keep)]
    roots = [root]
    if os.path.isdir(root):
        roots += [os.path.join(root, name)
                  for name in sorted(os.listdir(root))
                  if name.startswith("shard_r")
                  and os.path.isdir(os.path.join(root, name))]
    pruned = set()
    for r in roots:
        for s in all_steps(r):
            if s < cutoff:
                shutil.rmtree(os.path.join(r, f"step_{s:08d}"),
                              ignore_errors=True)
                pruned.add(s)
    return sorted(pruned)


def restore(root: str, template: Any, step: Optional[int] = None,
            sharding=None, verify: bool = False,
            shard: Optional[tuple] = None) -> Any:
    """Load a checkpoint into the structure of ``template``.

    ``step=None`` loads the latest.  ``sharding`` controls device placement:
    a single ``jax.sharding.Sharding`` applies to every leaf; a pytree
    matching ``template``'s structure gives per-leaf placement.  Default
    leaves arrays on host for the caller to place.  ``verify=True``
    recomputes ``arrays.npz``'s sha256 against the digest recorded at save
    time before deserializing — the load-time check for a checkpoint
    corrupted after commit (bit rot, partial copy, crash without fsync).

    ``shard=(rank, world)`` loads this rank's rank-sharded state (see
    :func:`save`): the recorded shard coordinates must match exactly —
    direct restore is the fast same-world path; a checkpoint saved at a
    different world size resumes through elastic resharding
    (``resilience.TrainState.resume`` / ``resilience.reshard``).

    Raises with a precise message when the tree structure or a leaf
    shape/dtype does not match the template — resuming into a changed model
    must fail loudly, not load garbage.
    """
    import jax

    if shard is not None:
        root = shard_root(root, int(shard[0]))
    if step is None:
        step = latest_step(root)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {root!r}")
    path = os.path.join(root, f"step_{step:08d}")
    with open(os.path.join(path, "tree.json")) as f:
        meta = json.load(f)
    if shard is not None:
        rank, world = int(shard[0]), int(shard[1])
        rec = meta.get("metadata", {})
        got = (rec.get("shard_rank"), rec.get("shard_world"))
        if got != (rank, world):
            raise ValueError(
                f"sharded checkpoint at {path!r} was saved as rank "
                f"{got[0]} of world {got[1]}, but this process is rank "
                f"{rank} of world {world}.  Direct restore is exact-match "
                f"only; to resume at a different world size use elastic "
                f"resharding (resilience.TrainState.resume, or "
                f"resilience.reshard.reshard_restore).")
    npz_path = os.path.join(path, "arrays.npz")
    if verify:
        recorded = meta.get("arrays_sha256")
        if recorded is None:
            raise ValueError(
                f"checkpoint at {path!r} records no arrays digest (written "
                f"by an older tpu_dist); re-save it or pass verify=False")
        actual = _sha256_file(npz_path)
        if actual != recorded:
            raise DigestError(
                f"checkpoint at {path!r} failed digest verification "
                f"(recorded sha256 {recorded[:12]}…, actual {actual[:12]}…) "
                f"— truncated or corrupted; refusing to load")
    with np.load(npz_path) as npz:
        arrays = {k: npz[k] for k in npz.files}

    flat_t = _flatten(template)
    missing = sorted(set(flat_t) - set(arrays))
    extra = sorted(set(arrays) - set(flat_t))
    if missing or extra:
        raise ValueError(
            f"checkpoint at {path!r} does not match template: "
            f"missing={missing[:5]}{'…' if len(missing) > 5 else ''} "
            f"extra={extra[:5]}{'…' if len(extra) > 5 else ''}")
    for k, tleaf in flat_t.items():
        # metadata-only checks: no np.asarray — that would pull every device
        # array to host (and fail outright on non-fully-addressable shards)
        tshape = tuple(np.shape(tleaf))
        tdtype = np.dtype(getattr(tleaf, "dtype", np.result_type(tleaf)))
        if tuple(arrays[k].shape) != tshape:
            raise ValueError(
                f"checkpoint leaf {k!r} shape {arrays[k].shape} != template "
                f"{tshape}")
        if arrays[k].dtype != tdtype:
            raise ValueError(
                f"checkpoint leaf {k!r} dtype {arrays[k].dtype} != template "
                f"{tdtype}; cast the template (or re-save) explicitly "
                f"rather than loading silently converted values")

    from jax.sharding import Sharding
    if sharding is None or isinstance(sharding, Sharding):
        flat_s = {k: sharding for k in flat_t}
    else:
        flat_s = _flatten(sharding)
        if set(flat_s) != set(flat_t):
            raise ValueError(
                "sharding pytree structure does not match template")

    treedef = jax.tree_util.tree_structure(template)
    out_leaves = []
    for key in flat_t:  # _flatten preserves leaf order
        a = arrays[key]
        if flat_s[key] is not None:
            a = jax.device_put(a, flat_s[key])
        out_leaves.append(a)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


class GracefulShutdown:
    """Preemption-safe training: save on SIGTERM, exit cleanly, resume.

    Cloud TPU VMs receive SIGTERM ahead of maintenance/preemption (and
    torchelastic sends it to workers it is about to tear down); a handler
    cannot safely serialize device state from signal context, so this
    follows the flag pattern (orbax/t5x): the handler only records the
    request, the step loop checks it at the next iteration boundary and
    saves::

        with GracefulShutdown() as stop, \\
             AsyncCheckpointer(root, keep=3) as ckpt:
            for step in range(start, n):
                state, _ = ddp.train_step(state, x, y)
                if stop.requested:
                    ckpt.save(jax.device_get(state), step=step)
                    break          # launcher restarts -> restore(latest)

    Pairs with ``python -m tpu_dist.launch --max_restarts`` (the restarted
    round resumes via :func:`latest_step` + :func:`restore`).  Installed
    handlers are restored on exit; entering from a non-main thread raises
    (Python only delivers signals to the main thread).
    """

    def __init__(self, signals=None):
        import signal as _signal
        self._signal = _signal
        # SIGTERM only by default: capturing SIGINT would make Ctrl-C
        # unable to break out of a step hung inside a collective (the flag
        # is only read at loop boundaries).  Opt in explicitly with
        # ``signals=(SIGTERM, SIGINT)`` for non-interactive jobs.
        self.signals = tuple(signals) if signals is not None else (
            _signal.SIGTERM,)
        self._previous = {}
        self.requested = False
        self.signum = None

    def _handler(self, signum, frame):
        self.requested = True
        self.signum = signum

    def __enter__(self):
        try:
            for s in self.signals:
                self._previous[s] = self._signal.signal(s, self._handler)
        except BaseException:
            self.__exit__()  # restore the handlers already installed
            raise
        return self

    def __exit__(self, *exc):
        for s, prev in self._previous.items():
            self._signal.signal(s, prev)
        self._previous.clear()
        return False
