"""The slot cache's format: what a layer keeps per slot, how a forward pass
addresses it, and how rows are written, cut and joined along time.

A cache (a pool of slots, or one request's batch-1 *rows*) is a tree
``{layer path: {name: leaf}}`` of RESIDENT leaves only, of three kinds told
apart by name (:func:`is_timed`, :func:`pool_leaf`):

- TIME-INDEXED leaves BY HEAD, one column a position: ``k``/``v`` ``(B,
  Hkv, D, Tmax)`` and, for the int8 cache, ``k_scale``/``v_scale`` ``(B, H,
  Tmax)`` (:meth:`MultiheadSelfAttention.init_cache` builds them and says
  why time is last);
- a TIME-INDEXED leaf WITHOUT a head axis: ``latent`` ``(B, C, Tmax)``, the
  normalised latent and the roped shared key that every head of a latent
  attention layer reads (:meth:`MultiheadLatentAttention.init_cache`).
  Time is its last axis too, so every function below that cuts, pads,
  joins or describes a tree along time serves it as it serves ``k``;
- a slot's WHOLE STATE, with no time axis: any other name, such as
  :class:`GatedDeltaNet`'s ``state`` ``(B, Hv, Dk, Dv)`` float32 and its
  convolution tail ``conv`` (:meth:`GatedDeltaNet.init_cache`).  A
  convolution may be a layer's WHOLE mixer and may have no activation
  (:class:`GatedShortConv`): its entry is the tail ``conv`` ALONE, with no
  ``state`` and no pool (:func:`pool_leaf` None), and everything below
  serves it as it serves any other leaf of whole state.  A layer
  replaces such a leaf entire at every call; it cannot be cut, padded or
  joined along time, and the functions below that do so pass it through
  or refuse it, each as its docstring says.

The tree is keyed by the path of the MIXER that owns the entry, not by
layer: one layer may own entries of both kinds, where two mixers run side
by side on one input (:class:`ParallelMixer`: ``block3.attn.attention`` with
``k``/``v`` and ``block3.attn.ssm`` with ``state``/``conv``).  Nothing below
reads a layer, so such a slot is served as one whose layers alternate the
kinds.

What a single call adds travels beside it and is put in and taken out HERE
and nowhere else:

- ``index``, the call's write position, read by
  :meth:`MultiheadSelfAttention._decode` from its own entry: a scalar when
  every row writes at one position (a whole prompt), a ``(B,)`` vector for a
  slot step where each row stands at its own;
- ``valid``, the call's mask of positions that are a request's (``(B, t)``
  or None for all): a recurrent layer leaves its state as it was over the
  others (bucket padding, a free slot's row), which causal attention need
  not care about;
- the routed-row counters of the expert layers
  (:meth:`MoELayer.init_counters`), a tree of their own keyed by the
  ``MoELayer`` paths, with the same ``valid``, read by
  :meth:`MoELayer._count_rows`.

Three groups of functions: the layout (time is the last axis; pad, cut, join
and describe a tree along it), the call (:func:`call_state` /
:func:`split_state`), and the slot write (:func:`write_slot_rows`).  The
models, the engines and the host-side KV movers (serve/prefix, kvtransfer,
disagg, sharded) ask here instead of indexing shapes or filtering names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["is_timed", "state_leaves", "require_timed", "require_heads",
           "pool_leaf",
           "kv_entries", "slot_bytes", "time_axis", "time_slice", "extent", "pad_time",
           "join_time", "token_template", "call_state", "split_state",
           "write_slot_rows"]

# a pool's values, by head or headless, and the int8 pool's scales beside them
_VALUES = ("k", "v", "latent")
_TIMED = frozenset(_VALUES + ("k_scale", "v_scale"))


# -- the layout ---------------------------------------------------------------

def is_timed(name: str) -> bool:
    """Whether the leaf of this name is indexed by time (one column a
    position) or is a slot's whole state."""
    return name in _TIMED


def state_leaves(cache) -> list:
    """``["path.name", ...]`` of the leaves that are a slot's whole state
    (empty for a model of attention layers alone)."""
    return [f"{path}.{name}" for path, entry in cache.items()
            for name in entry if not is_timed(name)]


def require_timed(cache, who: str) -> None:
    """Refuse, by the leaf's name, a cache that ``who`` cannot move: the
    host-side movers (serve/prefix, kvtransfer, disagg, sharded) cut, join
    and ship rows ALONG TIME, and a state leaf has no time axis.  Reusing
    or shipping it takes a snapshot of the state at the cut, which they do
    not have yet."""
    found = state_leaves(cache)
    if found:
        raise NotImplementedError(
            f"{who} moves cache rows along time, and leaf {found[0]!r} is a "
            f"slot's whole state with no time axis ({len(found)} such "
            f"leaves); it needs state snapshots, which it does not have")


def require_heads(cache, who: str) -> None:
    """Refuse, by the leaf's name, a cache that ``who`` divides BY HEAD
    (tensor-parallel serving gives each shard its heads' K/V): a
    ``latent`` leaf is one column a position for all heads and has no
    head axis to divide."""
    found = [f"{path}.{name}" for path, entry in cache.items()
             for name in entry if name == "latent"]
    if found:
        raise NotImplementedError(
            f"{who} divides the cache by head, and leaf {found[0]!r} is a "
            f"latent every head reads, with no head axis ({len(found)} "
            f"such leaves)")


def pool_leaf(entry):
    """The first time-indexed leaf of a layer's ``entry`` that holds the
    pool's values (``k`` of a K/V pool, ``latent`` of a latent one; not an
    int8 pool's scales): the leaf whose extent, type and tiling say what
    the pool is.  None for an entry of whole state alone."""
    return next((entry[name] for name in _VALUES if name in entry), None)


def kv_entries(cache) -> list:
    """The entries that hold a time-indexed pool of either kind (the
    attention layers')."""
    return [entry for entry in cache.values()
            if pool_leaf(entry) is not None]


def slot_bytes(cache) -> tuple:
    """``(bytes of whole state a slot holds, bytes a resident position
    holds)`` over all layers: what a decode step must read AND write of a
    busy slot whatever its length, and what it reads per position held."""
    state = per_pos = 0
    for entry in cache.values():
        for name, leaf in entry.items():
            row = leaf.dtype.itemsize * int(np.prod(leaf.shape[1:]))
            if is_timed(name):
                per_pos += row // leaf.shape[time_axis(leaf)]
            else:
                state += row
    return state, per_pos


def time_axis(leaf) -> int:
    """The time axis of a time-indexed leaf: the LAST one, for ``k``/``v``,
    the int8 scales and a headless ``latent`` alike.  (A state leaf has
    none.)"""
    return leaf.ndim - 1


def time_slice(leaf, lo, hi):
    """Columns ``[lo, hi)`` of a time-indexed leaf along its time axis (a
    view, clipped to the leaf's extent like any slice)."""
    idx = [slice(None)] * leaf.ndim
    idx[time_axis(leaf)] = slice(lo, hi)
    return leaf[tuple(idx)]


def extent(cache):
    """``(max_len, dtype)`` of a pool or row tree: the positions a slot
    holds and the type its pool is stored in, what
    ``init_slot_cache(batch, max_len, dtype)`` was given.  Read from the
    first time-indexed pool, of either kind; state leaves keep types of
    their own."""
    entries = kv_entries(cache)
    if not entries:
        raise ValueError("extent() reads a time-indexed pool, and this "
                         f"cache holds none (leaves: {state_leaves(cache)})")
    leaf = pool_leaf(entries[0])
    return leaf.shape[time_axis(leaf)], leaf.dtype


def _map_leaves(fn, tree, state=lambda leaf: leaf):
    """``fn`` over the time-indexed leaves, ``state`` over the others."""
    return {path: {name: (fn if is_timed(name) else state)(leaf)
                   for name, leaf in entry.items()}
            for path, entry in tree.items()}


def pad_time(rows, total: int):
    """Host rows zero-padded along time to ``total`` columns (a bucket, or
    the whole ``max_len``): the fixed shape one compiled program takes.  A
    state leaf has one shape whatever the bucket and passes through."""
    def pad(leaf):
        width = [(0, 0)] * leaf.ndim
        width[time_axis(leaf)] = (0, total - leaf.shape[time_axis(leaf)])
        return np.pad(leaf, width)

    return _map_leaves(pad, rows)


def join_time(chain):
    """One host row tree from a chain of them, joined along time in order
    (a prefix-cache hit's blocks).  Time-indexed leaves only: the state
    after a chain is not a join of its blocks' states
    (:func:`require_timed`)."""
    require_timed(chain[0], "join_time")
    return {path: {name: np.concatenate([rows[path][name] for rows in chain],
                                        axis=time_axis(leaf))
                   for name, leaf in entry.items()}
            for path, entry in chain[0].items()}


def token_template(cache):
    """``{path: {name: (per-token shape, dtype)}}``: each time-indexed
    leaf's shape less its batch and time axes; a state leaf's whole shape
    less its batch axis (it is per slot, not per token).  Two endpoints
    that move rows derive it from their own model and compare."""
    describe = lambda leaf, shape: (tuple(int(d) for d in shape),
                                    np.dtype(leaf.dtype))
    return _map_leaves(
        lambda leaf: describe(leaf, leaf.shape[1:time_axis(leaf)]), cache,
        state=lambda leaf: describe(leaf, leaf.shape[1:]))


# -- the call -----------------------------------------------------------------

def call_state(cache, index, counters=None, valid=None):
    """The state a forward pass reads (``apply(state=...)``): every entry
    of ``cache`` with this call's write position ``index`` and its request
    mask ``valid``, and every entry of ``counters`` with ``valid``."""
    state = {path: dict(entry, index=index, valid=valid)
             for path, entry in cache.items()}
    state.update({path: dict(entry, valid=valid)
                  for path, entry in (counters or {}).items()})
    return state


def split_state(state, counters=None):
    """``(cache, counters)`` out of the state a forward pass returned: the
    entries the call addressed, less their advanced ``index`` and the
    ``valid`` mask, and the entries at the paths of ``counters``.  Anything
    else a layer published (a training-time aux loss) is dropped."""
    strip = lambda entry: {k: v for k, v in entry.items()
                           if k not in ("index", "valid")}
    return ({path: strip(entry) for path, entry in state.items()
             if "index" in entry},
            {path: strip(state[path]) for path in counters or {}})


# -- the slot write -----------------------------------------------------------

def write_slot_rows(cache, rows, slot, present=None):
    """ONE request's batch-1 ``rows`` into slot ``slot`` of the pool
    ``cache``, every other slot untouched: the one way rows land in a pool,
    whether the engine prefilled them or a prefill rank sent them
    (serve/disagg.py).  ``rows`` may hold fewer columns than the pool (a
    bucket's worth lands at column 0); a state leaf lands entire, so
    nothing of the slot's last request is left.  The update is on the slot
    axis alone, so it is in place in a donated pool.

    A vector ``slot`` (P,) lands row ``i`` of batch-P ``rows`` in
    ``slot[i]`` (a prefill program of several prompts).  ``present`` (P,)
    bool marks the rows that are a request's (None: all); an absent row is
    never written.  The rows land in a loop a LAYER's entry over the
    present rows: the program's text does not grow with P x leaves (16
    rows of 48 layers written out tripled it and the set-up's lowering with
    it), and a layer's rows are dead once they have landed, where one loop
    at the end kept every layer's alive beside the pool (PERF.md section
    6, PR 48)."""
    slot = jnp.asarray(slot, jnp.int32)
    update = lambda leaf, new, at: jax.lax.dynamic_update_slice(
        leaf, new.astype(leaf.dtype), (at,) + (0,) * (leaf.ndim - 1))

    def land(entry, new):
        if slot.size == 1:
            return {name: update(leaf, new[name], slot.reshape(()))
                    for name, leaf in entry.items()}

        def body(k, entry):
            i = order[k]
            return {name: update(leaf, jax.lax.dynamic_slice_in_dim(
                new[name], i, 1, axis=0), slot[i])
                for name, leaf in entry.items()}
        return jax.lax.fori_loop(0, count, body, entry)

    if slot.size > 1:
        if present is None:
            present = jnp.ones(slot.shape, bool)
        order = jnp.argsort(~present, stable=True)  # a request's rows lead
        count = present.sum()
    with jax.named_scope("cache_write"):
        return {path: land(entry, rows[path]) for path, entry in cache.items()}
