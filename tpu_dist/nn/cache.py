"""The slot cache's format: what a layer keeps per slot, how a forward pass
addresses it, and how rows are written, cut and joined along time.

A cache (a pool of slots, or one request's batch-1 *rows*) is a tree
``{layer path: {name: leaf}}`` of RESIDENT leaves only: ``k``/``v``
``(B, H, D, Tmax)`` and, for the int8 cache, ``k_scale``/``v_scale``
``(B, H, Tmax)`` (:meth:`MultiheadSelfAttention.init_cache` builds them and
says why time is last).  What a single call adds travels beside it and is
put in and taken out HERE and nowhere else:

- ``index``, the call's write position, read by
  :meth:`MultiheadSelfAttention._decode` from its own entry: a scalar when
  every row writes at one position (a whole prompt), a ``(B,)`` vector for a
  slot step where each row stands at its own;
- the routed-row counters of the expert layers
  (:meth:`MoELayer.init_counters`), a tree of their own keyed by the
  ``MoELayer`` paths, with ``valid``, the call's mask of rows that are a
  request's, read by :meth:`MoELayer._count_rows`.

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

__all__ = ["time_axis", "time_slice", "extent", "pad_time", "join_time",
           "token_template", "call_state", "split_state", "write_slot_rows"]


# -- the layout ---------------------------------------------------------------

def time_axis(leaf) -> int:
    """The time axis of a resident leaf: the LAST one, for ``k``/``v`` and
    the int8 scales alike."""
    return leaf.ndim - 1


def time_slice(leaf, lo, hi):
    """Columns ``[lo, hi)`` of a leaf along its time axis (a view, clipped
    to the leaf's extent like any slice)."""
    idx = [slice(None)] * leaf.ndim
    idx[time_axis(leaf)] = slice(lo, hi)
    return leaf[tuple(idx)]


def extent(cache):
    """``(max_len, dtype)`` of a pool or row tree: the positions a slot
    holds and the type its K/V are stored in, what
    ``init_slot_cache(batch, max_len, dtype)`` was given."""
    k = next(iter(cache.values()))["k"]
    return k.shape[time_axis(k)], k.dtype


def _map_leaves(fn, tree):
    return {path: {name: fn(leaf) for name, leaf in entry.items()}
            for path, entry in tree.items()}


def pad_time(rows, total: int):
    """Host rows zero-padded along time to ``total`` columns (a bucket, or
    the whole ``max_len``): the fixed shape one compiled program takes."""
    def pad(leaf):
        width = [(0, 0)] * leaf.ndim
        width[time_axis(leaf)] = (0, total - leaf.shape[time_axis(leaf)])
        return np.pad(leaf, width)

    return _map_leaves(pad, rows)


def join_time(chain):
    """One host row tree from a chain of them, joined along time in order
    (a prefix-cache hit's blocks)."""
    return {path: {name: np.concatenate([rows[path][name] for rows in chain],
                                        axis=time_axis(leaf))
                   for name, leaf in entry.items()}
            for path, entry in chain[0].items()}


def token_template(cache):
    """``{path: {name: (per-token shape, dtype)}}``: each leaf's shape less
    its batch and time axes.  Two endpoints that move rows derive it from
    their own model and compare."""
    return _map_leaves(
        lambda leaf: (tuple(int(d) for d in leaf.shape[1:time_axis(leaf)]),
                      np.dtype(leaf.dtype)), cache)


# -- the call -----------------------------------------------------------------

def call_state(cache, index, counters=None, valid=None):
    """The state a forward pass reads (``apply(state=...)``): every entry
    of ``cache`` with this call's write position ``index``, and every entry
    of ``counters`` with the call's request mask ``valid``."""
    state = {path: dict(entry, index=index) for path, entry in cache.items()}
    state.update({path: dict(entry, valid=valid)
                  for path, entry in (counters or {}).items()})
    return state


def split_state(state, counters=None):
    """``(cache, counters)`` out of the state a forward pass returned: the
    entries the call addressed, less their advanced ``index``, and the
    entries at the paths of ``counters``.  Anything else a layer published
    (a training-time aux loss) is dropped."""
    strip = lambda entry, name: {k: v for k, v in entry.items() if k != name}
    return ({path: strip(entry, "index") for path, entry in state.items()
             if "index" in entry},
            {path: strip(state[path], "valid") for path in counters or {}})


# -- the slot write -----------------------------------------------------------

def write_slot_rows(cache, rows, slot):
    """ONE request's batch-1 ``rows`` into slot ``slot`` of the pool
    ``cache``, every other slot untouched: the one way rows land in a pool,
    whether the engine prefilled them or a prefill rank sent them
    (serve/disagg.py).  ``rows`` may hold fewer columns than the pool (a
    bucket's worth lands at column 0).  The update is on the slot axis
    alone, so it is in place in a donated pool."""
    slot = jnp.asarray(slot, jnp.int32)
    with jax.named_scope("cache_write"):
        return {path: {
            name: jax.lax.dynamic_update_slice(
                leaf, rows[path][name].astype(leaf.dtype),
                (slot,) + (0,) * (leaf.ndim - 1))
            for name, leaf in entry.items()} for path, entry in cache.items()}
