"""One logical-axis sharding-rule table driving every partition layout.

ROADMAP item 1: the host path (bucketer/ZeRO/rings/pipeline), the XLA mesh
path (fsdp/gspmd pjit specs), and tensor-parallel serving each grew their
own span math — three places where partition layouts could silently drift.
This module is the single source of truth they all derive from, following
veScale's eager-mode-consistent SPMD (PAPERS.md) and the portable
redistribution formulation of arXiv 2112.01075:

* a **rule table** maps logical axis names (``batch``/``heads``/``mlp``/
  ``vocab``/``embed``/...) to mesh dims — SNIPPETS [2]/[3]'s
  ``DEFAULT_RULES`` idiom (``{"heads": "model", ...}``);
* a **layout table** maps parameter paths to the logical factorization of
  each tensor dim (e.g. a fused qkv weight's columns are
  ``(qkv3, heads, head_dim)``);
* consumers bind the two:
  - :func:`spec_for` / :func:`partition_pairs` → ``PartitionSpec`` trees
    for pjit (``parallel/gspmd.py``, ``parallel/fsdp.py``);
  - :func:`spans_for` → contiguous flat element spans for the serving
    shards (``serve/sharded.py`` shard slicing and checkpoint
    range-reads);
  - :func:`chunk_bounds` / :func:`chunk_span` → the flat ZeRO/reshard
    chunk contract (``parallel/zero.py``, ``resilience/reshard.py``).

Changing only the rule table re-partitions every consumer coherently.

Everything here is pure layout arithmetic over numpy/ints — jax is
imported lazily and only when PartitionSpecs are requested, so the host
path (resilience, serving) never pays for it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["DEFAULT_RULES", "SERVING_RULES", "LeafLayout",
           "TRANSFORMER_LAYOUTS", "ShardLayoutError", "layout_for",
           "spec_for", "spec_for_key", "partition_pairs", "spans_for",
           "shard_leaf", "chunk_bounds", "chunk_span", "model_axes",
           "mapped_axes"]


class ShardLayoutError(ValueError):
    """A leaf cannot be laid out as asked: logical-axis size not divisible
    by the shard world, a dim factored by two different mesh axes, or a
    factorization that does not multiply out to the tensor's shape."""


# ---------------------------------------------------------------------------
# rule tables: logical axis -> mesh dim (None = replicated along that axis)
# ---------------------------------------------------------------------------

#: Training default — dp×tp on a ("data", "model") mesh.  ``batch`` rides
#: the data dim; attention heads, the MLP hidden width, and the vocab
#: (head/embedding) split over the model dim.  Megatron column/row pairing
#: falls out of the layout table below: qkv/up are column-parallel, out/
#: down are row-parallel with partial-sum outputs.
DEFAULT_RULES: Dict[str, Optional[str]] = {
    "batch": "data",
    "seq": None,
    "embed": None,
    "qkv3": None,
    "heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    # expert parallelism is its own binding (gspmd.MOE_EP_RULES): the
    # default dp×tp table leaves expert banks replicated so dense and MoE
    # models shard identically under it
    "expert": None,
}

#: Serving binding (serve/sharded.py): the shard gang splits heads and the
#: MLP hidden width only — head/tok stay full on every rank (lockstep
#: sampling needs full logits, and the decode hot path is attention/MLP).
SERVING_RULES: Dict[str, Optional[str]] = {
    "batch": None,
    "seq": None,
    "embed": None,
    "qkv3": None,
    "heads": "shard",
    "head_dim": None,
    "mlp": "shard",
    "vocab": None,
    "expert": None,
}


# ---------------------------------------------------------------------------
# layout table: parameter path -> per-dim logical factorization
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LeafLayout:
    """Logical factorization of one parameter tensor.

    ``dims``: for each tensor dim (leading; trailing dims default to
    unfactored/replicated), the tuple of logical axis names whose sizes
    multiply to that dim — row-major, so ``("qkv3", "heads", "head_dim")``
    describes the fused qkv column layout ``[3][H][hd]``.

    ``partial_axis``: set on row-parallel output biases (attn out_bias,
    mlp down bias).  When the named axis is sharded, the matmul feeding
    this bias produces rank-partial sums; the bias must be added exactly
    once.  :func:`spans_for` gives it to shard 0 alone (serving's
    convention: the combine's sum then holds it once); the pjit specs
    replicate it and XLA adds it after the psum."""

    dims: Tuple[Tuple[str, ...], ...]
    partial_axis: Optional[str] = None


#: (path regex, name regex, layout) — first fullmatch wins.  Paths are the
#: module paths of tpu_dist.models.transformer.TransformerLM; the MoE row
#: covers parallel.gspmd's expert-parallel binding.
TRANSFORMER_LAYOUTS: Tuple[Tuple[str, str, LeafLayout], ...] = (
    (r"block\d+\.attn", r"qkv_weight",
     LeafLayout((("embed",), ("qkv3", "heads", "head_dim")))),
    (r"block\d+\.attn", r"qkv_bias",
     LeafLayout((("qkv3", "heads", "head_dim"),))),
    (r"block\d+\.attn", r"out_weight",
     LeafLayout((("heads", "head_dim"), ("embed",)))),
    (r"block\d+\.attn", r"out_bias",
     LeafLayout((("embed",),), partial_axis="heads")),
    (r"block\d+\.mlp\.0", r"weight", LeafLayout((("embed",), ("mlp",)))),
    (r"block\d+\.mlp\.0", r"bias", LeafLayout((("mlp",),))),
    (r"block\d+\.mlp\.2", r"weight", LeafLayout((("mlp",), ("embed",)))),
    (r"block\d+\.mlp\.2", r"bias",
     LeafLayout((("embed",),), partial_axis="mlp")),
    (r"head", r"weight", LeafLayout((("embed",), ("vocab",)))),
    (r"head", r"bias", LeafLayout((("vocab",),))),
    (r"tok", r"weight", LeafLayout((("vocab",), ("embed",)))),
    (r"pos", r"weight", LeafLayout((("seq",), ("embed",)))),
    # MoE expert banks (nn.moe): leading dim is the expert bank
    (r"block\d+\.mlp", r"[wb][123]", LeafLayout((("expert",),))),
)


def layout_for(path: str, name: str,
               table: Sequence[Tuple[str, str, LeafLayout]] = None
               ) -> Optional[LeafLayout]:
    """First layout row whose (path, name) regexes fullmatch, else None
    (= unfactored: replicated under every rule binding)."""
    for ppat, npat, lay in (TRANSFORMER_LAYOUTS if table is None else table):
        if re.fullmatch(ppat, path) and re.fullmatch(npat, name):
            return lay
    return None


def mapped_axes(rules: Dict[str, Optional[str]], mesh_axis: str
                ) -> Tuple[str, ...]:
    """Logical axes the rule table binds to ``mesh_axis``."""
    return tuple(a for a, m in rules.items() if m == mesh_axis)


# ---------------------------------------------------------------------------
# pjit specs
# ---------------------------------------------------------------------------

def _dim_mesh_axis(factors: Tuple[str, ...],
                   rules: Dict[str, Optional[str]]) -> Optional[str]:
    mapped = [rules.get(f) for f in factors if rules.get(f) is not None]
    if len(set(mapped)) > 1:
        raise ShardLayoutError(
            f"dim factored as {factors} maps to multiple mesh axes "
            f"{sorted(set(mapped))} — a tensor dim shards along at most one")
    return mapped[0] if mapped else None


def spec_for(path: str, name: str, rules: Dict[str, Optional[str]] = None,
             table: Sequence[Tuple[str, str, LeafLayout]] = None):
    """``PartitionSpec`` for one parameter under a rule binding.  Trailing
    replicated dims are trimmed, so fully-replicated leaves give ``P()``
    (the same spec an unmatched leaf gets from ``PartitionRules``)."""
    from jax.sharding import PartitionSpec as P
    if rules is None:
        rules = DEFAULT_RULES
    lay = layout_for(path, name, table)
    if lay is None:
        return P()
    entries = [_dim_mesh_axis(factors, rules) for factors in lay.dims]
    if not any(e is not None for e in entries):
        return P()  # fully replicated — the unmatched-leaf default
    return P(*entries)


_KEY_RE = re.compile(r"^\['([^']+)'\]\['([^']+)'\]$")


def spec_for_key(keystr: str, rules: Dict[str, Optional[str]] = None,
                 table: Sequence[Tuple[str, str, LeafLayout]] = None):
    """:func:`spec_for` addressed by a jax ``keystr`` path like
    ``['block0.attn']['qkv_weight']`` (the form gspmd's rule regexes
    match against)."""
    from jax.sharding import PartitionSpec as P
    m = _KEY_RE.match(keystr)
    if m is None:
        return P()
    return spec_for(m.group(1), m.group(2), rules, table)


def partition_pairs(rules: Dict[str, Optional[str]] = None,
                    table: Sequence[Tuple[str, str, LeafLayout]] = None
                    ) -> List[Tuple[str, object]]:
    """Derive ``(keystr regex, PartitionSpec)`` pairs for
    :class:`parallel.gspmd.PartitionRules` from the layout + rule tables.
    Rows that come out fully replicated are dropped (the PartitionRules
    default already answers ``P()`` for unmatched leaves)."""
    from jax.sharding import PartitionSpec as P
    if rules is None:
        rules = DEFAULT_RULES
    pairs = []
    for ppat, npat, lay in (TRANSFORMER_LAYOUTS if table is None else table):
        entries = [_dim_mesh_axis(factors, rules) for factors in lay.dims]
        if not any(e is not None for e in entries):
            continue  # replicated — PartitionRules' default

        pairs.append((r"\['" + ppat + r"'\]\['" + npat + r"'\]",
                      P(*entries)))
    return pairs


# ---------------------------------------------------------------------------
# host-path spans (the serving shards' slices)
# ---------------------------------------------------------------------------

def _find_sharded(lay: LeafLayout, rules: Dict[str, Optional[str]],
                  mesh_axis: str) -> Optional[Tuple[int, int]]:
    """(dim index, factor index) of the factor riding ``mesh_axis``."""
    hits = []
    for d, factors in enumerate(lay.dims):
        for j, f in enumerate(factors):
            if rules.get(f) == mesh_axis:
                hits.append((d, j))
    if len(hits) > 1:
        raise ShardLayoutError(
            f"layout {lay.dims} maps {len(hits)} factors to mesh axis "
            f"{mesh_axis!r} — host-path sharding splits exactly one")
    return hits[0] if hits else None


def _full(shape: Tuple[int, ...]):
    return [(0, int(np.prod(shape, dtype=np.int64)) if shape else 1)], shape


def spans_for(path: str, name: str, shape: Tuple[int, ...],
              axes: Dict[str, int], rank: int, world: int,
              rules: Dict[str, Optional[str]] = None,
              mesh_axis: str = "model",
              table: Sequence[Tuple[str, str, LeafLayout]] = None
              ) -> Optional[Tuple[List[Tuple[int, int]], Tuple[int, ...]]]:
    """``(contiguous flat element spans, local shape)`` of shard ``rank``'s
    slice of a parameter, or None when this rank holds nothing (a
    partial-sum bias whose controlling axis is sharded, on rank > 0:
    rank 0 owns the full bias, so the combine's sum holds it once).

    ``axes`` gives the logical axis sizes (:func:`model_axes`).  Every
    span is contiguous in the flat row-major layout — what lets both
    in-memory slicing and checkpoint range-reads assemble identical
    shards (serve/sharded.py's contract, now generalized)."""
    if rules is None:
        rules = DEFAULT_RULES
    lay = layout_for(path, name, table)
    if lay is None:
        return _full(shape)
    if lay.partial_axis is not None and rules.get(lay.partial_axis) \
            == mesh_axis and world > 1:
        return _full(shape) if rank == 0 else None
    sh = _find_sharded(lay, rules, mesh_axis)
    if sh is None:
        return _full(shape)
    d, j = sh
    factors = lay.dims[d]
    try:
        sizes = [int(axes[f]) for f in factors]
    except KeyError as e:
        raise ShardLayoutError(
            f"axis size for {e.args[0]!r} missing (leaf {path}.{name}); "
            f"pass it in `axes` (see model_axes)") from None
    if d >= len(shape) or int(np.prod(sizes, dtype=np.int64)) != shape[d]:
        raise ShardLayoutError(
            f"leaf {path}.{name} dim {d} is {shape[d] if d < len(shape) else None}, "
            f"but factors {factors} multiply to {sizes}")
    nj = sizes[j]
    if nj % world:
        raise ShardLayoutError(
            f"logical axis {factors[j]!r} of size {nj} not divisible by "
            f"shard world {world} (leaf {path}.{name})")
    chunk = nj // world
    start = rank * chunk
    outer = int(np.prod(shape[:d], dtype=np.int64)) * \
        int(np.prod(sizes[:j], dtype=np.int64))
    inner = int(np.prod(sizes[j + 1:], dtype=np.int64)) * \
        int(np.prod(shape[d + 1:], dtype=np.int64))
    spans = [(o * nj * inner + start * inner,
              o * nj * inner + (start + chunk) * inner)
             for o in range(outer)]
    out_shape = shape[:d] + (shape[d] // world,) + shape[d + 1:]
    return spans, out_shape


def shard_leaf(arr: np.ndarray, plan) -> Optional[np.ndarray]:
    """Materialize one shard from a :func:`spans_for` plan (None passes
    through: the rank holds nothing)."""
    if plan is None:
        return None
    spans, out_shape = plan
    flat = np.ascontiguousarray(arr).reshape(-1)
    if len(spans) == 1:
        lo, hi = spans[0]
        return flat[lo:hi].reshape(out_shape).copy()
    return np.concatenate([flat[lo:hi] for lo, hi in spans]
                          ).reshape(out_shape)


# ---------------------------------------------------------------------------
# flat chunk bounds — the ZeRO / reshard contract
# ---------------------------------------------------------------------------

def chunk_bounds(n_elems: int, world: int) -> List[Tuple[int, int]]:
    """Per-rank [lo, hi) bounds of a flat buffer split into ``world``
    near-equal contiguous chunks — THE layout contract shared by the ring
    reduce-scatter, ZeroOptimizer shards, and reshard manifests (the
    first ``n_elems % world`` chunks get one extra element).  Delegates
    to the ring implementation so existing sharded checkpoints stay
    bitwise-compatible by construction."""
    from ..collectives.ring import _bounds
    return _bounds(n_elems, world)


def chunk_span(n_elems: int, world: int, rank: int) -> Tuple[int, int]:
    """Rank's own [lo, hi) from :func:`chunk_bounds`."""
    from ..collectives.ring import ring_chunk_span
    return ring_chunk_span(n_elems, world, rank)


# ---------------------------------------------------------------------------
# logical axis sizes
# ---------------------------------------------------------------------------

def model_axes(model) -> Dict[str, int]:
    """Logical axis sizes of a ``TransformerLM``-shaped model, keyed by
    the names the layout table uses.  Probes the modules (block0.attn,
    head) rather than constructor args so quantized/subclassed variants
    answer too."""
    axes: Dict[str, int] = {"qkv3": 3}
    attn = getattr(getattr(model, "block0", None), "attn", None)
    if attn is not None:
        axes["embed"] = attn.embed_dim
        axes["heads"] = attn.num_heads
        axes["head_dim"] = attn.head_dim
    mlp = getattr(getattr(model, "block0", None), "mlp", None)
    try:
        up = mlp[0] if mlp is not None else None
    except (TypeError, IndexError, KeyError):
        up = None
    if up is not None and hasattr(up, "out_features"):
        axes["mlp"] = up.out_features
    head = getattr(model, "head", None)
    if head is not None and hasattr(head, "out_features"):
        axes["vocab"] = head.out_features
    pos = getattr(model, "pos", None)
    if pos is not None and hasattr(pos, "num_embeddings"):
        axes["seq"] = pos.num_embeddings
    if mlp is not None and hasattr(mlp, "num_experts"):
        axes["expert"] = mlp.num_experts
    return axes
