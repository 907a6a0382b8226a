"""Mixture-of-Experts layer — expert parallelism the GSPMD way.

No reference counterpart (SURVEY.md §2: the reference is data-parallel image
classifiers); this exists because tpu_dist treats the 'expert' mesh axis as
first-class alongside dp/tp/pp/sp, and the driver's multi-chip dry-run
exercises an ep sharding.

TPU-first design — routing as dense einsums, not gather/scatter:

- Expert FFN weights are **stacked** on a leading expert axis: ``w1 (E, d,
  h)``, ``w2 (E, h, d)``.  Under expert parallelism that axis is sharded
  ``P('expert')`` (see :data:`MOE_EP_RULES`) and every expert matmul is a
  batched einsum the MXU tiles directly.
- Token routing is the GShard/Switch capacity formulation: a cumsum
  position assignment gives every (choice, token) a slot at its chosen
  expert; tokens beyond an expert's capacity ``C = ceil(k*N/E *
  capacity_factor)`` are dropped — their combine weights are zero, so they
  pass through the surrounding residual unchanged.  Three
  dispatch realizations (``dispatch=``), numerically identical while
  nothing is dropped:

  * ``"einsum"`` — dense **dispatch/combine tensors** ``(N, E, C)`` built
    from one-hots: static shapes, no data-dependent indexing, and the XLA
    SPMD partitioner inserts the token all-to-alls purely from the
    shardings (einsum ``nec,nd->ecd`` with the output sharded over
    'expert' IS the dispatch all-to-all).  The GSPMD/expert-parallel
    default — but the ``(N, E, C)`` temps cost ``O(N*E*C*d)`` FLOPs and
    HBM, which at LM scale rivals the expert FFNs themselves.
  * ``"gather"`` — the routing is a partial permutation, so dispatch and
    combine are **row-gathers** by the slot maps; custom VJPs express both
    backward passes as gathers by the opposite map, so XLA never emits a
    data scatter.  ``O(k*N*d)`` — use for single-device and shard_map/DDP
    execution (layer internals are per-shard local there), where it is
    strictly cheaper; prefer ``"einsum"`` under a GSPMD 'expert' axis.
  * ``"dropless"`` — MegaBlocks-style: rows sorted by expert (the routing
    cumsum doubles as a counting sort — no argsort, which alone measures
    ~5 ms at 16k rows on v5e), each expert run over its exact contiguous
    segment by the grouped-matmul kernels (ops/gmm.py), segments padded
    only to the row-block size.  No capacity, no drops, and the output
    never depends on batch composition: the path to SERVE with, where a
    capacity cumsum over free slots and bucket padding would make one
    client's tokens depend on who shares the pool.  On the chip at OLMoE's
    shapes (64 experts of 2048 x 1024, 8 a token; PERF.md, PR 25) the
    grouped matmuls are bound by reading the experts' weights, not by the
    MXU: 1.5 ms a layer for a 1024-token prefill (128 rows an expert) and
    1.1 ms for a 32-slot decode step (4 rows an expert) against the
    0.98 ms that reading 806 MB takes; the row gathers around them
    (``dispatch``, ``combine``) add a quarter of that in prefill.  Against
    the capacity paths in training it has no measurement on this machine:
    the only record is a lead from before PR 1 at dim 768 with 8 experts
    (~0.6x the capacity path forward, 0.8x forward and backward, XLA's
    dense batched einsum over the padded (E, C, d) tensor beating the
    finer-grained kernels despite 1.25x the FLOPs).

- ``gated=True`` makes each expert ``down(silu(gate(x)) * up(x))`` without
  biases (``w1`` gate, ``w3`` up, ``w2`` down, the LLaMA-family names) in
  place of the two-matrix GELU MLP; every dispatch computes either.
- The router's logits are accumulated, scored and ranked in float32
  whatever the activations' type (top-k among 64 near-equal probabilities
  is not a bfloat16 decision).  Two scoring forms: ``scoring="softmax"``
  over all experts (OLMoE, Qwen3-Next), and ``scoring="sigmoid"`` of each
  logit by itself (DeepSeek-V3, Kimi K2), which ``selection_bias`` pairs
  with a learned per-expert ``router_bias`` added to the scores for the
  SELECTION alone (``topk_method: noaux_tc``): the k picks are the largest
  ``score + bias``, their weights the unbiased scores.
  ``normalize_gates=False`` uses the selected scores as they are (OLMoE's
  ``norm_topk_prob: false``); ``routed_scale`` multiplies the weights
  after that (``routed_scaling_factor``).
- ``experts_held`` / ``expert_offset`` give the layer ONE CHIP'S SHARE of
  an expert-parallel deployment: the router keeps its ``num_experts``
  outputs and its ``top_k`` a token (renormalised over all of them), the
  layer holds the weights of experts ``[offset, offset + held)`` and adds
  up the picks that fall on those; a pick of an absent expert is given no
  row and costs no matmul.  The shares of all chips add up to the whole
  layer (tests/test_qwen3_next.py); nothing here stands in for the absent
  chips or their exchange.  ``shared_hidden`` adds a shared expert,
  computed for every token, here (scope ``shared``); its sigmoid gate is
  optional (``shared_gate``: Qwen3-Next's has one, Kimi K2's none).
- Inside the layer ``jax.named_scope``s ``route``, ``dispatch``,
  ``experts`` and ``combine`` split a device trace (``python3 -m
  chipbench.scope_reduce``), and while serving the layer counts its routed
  rows per expert on the device (:meth:`MoELayer._count_rows`).
- The Switch **load-balancing auxiliary loss** ``E * sum_e f_e * p_e``
  (fraction of tokens routed to e times mean router probability of e) is
  published through the module-state mechanism (``state["aux_loss"]``):
  it is a traced value in ``new_state``, so a trainer that adds
  ``coeff * new_state[path]["aux_loss"]`` to its objective gets gradients
  through the router exactly as if the layer had returned it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .module import Module
from . import init as init_lib

from ..ops._pallas import ceil_to as _ceil_to, sublane_tile

__all__ = ["MoELayer", "experts_around_a_common_one"]


# -- gather dispatch: permutation as index maps, not one-hot einsums --------
#
# The GShard (N, E, C) dispatch/combine tensors cost O(N*E*C*d) FLOPs and
# HBM — at GPT-2-small MoE shapes that is comparable to the expert FFNs
# themselves and OOMs a 16G chip at per-chip batch 8.  But the routing is a
# (partial) permutation: each (choice, token) lands in at most one (expert,
# slot) cell.  So dispatch = one row-gather by the inverse map and combine =
# one row-gather by the forward map; both backward passes are *also* pure
# gathers (by the opposite map), which the custom VJPs below express so XLA
# never emits a data scatter.  The only scatter anywhere is the int32
# slot->choice inverse-map build (~0.1 ms at 32k tokens on v5e).  Integer
# index arguments take no gradient (None cotangents).

def _rows_or_zero(rows, index):
    """``rows[index]`` with zeros where ``index == len(rows)``, the sentinel
    of an empty slot or a dropped choice.  Appending the zero row copies the
    SOURCE, masking the gather's result is a pass over the RESULT: take the
    smaller.  On the chip at a 1024-token OLMoE prefill (PERF.md, PR 25)
    the dispatch (1,024 rows gathered into 16,384) took 0.23 ms padded and
    0.33 ms masked, and the combine's padded copy of its 16,384 x 2048
    source was a 0.09 ms operation of its own."""
    if rows.shape[0] < index.size:
        pad = jnp.concatenate(
            [rows, jnp.zeros((1,) + rows.shape[1:], rows.dtype)])
        return pad[index]
    return jnp.take(rows, index, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def _dispatch_rows(xt, token_for_slot, slot):
    """xt (N, d) -> xs_flat (E*C, d): row token_for_slot[s], zeros if == N."""
    return _rows_or_zero(xt, token_for_slot)


def _dispatch_rows_fwd(xt, token_for_slot, slot):
    return _dispatch_rows(xt, token_for_slot, slot), slot


def _dispatch_rows_bwd(slot, g):
    # grad_xt[i] = sum_j grad_xs[slot[j, i]]; dropped choices point past
    # the last row (slot == E*C) and read zeros
    return _rows_or_zero(g, slot).sum(0), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(out_flat, w, choice_for_slot, slot):
    """y (N, d) = sum_j w[j, i] * out_flat[slot[j, i]] (pad row = zeros).

    ``choice_for_slot`` (E*C,) is the inverse of ``slot``: the flattened
    (choice-major) index occupying each slot, k*N if empty — only the
    backward pass needs it, to invert the gy and w lookups as gathers.
    """
    g = _rows_or_zero(out_flat, slot)
    return (g * w[:, :, None].astype(g.dtype)).sum(0)


def _combine_rows_fwd(out_flat, w, choice_for_slot, slot):
    return (_combine_rows(out_flat, w, choice_for_slot, slot),
            (out_flat, w, choice_for_slot, slot))


def _combine_rows_bwd(res, gy):
    out_flat, w, choice_for_slot, slot = res
    k, n = slot.shape
    # grad_out[s] = w[choice(s)] * gy[token(s)]; empty slots read zeros in
    # both lookups (choice_for_slot == k*n -> token == n)
    token_for_slot = jnp.where(choice_for_slot == k * n, n,
                               choice_for_slot % jnp.int32(n))
    w_at_slot = _rows_or_zero(w.reshape(-1), choice_for_slot)
    g_out = (w_at_slot[:, None].astype(gy.dtype)
             * _rows_or_zero(gy, token_for_slot))
    # grad_w[j, i] = dot(gy[i], out_flat[slot[j, i]])
    g_rows = _rows_or_zero(out_flat, slot)
    g_w = (g_rows * gy[None, :, :].astype(g_rows.dtype)).sum(-1)
    return g_out, g_w.astype(w.dtype), None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# A row buffer much smaller than the picks of the call (a layer that holds a
# share of its experts) is combined by its ROWS, not by the picks: at most
# ``len(out_flat)`` rows carry anything, and ops/moe_combine.py walks them in
# token order.  Timed on the chip at the shapes of the cells that hold a
# share (PERF.md, PR 46): a buffer of at most half the picks gains 1.5 ms a
# layer at 40,960 picks (64 of 512 experts, a 4,096 prefill: 1.68 -> 0.34 ms)
# and 0.6 ms at 16,384 (12 of 384, a 2,048 prefill).  At 2,048 picks (Kimi
# Linear's 256 prefill) and at a 128-slot decode step's 1,024 the forms alone
# stand 0.02-0.04 ms a layer apart, which no cell's window shows: those calls
# keep the row gather, and the floor stands between 2,048 and 16,384.
_BY_TOKEN_MIN_PICKS = 4096


def _combines_by_token(m_rows: int, kn: int) -> bool:
    """Whether a call of ``kn`` picks over a buffer of ``m_rows`` takes
    :func:`_combine_held_rows` (both static)."""
    return 2 * m_rows <= kn and kn >= _BY_TOKEN_MIN_PICKS


@jax.custom_vjp
def _combine_held_rows(out_flat, w, choice_for_slot, slot):
    """:func:`_combine_rows`, the same function of ``out_flat`` and ``w``,
    by the buffer's rows in token order (ops/moe_combine.py): no ``(k, N,
    d)`` array, the sum accumulated in float32 and rounded once."""
    from ..ops.moe_combine import combine_by_token
    return combine_by_token(out_flat, w, slot)


def _combine_held_rows_fwd(out_flat, w, choice_for_slot, slot):
    return (_combine_held_rows(out_flat, w, choice_for_slot, slot),
            (out_flat, w, choice_for_slot, slot))


_combine_held_rows.defvjp(_combine_held_rows_fwd, _combine_rows_bwd)


def experts_around_a_common_one(experts: dict, key, fold: int,
                                deviation: float) -> dict:
    """Seeded weights for a benchmark, not a model's: a gated layer's routed
    experts (``w1``, ``w3``, ``w2`` of its parameters ``experts``, each ``(E,
    in, out)``) redrawn as ONE expert, U(+-1/sqrt(fan_in)) a matrix from
    ``key`` folded with ``fold``, ``fold + 1``, ``fold + 2``, plus
    ``deviation`` times the draw each came with.  Where every
    expert is held, bfloat16 and float32 decide a near-tie in a router's top
    k differently for about one token in a hundred a layer, and a swap of
    two INDEPENDENT experts moves that token's logits as far as a precision
    moves every token's; near-copies keep the comparison that decides
    ``correct`` on the arithmetic (models/xing4.py, models/lfm2_moe.py)."""
    out = dict(experts)
    for j, name in enumerate(("w1", "w3", "w2")):
        w = experts[name]
        common = init_lib.torch_default_uniform(
            jax.random.fold_in(key, fold + j), w.shape[1:], w.shape[1])
        out[name] = common + deviation * w
    return out


class MoELayer(Module):
    """Top-k routed mixture of expert FFNs (drop-in for a transformer MLP).

    Args:
        dim: model width.
        num_experts: E, the expert count (shard over 'expert' for ep).
        hidden: expert FFN hidden width (default ``4 * dim``).
        top_k: experts consulted per token (1 = Switch, 2 = GShard default).
        capacity_factor: slack multiplier on the perfectly-balanced
            per-expert token budget; tokens past capacity are dropped.
            NOTE dropping makes outputs depend on the BATCH COMPOSITION
            (slot competition is a cumsum over every token in the call),
            so e.g. KV-cache decode of a prefix will not bit-match the
            full-sequence forward while drops occur.  For serving, use
            ``capacity_factor >= num_experts / top_k`` — capacity then
            equals the token count, nothing drops, and cached decode
            equals the full forward exactly (tests/test_moe.py).
        normalize_gates: renormalize the k selected gate values to sum to 1
            (GShard semantics); off uses raw softmax probabilities (Switch).
        dispatch: ``"einsum"`` (GSPMD/ep-friendly dense dispatch tensors),
            ``"gather"`` (index-map permutation — cheaper for
            single-device / shard_map execution), or ``"dropless"``
            (sort-by-expert + grouped-matmul kernels, ops/gmm.py: no
            capacity, no drops, batch-composition-independent outputs —
            the one to serve with, see module docstring;
            ``capacity_factor`` is ignored).
        gated: each expert is ``down(silu(gate(x)) * up(x))`` without
            biases, ``hidden`` wide (parameters ``w1``, ``w3``, ``w2``),
            instead of ``w2(gelu(w1 x + b1)) + b2``.
        shared_hidden: width of a shared gated expert added to every
            token's output (parameters ``shared_w1``, ``shared_w3``,
            ``shared_w2``); 0 = none.
        shared_gate: the shared expert's output is multiplied by
            ``sigmoid(x w_s)`` (parameter ``shared_gate``); off adds it
            as it is.
        scoring: ``"softmax"`` over the router's logits or ``"sigmoid"``
            of each (module docstring).
        selection_bias: a per-expert ``router_bias`` is added to the
            scores to choose the ``top_k`` and left out of their weights.
        routed_scale: factor on the (normalised) weights of the picks.
        experts_held / expert_offset: the experts whose weights this
            layer holds, ``[offset, offset + held)`` of the router's
            ``num_experts`` (0 = all); dropless dispatch only.
    """

    def __init__(self, dim: int, num_experts: int, hidden: int = 0,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 normalize_gates: bool = True, dispatch: str = "einsum",
                 gated: bool = False, shared_hidden: int = 0,
                 experts_held: int = 0, expert_offset: int = 0,
                 shared_gate: bool = True, scoring: str = "softmax",
                 selection_bias: bool = False, routed_scale: float = 1.0):
        super().__init__()
        if num_experts < 2:
            raise ValueError(f"num_experts must be >= 2, got {num_experts}")
        if not 1 <= top_k <= num_experts:
            raise ValueError(f"top_k {top_k} not in [1, {num_experts}]")
        if dispatch not in ("einsum", "gather", "dropless"):
            raise ValueError(f"dispatch must be 'einsum', 'gather', or "
                             f"'dropless', got {dispatch!r}")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                             f"{scoring!r}")
        self.dim = dim
        self.num_experts = num_experts
        self.hidden = hidden or 4 * dim
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.normalize_gates = normalize_gates
        self.dispatch = dispatch
        self.gated = gated
        self.shared_hidden = shared_hidden
        self.shared_gate = shared_gate
        self.scoring = scoring
        self.selection_bias = selection_bias
        self.routed_scale = routed_scale
        self.experts_held = experts_held or num_experts
        self.expert_offset = expert_offset
        if self.experts_held != num_experts:
            if dispatch != "dropless":
                raise ValueError("a share of the experts is computed by the "
                                 "dropless dispatch alone")
            if not (0 <= expert_offset
                    and expert_offset + self.experts_held <= num_experts):
                raise ValueError(
                    f"experts [{expert_offset}, {expert_offset} + "
                    f"{self.experts_held}) are not among {num_experts}")

    def _shared_params(self, key):
        if not self.shared_hidden:
            return {}
        ks = jax.random.split(jax.random.fold_in(key, 7), 4)
        d, h = self.dim, self.shared_hidden
        p = {"shared_w1": init_lib.torch_default_uniform(ks[0], (d, h), d),
             "shared_w3": init_lib.torch_default_uniform(ks[1], (d, h), d),
             "shared_w2": init_lib.torch_default_uniform(ks[2], (h, d), h)}
        if self.shared_gate:
            p["shared_gate"] = init_lib.torch_default_uniform(ks[3], (d, 1),
                                                              d)
        return p

    def _router_params(self, key):
        """The router over ALL experts and, with ``selection_bias``, its
        per-expert bias.  Published code starts the bias at zero and loads
        the trained value, which exists to BALANCE the experts' load; drawn
        from the seed here, U(+-0.01): a unit-RMS input of width 7,168
        through this router gives a token's eight largest of 384 sigmoid
        scores 0.947-0.983, the eighth and ninth 0.004 apart, and this bias
        then swaps one of the eight picks for two tokens in three and never
        more than three, so that it changes the selection, a bias that
        leaked into the weights shows, and the load stays near the
        router's own (U(+-0.05) made the busiest expert's load 2.8 times
        the mean and a chip's held experts reached 8 to 10 of 12 a step by
        the seed: PERF.md, PR 32)."""
        p = {"router": init_lib.kaiming_uniform(
            key, (self.dim, self.num_experts))}
        if self.selection_bias:
            p["router_bias"] = init_lib.uniform(
                jax.random.fold_in(key, 11), (self.num_experts,), -0.01, 0.01)
        return p

    def create_params(self, key):
        kr, k1, k2 = jax.random.split(key, 3)
        # the router spans all experts, the weights those held here
        e, d, h = self.experts_held, self.dim, self.hidden

        def expert_uniform(k, shape, fan_in):
            # kaiming_uniform per expert: stacked (E, in, out) weights get
            # the same bound a (in, out) Linear would (init.calculate_fan
            # only knows 2-D/4-D shapes).  Beside a shared expert the
            # routed ones are drawn as IT is, U(+-1/sqrt(fan_in)), so that
            # one routed expert weighs what the shared one does (published
            # code draws both alike); at the kaiming bound a routed expert's
            # output is 6 ** 1.5 = 14.7 times the shared one's and the
            # swap of a token's least pick moves its logits more than the
            # arithmetic's precision does (PERF.md, PR 30)
            if self.shared_hidden:
                return init_lib.torch_default_uniform(k, shape, fan_in)
            bound = math.sqrt(6.0 / fan_in)
            return init_lib.uniform(k, shape, -bound, bound)

        if self.gated:
            # w1 = gate, w3 = up, w2 = down (the LLaMA-family names); no
            # biases, as every published gated expert has none
            return {
                **self._router_params(kr),
                "w1": expert_uniform(k1, (e, d, h), d),
                "w3": expert_uniform(jax.random.fold_in(key, 3), (e, d, h),
                                     d),
                "w2": expert_uniform(k2, (e, h, d), h),
                **self._shared_params(key),
            }
        return {
            **self._router_params(kr),
            "w1": expert_uniform(k1, (e, d, h), d),
            "b1": jnp.zeros((e, h)),
            "w2": expert_uniform(k2, (e, h, d), h),
            "b2": jnp.zeros((e, d)),
            **self._shared_params(key),
        }

    def create_state(self):
        return {"aux_loss": jnp.zeros(())}

    def _capacity(self, n_tokens: int) -> int:
        c = math.ceil(self.top_k * n_tokens / self.num_experts
                      * self.capacity_factor)
        # an expert can receive each token at most once (top-k experts are
        # distinct), so capacity beyond n_tokens only pads the einsums
        return max(1, min(c, n_tokens))

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        lead, d = x.shape[:-1], x.shape[-1]
        xt = x.reshape(-1, d)
        y = self._routed(ctx, p, xt)
        if self.shared_hidden:
            with jax.named_scope("shared"):
                out = (jax.nn.silu(xt @ p["shared_w1"])
                       * (xt @ p["shared_w3"])) @ p["shared_w2"]
                if self.shared_gate:
                    out = jax.nn.sigmoid(xt @ p["shared_gate"]) * out
                y = y + out
        return y.reshape(*lead, d)

    def _routed(self, ctx, p, xt):
        """The routed experts' part of the output for rows ``xt`` (N, d):
        the picks that fall on the experts held here."""
        e, k = self.experts_held, self.top_k
        n, d = xt.shape
        c = self._capacity(n)

        with jax.named_scope("route"):
            # router logits accumulate and are scored in float32 whatever
            # the activations' type: with 64 experts a bfloat16 softmax puts
            # near-ties among the top-k in the wrong order
            logits = jnp.dot(xt, p["router"],
                             preferred_element_type=jnp.float32)
            probs = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                     else jax.nn.softmax(logits, axis=-1))
            if self.selection_bias:
                # the bias chooses, the unbiased scores weigh
                _, gate_idx = lax.top_k(
                    probs + p["router_bias"].astype(jnp.float32), k)
                gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
            else:
                gate_vals, gate_idx = lax.top_k(probs, k)        # (N, k)
            if self.normalize_gates and k > 1:
                gate_vals = gate_vals / jnp.maximum(
                    gate_vals.sum(-1, keepdims=True), 1e-9)
            if self.routed_scale != 1.0:
                gate_vals = gate_vals * self.routed_scale
            gate_vals = gate_vals.astype(xt.dtype)

            # slot assignment: flatten the k choices in priority order (all
            # first choices, then all second choices, ...) and cumsum the
            # one-hots — each (choice, token) gets its arrival index at the
            # chosen expert; indices >= capacity are dropped.  Bookkeeping
            # runs in int32 no matter what xt's dtype is: a bf16 cumsum
            # rounds positions past 256 and mis-slots tokens.
            # Experts are numbered from the first one HELD: a pick of an
            # absent expert (index < 0 or >= e) has an all-zero one-hot
            # row, so it takes no position and counts for no expert.
            local_idx = gate_idx - self.expert_offset
            oh_i = jax.nn.one_hot(local_idx.T, e, dtype=jnp.int32)  # (k,N,E)
            flat = oh_i.reshape(k * n, e)
            pos = (jnp.cumsum(flat, axis=0) - flat)              # (k*N, E)
            pos = (pos * flat).sum(-1).reshape(k, n)             # (k, N)
            counts = oh_i.sum((0, 1))                            # (E,)
            if self.dispatch == "dropless":
                b = self._block_rows(k * n, xt.dtype)
                computed = (((counts + b - 1) // b) * b).sum()
                # the smallest buffer the rows fit takes the call
                # (_forward_dropless), and its size says which combine:
                # by its rows, all or the half the held picks fit
                from ..ops.moe_combine import gather_sizes
                combined = k * n
                for m_rows in reversed(self._buffer_sizes(k * n, b)[:-1]):
                    if _combines_by_token(m_rows, k * n):
                        half, whole = gather_sizes(m_rows)
                        combined = jnp.where(
                            computed <= m_rows,
                            jnp.where(counts.sum() <= half, half, whole),
                            combined)
            else:
                computed = e * c
                combined = k * n
            # serving counts its routed rows; training publishes the
            # load-balancing loss (the state entry holds one or the other)
            if not self._count_rows(ctx, gate_idx, oh_i, computed, combined):
                self._put_switch_aux(xt, probs.astype(xt.dtype), gate_idx)

        if self.dispatch == "dropless":
            # pos IS each row's stable within-expert rank — the same
            # cumsum doubles as a counting sort, so no argsort is needed
            # (measured ~5 ms for a 16k-row argsort on v5e, dwarfing the
            # expert matmuls themselves)
            held = (local_idx >= 0) & (local_idx < e)            # (N, k)
            return self._forward_dropless(p, xt, gate_vals, local_idx, held,
                                          pos, counts, b)

        keep = (pos < c).astype(xt.dtype)                        # (k, N)

        with jax.named_scope("dispatch"):
            if self.dispatch == "gather":
                # forward map: (choice, token) -> flat slot e*C + pos (trash
                # slot E*C for dropped); inverse map via one int32 scatter
                slot = jnp.where(keep > 0,
                                 gate_idx.T.astype(jnp.int32) * c + pos,
                                 e * c)                          # (k, N)
                choice_for_slot = (
                    jnp.full((e * c + 1,), k * n, jnp.int32)
                    .at[slot.reshape(-1)]
                    .set(jnp.arange(k * n, dtype=jnp.int32),
                         mode="drop")[:-1])
                token_for_slot = jnp.where(choice_for_slot == k * n, n,
                                           choice_for_slot % jnp.int32(n))
                xs = _dispatch_rows(xt, token_for_slot, slot).reshape(e, c, d)
                combine_t = None
            else:
                slot_oh = jax.nn.one_hot(pos, c, dtype=xt.dtype)  # (k, N, C)
                oh = oh_i.astype(xt.dtype)
                # (k, N, E, C) collapsed over k → dispatch/combine (N, E, C)
                dispatch_t = jnp.einsum("kne,knc,kn->nec", oh, slot_oh, keep)
                combine_t = jnp.einsum("kne,knc,kn->nec", oh, slot_oh,
                                       keep * gate_vals.T)
                xs = jnp.einsum("nec,nd->ecd", dispatch_t, xt)
        def linear(rows, w, bias):              # (E, C, in) -> (E, C, out)
            y = jnp.einsum("eci,eio->eco", rows, w)
            return y if bias is None else y + bias[:, None, :]

        out = self._experts(p, xs, linear)
        # dropped tokens have all-zero combine rows → output 0; the
        # surrounding residual connection passes them through unchanged
        with jax.named_scope("combine"):
            if self.dispatch == "gather":
                y = _combine_rows(out.reshape(e * c, d), keep * gate_vals.T,
                                  choice_for_slot, slot)
            else:
                y = jnp.einsum("nec,ecd->nd", combine_t, out)
        return y

    def _experts(self, p, xs, linear):
        """The expert FFN over rows already in expert order, ``linear(rows,
        w, bias)`` being the per-expert matmul of the dispatch at hand:
        ``w2(silu(w1 x) * w3 x)`` gated, ``w2 gelu(w1 x + b1) + b2`` not."""
        with jax.named_scope("experts"):
            if self.gated:
                hdn = (jax.nn.silu(linear(xs, p["w1"], None))
                       * linear(xs, p["w3"], None))
                return linear(hdn, p["w2"], None)
            return linear(jax.nn.gelu(linear(xs, p["w1"], p["b1"])),
                          p["w2"], p["b2"])

    def init_counters(self):
        """Routed-row counters for serving, this layer's entry of the
        counter tree (``TransformerLM.init_moe_counters``): ``rows`` (E,)
        picks per ROUTER expert that belong to a request (held or not),
        ``held_rows`` those of them that fell on an expert held here (all,
        for a layer that holds every expert), ``pad_rows`` picks that
        belong to no request (free slots in a decode step, bucket padding in
        a prefill), ``computed_rows`` the rows the expert matmuls ran over
        (every held pick, a request's or not, with each expert's segment
        rounded up to the row block), ``combined_rows`` the rows the combine
        gathered (every pick of the call by the picks; where the call is
        combined by its buffer's rows, :func:`_combines_by_token`, those in
        whole chunks, or half of them where the held picks fit the half),
        ``calls`` of the layer, and
        ``experts_hit``, the held experts with a request's row summed over
        calls.  int32: a reader takes differences modulo 2**32."""
        z = lambda *shape: jnp.zeros(shape, jnp.int32)
        return {"rows": z(self.num_experts), "held_rows": z(),
                "pad_rows": z(), "computed_rows": z(), "combined_rows": z(),
                "calls": z(), "experts_hit": z()}

    def _count_rows(self, ctx, gate_idx, oh_i, computed, combined) -> bool:
        """When this layer's state entry carries the counters
        (:meth:`init_counters`), add this call's routed rows to it, on the
        device.  ``valid`` (the rows that belong to a request; put into the
        entry by ``nn.cache.call_state``) keeps the rows of free slots and
        of bucket padding apart: they are routed and cost work, but are
        nobody's.  ``oh_i`` is the one-hot of the picks over the experts
        held.  True when counted (the training-time aux loss is then not
        published: the entry holds counters and nothing else)."""
        st = ctx.state.get(self._path) if ctx.state else None
        if st is None or "rows" not in st:
            return False
        k, n, _ = oh_i.shape
        valid = st["valid"].reshape(n).astype(jnp.int32)
        held = (oh_i.sum(0) * valid[:, None]).sum(0)             # (held,)
        if self.experts_held == self.num_experts:
            rows = held
        else:
            rows = (jax.nn.one_hot(gate_idx, self.num_experts,
                                   dtype=jnp.int32).sum(1)
                    * valid[:, None]).sum(0)
        n_valid = valid.sum()
        ctx.put_state(self._path, {
            "rows": st["rows"] + rows,
            "held_rows": st["held_rows"] + held.sum(),
            "pad_rows": st["pad_rows"] + k * (n - n_valid),
            "computed_rows": st["computed_rows"]
            + jnp.asarray(computed, jnp.int32),
            "combined_rows": st["combined_rows"]
            + jnp.asarray(combined, jnp.int32),
            "calls": st["calls"] + 1,
            "experts_hit": st["experts_hit"] + (held > 0).sum().astype(
                jnp.int32)})
        return True

    def _put_switch_aux(self, xt, probs, gate_idx):
        # Switch load-balance loss on first-choice assignments
        e = self.num_experts
        frac = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], e, dtype=xt.dtype),
                        axis=0)
        self._put_aux(e * jnp.sum(frac * probs.mean(0)))

    def _block_rows(self, kn: int, dtype) -> int:
        """The dropless path's row-block size for a call of ``kn`` picks:
        512 rows amortizes grid/DMA overhead at LM shapes; tiny calls
        (tests, dryrun, a decode step) shrink to the rows an expert expects,
        down to one sublane tile of the activations' dtype (16 rows of
        bf16)."""
        return min(512, _ceil_to(max(kn // self.num_experts, 1),
                                 sublane_tile(dtype)))

    def _forward_dropless(self, p, xt, gate_vals, gate_idx, held, rank,
                          counts, b):
        """Dropless expert compute: sort the (choice, token) rows by
        expert and run each expert over its exact segment with the
        grouped-matmul kernels (ops/gmm.py) — MegaBlocks-style.
        ``gate_idx`` (N, k) numbers the experts from the first one held,
        ``held`` marks the picks that fall on one, ``counts`` (E,) and
        ``rank`` (k, N) are the counting sort's, ``b`` the row block.

        No capacity, no drops: every routed row is processed, and the only
        padding is each segment's round-up to the row-block size (average
        E*B/2 rows ≈ a few percent at LM shapes, vs the capacity path's
        ``capacity_factor - 1`` ≈ 25% structural pad — the r4 verdict's
        remaining MoE cost).  Batch-composition independence comes free:
        unlike capacity slot competition, a token's output never depends
        on the other tokens in the call.

        The dispatch/combine row movements reuse the gather-path custom
        VJPs (_dispatch_rows/_combine_rows: both directions of both
        passes are gathers, never a data scatter); the per-expert FFN
        matmuls and all three of their backward passes are grouped
        matmuls over the same block→expert map (ops.gmm.grouped_linear).
        A buffer much smaller than the picks of the call (a share's usual
        and middle buffers in a prefill) is combined by ITS rows in token
        order (:func:`_combine_held_rows`, the same VJP), not by a row for
        every pick: :func:`_combines_by_token` chooses from the two sizes.

        A layer that holds a share of the experts expects a share of the
        picks, but MAY be sent all of them.  The row buffer is sized for
        twice the expected share and the call falls back, under
        ``lax.cond``, to the buffer that holds every pick when the rows do
        not fit: nothing is dropped, and the usual call does not pay the
        gathers, zero writes and grid steps of rows that hardly ever
        come.  On the chip, one layer that holds 64 of 512 experts over
        4,096 rows takes 2.78 ms under the ``cond`` and 5.20 ms with the
        worst-size buffer alone, 29 ms of a 178 ms prefill over 12 layers;
        over 96 rows 0.59 against 0.61 ms (PERF.md, PR 30).
        tests/test_qwen3_next.py sends every pick to held experts and
        takes the fallback.  Where the share is small (12 of 384: the
        buffer of every pick is ten times the usual one) buffers of four
        times the rows stand between the two, so that one expert most of a
        prompt picks costs a layer 1.4 ms and not 4.5 (PERF.md, PR 32).
        """
        k, n = self.top_k, xt.shape[0]
        padded = ((counts + b - 1) // b) * b
        cum_padded = jnp.cumsum(padded)
        rows = functools.partial(
            self._dropless_rows, p, xt, gate_vals, gate_idx, held, rank,
            counts, padded, cum_padded, b)

        def smallest(sizes):
            if len(sizes) == 1:
                return rows(sizes[0])
            return lax.cond(cum_padded[-1] <= sizes[0],
                            lambda: rows(sizes[0]),
                            lambda: smallest(sizes[1:]))
        return smallest(self._buffer_sizes(k * n, b))

    def _buffer_sizes(self, kn: int, b: int) -> list:
        """The row buffers a dropless call of ``kn`` picks may take, rising
        (:meth:`_forward_dropless`): twice the expected share, four times
        that while eight times still fits, and last the one that holds
        every pick on a held expert, which alone a layer that holds every
        expert has."""
        e = self.experts_held
        worst = (-(-kn // b) + e) * b
        usual = (-(-2 * kn * e // (self.num_experts * b)) + e) * b
        sizes = [usual]
        while 8 * sizes[-1] <= worst:
            sizes.append(4 * sizes[-1])
        return [m for m in sizes if m < worst] + [worst]

    def _dropless_rows(self, p, xt, gate_vals, gate_idx, held, rank, counts,
                       padded, cum_padded, b, m_rows):
        """:meth:`_forward_dropless` over a row buffer of ``m_rows`` (a
        static bound on the block-aligned rows of the call)."""
        from ..ops.gmm import grouped_linear

        e, k = self.experts_held, self.top_k
        n, d = xt.shape
        kn = k * n
        nb = m_rows // b

        with jax.named_scope("dispatch"):
            # destination row per (choice, token): its expert's
            # block-aligned segment start + its arrival rank there
            # (``rank`` is the routing cumsum from forward() — a stable
            # counting sort, no argsort); a pick of an absent expert
            # points past the last row and is given none
            pad_start = cum_padded - padded                      # (E,)
            slot = jnp.where(
                held.T, pad_start[jnp.clip(gate_idx.T, 0, e - 1)] + rank,
                m_rows).astype(jnp.int32)                        # (k, N)
            pos = slot.reshape(-1)                               # (k*N,)

            # the two inverse maps the gather VJPs need; pad rows point at
            # the sentinels (token n = zero row, choice k*n = dropped)
            flat_choice = jnp.arange(kn, dtype=jnp.int32)
            token_for_row = (jnp.full((m_rows,), n, jnp.int32)
                             .at[pos].set(flat_choice % n, mode="drop"))
            choice_for_row = (jnp.full((m_rows,), kn, jnp.int32)
                              .at[pos].set(flat_choice, mode="drop"))

            n_live = (cum_padded[-1] // b).astype(jnp.int32)
            # block -> expert map; overallocation-tail blocks get clamped
            # to E-1 (tgmm needs them to extend the final segment with
            # zero rows)
            bg = jnp.searchsorted(cum_padded,
                                  jnp.arange(nb, dtype=jnp.int32) * b,
                                  side="right")
            bg = jnp.minimum(bg, e - 1).astype(jnp.int32)
            present = counts > 0
            xs = _dispatch_rows(xt, token_for_row, slot)        # (M, d)
        # the kernels are named by the picks of the call, so a device
        # trace tells a 1024-token prefill's calls (gmm_r8192) from a
        # 32-slot decode step's (gmm_r256)
        def linear(rows, w, bias):
            return grouped_linear(rows, w, bias, bg, n_live, present, b, 512,
                                  f"gmm_r{kn}")

        out = self._experts(p, xs, linear)
        with jax.named_scope("combine"):
            combine = (_combine_held_rows if _combines_by_token(m_rows, kn)
                       else _combine_rows)
            return combine(out, gate_vals.T, choice_for_row, slot)

    def _put_aux(self, aux) -> None:
        from .module import current_context
        ctx = current_context()
        if ctx is not None and ctx.state is not None:
            ctx.put_state(self._path, {"aux_loss": aux})

    def __repr__(self):
        return (f"MoELayer({self.dim}, num_experts={self.num_experts}, "
                f"hidden={self.hidden}, top_k={self.top_k})")
