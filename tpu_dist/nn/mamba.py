"""Mamba-2 — a state-space token mixer (Dao & Gu 2024, "Transformers are
SSMs", the SSD layer; the ``mamba`` half of every Falcon-H1 layer, HF
``modeling_falcon_h1.py``), whose cache is a fixed-size state like the
delta-rule layers' (nn/deltanet.py) and whose recurrence has NO delta term.

Per head a layer keeps a ``(P, N)`` float32 state ``S`` (head size by state
size, zero at a request's start) and reads one token as::

    S <- exp(dt_t A) S + dt_t x_t (x) B_t;    y_t = S C_t + D x_t

with ``x_t`` (P,) the head's input, ``B_t``, ``C_t`` (N,) shared by the
``H / G`` consecutive heads of a group, ``dt_t = softplus(dt + dt_bias)`` a
number a head a token, ``A = -exp(A_log)`` and ``D`` a number a head, all in
float32.  ``[x | B | C]`` first pass ONE causal depthwise convolution of
width ``K`` with a bias, and SiLU, so a layer also keeps the last ``K - 1``
inputs of that convolution, its ``conv`` tail.  The output is ``y *
silu(z)`` (``z`` a full-rank projection of the input) under an RMSNorm over
each GROUP's ``H P / G`` numbers.  The cost of a token does not grow with
the context.

Two forms of the same recurrence, both plain ``jax.numpy``:

- :func:`ssd_step`, ONE token a row (a decode step over the slot pool): the
  decay, the rank-one update and the contraction with ``C`` are elementwise
  passes and a reduction over the state in float32, one fusion that reads a
  slot's state and writes it;
- :func:`ssd_chunked`, a whole prompt in chunks of ``chunk`` positions (the
  published ``mamba_chunk_size``; the SSD form): inside a chunk the output
  is a masked, decayed ``(C B^T) (dt x)`` product over all chunks at once,
  and only the state's carry from chunk to chunk is sequential.  The decay
  is a number a head, so a chunk's pairwise decays ``exp(L_i - L_j)`` are
  one ``(chunk, chunk)`` matrix a head, masked BEFORE the exponential
  (above the diagonal the difference is positive).

**Positions that are nobody's** (bucket padding in a prefill, a free slot's
row in a decode step; ``valid`` false in the layer's cache entry,
nn/cache.py) are made a no-op of the recurrence, ``dt = 0``: no decay and no
update, exactly as the delta-rule layers do with ``g = 0`` and ``beta = 0``,
and the convolution's tail is taken at the prompt's true length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import functional as F
from . import init as I
from .deltanet import _CHUNK_PRECISION, _dt_bias
from .module import Module
from .shortconv import advanced, causal_conv, conv_tail, valid_positions

__all__ = ["Mamba2", "ssd_step", "ssd_chunked"]


def _by_group(a, groups: int, axis: int):
    """The head axis ``axis`` of ``a`` split ``(G, H / G)``: group ``g``
    holds heads ``[g H / G, (g + 1) H / G)``."""
    shape = a.shape
    return a.reshape(*shape[:axis], groups, shape[axis] // groups,
                     *shape[axis + 1:])


def ssd_step(state, x, dt, a, b, c, d):
    """One token a row.  ``state`` (B, H, P, N) float32; ``x`` (B, H, P);
    ``dt`` (B, H), >= 0; ``a`` (H,), < 0; ``b``, ``c`` (B, G, N), group
    ``g`` serving heads ``[g H / G, (g + 1) H / G)``; ``d`` (H,); all
    float32.  Returns ``(y (B, H, P), new state)``.  A row with ``dt = 0``
    leaves its state as it was."""
    groups = b.shape[1]
    s = _by_group(state, groups, 1)                      # (B, G, Hg, P, N)
    decay = _by_group(jnp.exp(dt * a), groups, 1)[..., None, None]
    s = s * decay + (_by_group(dt[..., None] * x, groups, 1)[..., None]
                     * b[:, :, None, None, :])
    # a reduction over the state where it lies, in float32: no matmul unit's
    # rounding, and one pass with the update above
    y = jnp.sum(s * c[:, :, None, None, :], -1).reshape(x.shape)
    return y + d[:, None] * x, s.reshape(state.shape)


def ssd_chunked(state, x, dt, a, b, c, d, chunk: int = 128):
    """A sequence, chunk by chunk.  ``state`` (B, H, P, N) float32; ``x``
    (B, T, H, P); ``dt`` (B, T, H), >= 0; ``a`` (H,), < 0; ``b``, ``c`` (B,
    T, G, N); ``d`` (H,); all float32, any T (padded here to whole chunks
    with no-op positions, ``dt = 0``).  Returns ``(y (B, T, H, P), state
    after T)``."""
    bsz, t, h, p = x.shape
    groups = b.shape[2]
    pad = -t % chunk
    if pad:
        widen = lambda m: jnp.pad(m, [(0, 0), (0, pad)]
                                  + [(0, 0)] * (m.ndim - 2))
        x_in, dt, b, c = (widen(m) for m in (x, dt, b, c))
    else:
        x_in = x
    n = (t + pad) // chunk
    mm = lambda eq, *ops: jnp.einsum(eq, *ops, precision=_CHUNK_PRECISION)
    split = lambda m: m.reshape(bsz, n, chunk, *m.shape[2:])
    # (B, n, L, G, Hg, P): what a position adds to its head's state, less B
    xdt = _by_group(split(x_in * dt[..., None]), groups, 3)
    b, c = split(b), split(c)                             # (B, n, L, G, N)
    # the log decay summed within the chunk, heads before positions
    cum = jnp.moveaxis(jnp.cumsum(split(dt * a), axis=2), 2, -1)  # (B,n,H,L)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # decay from position j to position i >= j of the same chunk
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                   # (B, n, H, L, L)
    scores = mm("bnigm,bnjgm->bngij", c, b)[:, :, :, None]  # one a GROUP
    within = mm("bngeij,bnjgep->bnigep",
                scores * _by_group(decay, groups, 2), xdt)
    # what the chunk's own positions leave in the state at its end, what the
    # whole chunk decays the state it starts from by, and what a position
    # reads of that state
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)   # (B,n,L,H)
    added = mm("bnjgep,bnjgm->bngepm",
               xdt * _by_group(to_end, groups, 3)[..., None], b)
    chunk_decay = _by_group(jnp.exp(cum[..., -1]), groups, 2)    # (B,n,G,Hg)
    from_start = _by_group(jnp.moveaxis(jnp.exp(cum), -1, 2), groups, 3)

    def carry(s, xs):
        added_i, decay_i, c_i, from_start_i = xs
        read = mm("bigm,bgepm->bigep", c_i, s) * from_start_i[..., None]
        return s * decay_i[..., None, None] + added_i, read

    over_chunks = lambda m: jnp.moveaxis(m, 1, 0)
    s, carried = lax.scan(carry, _by_group(state, groups, 1), tuple(map(
        over_chunks, (added, chunk_decay, c, from_start))))
    y = (within + jnp.moveaxis(carried, 0, 1)).reshape(bsz, n * chunk, h, p)
    return y[:, :t] + d[:, None] * x, s.reshape(state.shape)


class Mamba2(Module):
    """The Mamba-2 token mixer (drop-in for a block's attention, or one
    branch of a :class:`~tpu_dist.nn.ParallelMixer`).

    Args:
        dim: model width.
        num_heads / head_dim: ``H`` heads of ``P`` numbers; ``H P`` is the
            mixer's inner width (the published ``mamba_d_ssm``).
        state_dim: ``N``, the state's size a head-channel.
        num_groups: ``G``; ``B`` and ``C`` are projected a group and shared
            by its ``H / G`` consecutive heads, and the gated norm is over
            each group's ``H P / G`` numbers.
        conv_kernel: width of the causal depthwise convolution over ``[x |
            B | C]``.
        chunk_size: positions a chunk of the prefill's scan holds.
        eps: of the gated output norm.
        multipliers: five constants that scale the input projection's
            segments ``[z | x | B | C | dt]`` (Falcon-H1's
            ``ssm_multipliers``); ones are no operation.

    Parameters: ``in_weight`` ``(dim, 2 H P + 2 G N + H)`` split ``[z | x |
    B | C | dt]``, ``conv_weight`` ``(H P + 2 G N, K)`` (tap ``K - 1`` is
    the current position's) and ``conv_bias``, ``A_log``, ``dt_bias``, ``D``
    ``(H,)``, ``norm_weight`` ``(H P,)`` and ``out_weight`` ``(H P, dim)``;
    no other bias.  ``A_log`` is the log of U(1, 16), ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 0.1] and ``D`` one (the
    published initialisers), so at initialisation ``exp(dt A)`` spans short
    and long memories.

    Served through a slot cache the layer keeps per slot
    (:meth:`init_cache`) ``state`` and ``conv``, both replaced entire at
    every call; called without a cache (a plain forward) it starts every
    sequence from the zero state.
    """

    def __init__(self, dim: int, num_heads: int, head_dim: int,
                 state_dim: int, num_groups: int = 1, conv_kernel: int = 4,
                 chunk_size: int = 128, eps: float = 1e-5,
                 multipliers=(1.0, 1.0, 1.0, 1.0, 1.0)):
        super().__init__()
        if num_heads % num_groups:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_groups {num_groups}")
        if len(multipliers) != 5:
            raise ValueError(f"multipliers are five, one a segment of "
                             f"[z | x | B | C | dt], got {multipliers!r}")
        self.dim = dim
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_dim, self.num_groups = state_dim, num_groups
        self.inner_dim = num_heads * head_dim
        self.bc_dim = num_groups * state_dim
        self.conv_dim = self.inner_dim + 2 * self.bc_dim
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.eps = eps
        self.multipliers = tuple(float(m) for m in multipliers)
        #: the multipliers spread over the projection's columns; None for
        #: all ones, no operation
        self._column_scale = None if set(self.multipliers) == {1.0} else (
            np.repeat(np.float32(self.multipliers), self._segments))

    #: a layer of whole state reads no resident position
    attend_flops_per_position = 0

    @property
    def _segments(self) -> tuple:
        """Widths of ``[z | x | B | C | dt]`` in the input projection."""
        return (self.inner_dim, self.inner_dim, self.bc_dim, self.bc_dim,
                self.num_heads)

    def takes_step_kernel(self, entry) -> bool:
        """No Pallas kernel computes this update (tpu_dist.ops.delta_step's
        tile is a delta rule's): always :func:`ssd_step`.  Answered so that
        the model counts this layer among those that keep a whole state
        (``TransformerLM.slot_state_kernel``)."""
        return False

    def takes_scan_kernel(self, entry, t: int) -> bool:
        """No Pallas kernel computes this scan: always
        :func:`ssd_chunked` (``TransformerLM.prefill_scan_kernel``)."""
        return False

    @property
    def state_flops_per_row(self) -> int:
        """Operations ONE row's one-token update costs this layer's ``H``
        ``(P, N)`` states: the decay (P N), the rank-one update (2 P N) and
        the contraction with ``C`` (2 P N)."""
        return 5 * self.num_heads * self.head_dim * self.state_dim

    def create_params(self, key):
        ks = jax.random.split(key, 6)
        h = self.num_heads
        return {
            "in_weight": I.torch_default_uniform(
                ks[0], (self.dim, sum(self._segments)), self.dim),
            "conv_weight": I.torch_default_uniform(
                ks[1], (self.conv_dim, self.conv_kernel), self.conv_kernel),
            "conv_bias": I.torch_default_uniform(
                ks[2], (self.conv_dim,), self.conv_kernel),
            "A_log": jnp.log(jax.random.uniform(ks[3], (h,), minval=1.0,
                                                maxval=16.0)),
            "dt_bias": _dt_bias(ks[4], (h,)),
            "D": jnp.ones((h,)),
            "norm_weight": jnp.ones((self.inner_dim,)),
            "out_weight": I.torch_default_uniform(
                ks[5], (self.inner_dim, self.dim), self.inner_dim),
        }

    def init_cache(self, batch: int, max_len: int = 0, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py
        tree): ``state`` ``(B, H, P, N)``, float32 whatever ``dtype`` (the
        recurrence accumulates a whole context into it), and ``conv`` ``(B,
        (K - 1) * C)`` in ``dtype``, the convolution's last ``K - 1``
        inputs, oldest first, flattened as
        :meth:`GatedDeltaNet.init_cache` flattens its one.  Neither has a
        time axis: ``max_len`` does not size them."""
        return {"state": jnp.zeros((batch, self.num_heads, self.head_dim,
                                    self.state_dim), jnp.float32),
                "conv": jnp.zeros((batch, (self.conv_kernel - 1)
                                   * self.conv_dim), dtype)}

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        st = (ctx.get_state(self._path)
              if ctx.state is not None and self._path in ctx.state else None)
        b, t, _ = x.shape
        h, g, taps = self.num_heads, self.num_groups, self.conv_kernel - 1
        f32 = lambda m: m.astype(jnp.float32)
        with jax.named_scope("in_proj"):
            proj = F.linear(x, p["in_weight"])
            if self._column_scale is not None:
                proj = proj * jnp.asarray(self._column_scale, proj.dtype)
        z, mixed, dt = jnp.split(
            proj, [self.inner_dim, self.inner_dim + self.conv_dim], axis=-1)
        valid = valid_positions(st, b, t)

        with jax.named_scope("conv"):
            tail = conv_tail(st, "conv", jax.ShapeDtypeStruct(
                (b, taps, self.conv_dim), mixed.dtype))
            mixed, new_tail = causal_conv(mixed, tail, p["conv_weight"],
                                          valid, p["conv_bias"])

        xs, bm, cm = jnp.split(
            mixed, [self.inner_dim, self.inner_dim + self.bc_dim], axis=-1)
        xs = f32(xs.reshape(b, t, h, self.head_dim))
        bm, cm = (f32(m.reshape(b, t, g, self.state_dim)) for m in (bm, cm))
        # nobody's positions: dt = 0, the recurrence's no-op
        dt = jnp.where(valid[..., None],
                       jax.nn.softplus(f32(dt) + f32(p["dt_bias"])), 0.0)
        a, d = -jnp.exp(f32(p["A_log"])), f32(p["D"])
        state = (jnp.zeros((b, h, self.head_dim, self.state_dim),
                           jnp.float32) if st is None else st["state"])
        if t == 1:
            with jax.named_scope("state_update"):
                y, state = ssd_step(state, xs[:, 0], dt[:, 0], a, bm[:, 0],
                                    cm[:, 0], d)
                y = y[:, None]
        else:
            with jax.named_scope("scan"):
                y, state = ssd_chunked(state, xs, dt, a, bm, cm, d,
                                       chunk=self.chunk_size)
        if st is not None:
            ctx.put_state(self._path, advanced(
                st, t, state=state,
                conv=new_tail.reshape(b, -1).astype(st["conv"].dtype)))
        with jax.named_scope("gate_norm"):
            # gate first, then the norm over each group's numbers (the
            # published ``mamba_norm_before_gate: false``)
            y = (y.reshape(b, t, self.inner_dim) * jax.nn.silu(f32(z))
                 ).reshape(b, t, g, self.inner_dim // g)
            y = (F.rms_norm(y, eps=self.eps).reshape(b, t, self.inner_dim)
                 * f32(p["norm_weight"])).astype(x.dtype)
        with jax.named_scope("out_proj"):
            return F.linear(y, p["out_weight"])

    def __repr__(self):
        return (f"Mamba2({self.dim}, heads={self.num_heads}, "
                f"head_dim={self.head_dim}, state_dim={self.state_dim}, "
                f"groups={self.num_groups})")
