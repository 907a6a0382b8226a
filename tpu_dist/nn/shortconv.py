"""The short causal convolution a token mixer keeps a tail of, and the mixer
that is nothing else.

A causal depthwise convolution of width ``K`` over a layer's channels reads
the current position and the ``K - 1`` before it, so a layer served from the
slot cache keeps those ``K - 1`` inputs per slot: its ``conv`` TAIL, a leaf of
whole state (nn/cache.py), replaced entire at every call.  Four mixers keep
one and share the helpers below: in :class:`~tpu_dist.nn.GatedDeltaNet`,
:class:`~tpu_dist.nn.KimiDeltaAttention` and :class:`~tpu_dist.nn.Mamba2` the
convolution, with SiLU, stands before a recurrence whose state the slot keeps
too; in :class:`GatedShortConv` (LFM2's ``conv`` layers) the convolution,
WITHOUT an activation, is the layer's whole mixer and the tail its whole
cache entry.

- :func:`valid_positions`, the call's mask of positions that are a request's;
- :func:`conv_tail`, the tail before the call (zeros for a plain forward);
- :func:`causal_conv`, the convolution over the call's positions and the tail
  after the call's LAST REAL position: bucket padding in a prefill and a free
  slot's row in a decode step are nobody's, and a tail advanced over them
  would make a request's tokens depend on its bucket;
- :func:`advanced`, the cache entry after the call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import functional as F
from . import init as I
from .module import Module

__all__ = ["GatedShortConv", "causal_conv", "conv_tail", "advanced",
           "valid_positions"]


def valid_positions(st, b: int, t: int):
    """The call's mask of positions that are a request's, (B, t): the cache
    entry's ``valid``, all of them for a plain forward."""
    valid = None if st is None else st.get("valid")
    if valid is None:
        valid = jnp.ones((b, t), bool)
    return jnp.broadcast_to(valid, (b, t))


def conv_tail(st, name: str, like):
    """A convolution's last ``K - 1`` inputs before this call, (B, K - 1,
    C) in ``like``'s type: the cache entry's flattened leaf ``name``, zeros
    for a plain forward (``like`` (B, K - 1, C) gives the shape)."""
    if st is None:
        return jnp.zeros(like.shape, like.dtype)
    return st[name].reshape(like.shape).astype(like.dtype)


def advanced(st, t: int, **leaves):
    """The cache entry ``st`` after a call of ``t`` positions: ``leaves``
    replaced entire and the write index moved on."""
    return dict(st, index=jnp.asarray(st["index"]) + t, **leaves)


def causal_conv(x, tail, weight, valid, bias=None, activation=jax.nn.silu):
    """A causal depthwise convolution over ``x`` (B, t, C), whose last ``K -
    1`` inputs before this call are ``tail`` (B, K - 1, C); ``weight`` (C,
    K), tap ``K - 1`` the current position's; ``bias`` (C,) joins the taps'
    sum where the layer has one (:class:`~tpu_dist.nn.Mamba2`);
    ``activation`` of that sum, SiLU in the three recurrent mixers, None for
    the taps' sum as it is (:class:`GatedShortConv`, where the convolution
    is the layer's whole mixer).  Returns the activations and the tail after
    the call's LAST REAL position: rows ``[n, n + K - 1)`` of the window,
    ``n`` the call's count of real positions (``valid`` (B, t); they
    lead)."""
    taps, t = tail.shape[1], x.shape[1]
    window = jnp.concatenate([tail, x], axis=1)          # (B, K-1+t, C)
    w = weight.astype(x.dtype)
    mixed = sum(window[:, j:j + t] * w[:, j] for j in range(taps + 1))
    if bias is not None:
        mixed = mixed + bias.astype(x.dtype)
    out = mixed if activation is None else activation(mixed)
    n_real = valid.sum(-1).astype(jnp.int32)
    return out, jax.vmap(lambda win, n: lax.dynamic_slice_in_dim(
        win, n, taps, axis=0))(window, n_real)


class GatedShortConv(Module):
    """The gated short convolution token mixer (drop-in for a block's
    attention; the ``conv`` layers of LFM2, HF ``modeling_lfm2.py``
    ``Lfm2ShortConv``)::

        [B | C | u] = x W_in;   s_t = B_t * u_t
        c_t = sum_j w[:, j] * s_{t - (K - 1) + j}      (depthwise, causal)
        out = (C_t * c_t) W_out

    Two gates, elementwise, around a depthwise convolution of ``K`` taps
    with NO activation and no bias: the layer mixes ``K`` positions and
    nothing further back, so what it costs a token and what it keeps a slot
    do not grow with the context.

    Args:
        dim: model width; ``B``, ``C`` and ``u`` are each ``dim`` wide.
        conv_kernel: ``K``, the taps (the published ``conv_L_cache``).

    Parameters (no biases): ``in_weight`` ``(dim, 3 dim)`` split ``[B | C |
    u]``, ``conv_weight`` ``(dim, K)`` (tap ``K - 1`` is the current
    position's) and ``out_weight`` ``(dim, dim)``.

    Served through a slot cache the layer keeps per slot
    (:meth:`init_cache`) ONE leaf, ``conv``: the last ``K - 1`` gated inputs
    ``s``, replaced entire at every call.  No recurrent ``state``, no K/V
    columns: the first cache entry that is a tail alone.  Called without a
    cache (a plain forward) it starts every sequence from zeros before the
    first position.
    """

    def __init__(self, dim: int, conv_kernel: int = 3):
        super().__init__()
        if conv_kernel < 2:
            raise ValueError(f"a convolution over positions has at least 2 "
                             f"taps, got {conv_kernel}")
        self.dim = dim
        self.conv_kernel = conv_kernel

    #: a layer of whole state reads no resident position
    attend_flops_per_position = 0

    @property
    def short_conv_params(self) -> int:
        """Parameters of the three matrices and the taps: what ONE row of
        this layer costs is twice that in operations (the gates' two
        multiplies a channel beside them are not counted).  A host fact for
        ``SlotEngine.stats()["conv"]``."""
        return self.dim * (4 * self.dim + self.conv_kernel)

    def create_params(self, key):
        ks = jax.random.split(key, 3)
        return {
            "in_weight": I.torch_default_uniform(
                ks[0], (self.dim, 3 * self.dim), self.dim),
            "conv_weight": I.torch_default_uniform(
                ks[1], (self.dim, self.conv_kernel), self.conv_kernel),
            "out_weight": I.torch_default_uniform(
                ks[2], (self.dim, self.dim), self.dim),
        }

    def init_cache(self, batch: int, max_len: int = 0, dtype=jnp.float32):
        """What this layer keeps per slot (one entry of a nn/cache.py
        tree): ``conv`` ``(B, (K - 1) * dim)`` in ``dtype``, the
        convolution's last ``K - 1`` inputs ``B * u``, oldest first,
        flattened as :meth:`GatedDeltaNet.init_cache` flattens its tail.  It
        has no time axis: ``max_len`` does not size it."""
        return {"conv": jnp.zeros((batch, (self.conv_kernel - 1) * self.dim),
                                  dtype)}

    def forward(self, x):
        from .module import _ctx
        ctx = _ctx()
        p = ctx.get_params(self._path)
        st = (ctx.get_state(self._path)
              if ctx.state is not None and self._path in ctx.state else None)
        b, t, _ = x.shape
        with jax.named_scope("in_proj"):
            gate_in, gate_out, u = jnp.split(
                F.linear(x, p["in_weight"]), 3, axis=-1)
        with jax.named_scope("conv"):
            gated = gate_in * u
            tail = conv_tail(st, "conv", jax.ShapeDtypeStruct(
                (b, self.conv_kernel - 1, self.dim), gated.dtype))
            mixed, new_tail = causal_conv(
                gated, tail, p["conv_weight"], valid_positions(st, b, t),
                activation=None)
            y = gate_out * mixed
        if st is not None:
            ctx.put_state(self._path, advanced(
                st, t, conv=new_tail.reshape(b, -1).astype(st["conv"].dtype)))
        with jax.named_scope("out_proj"):
            return F.linear(y, p["out_weight"])

    def __repr__(self):
        return f"GatedShortConv({self.dim}, conv_kernel={self.conv_kernel})"
