"""Manifold-constrained hyper-connections (Xie et al., arXiv:2512.24880, on
the hyper-connections of Zhu et al., arXiv:2409.19606): the residual is ``n``
streams of width ``C`` a token, and every sublayer reads ONE mix of them and
writes back into ALL of them through coefficients computed from the streams
themselves, the ``n x n`` stream-to-stream matrix held doubly stochastic by a
Sinkhorn iteration.

For a token's ``X`` in R^(n x C) and a sublayer ``F`` (chipbench/reference/
xing4.py is the plain form of the same lines)::

    r      = RMSNorm_w(vec X)                  over all n C numbers
    Hpre~  = a_pre  (r P_pre)  + b_pre         in R^n
    Hpost~ = a_post (r P_post) + b_post        in R^n
    Hres~  = a_res  mat(r P_res) + b_res       in R^(n x n)
    Hpre = sigmoid(Hpre~);  Hpost = 2 sigmoid(Hpost~)
    M_0 = exp(clip(Hres~, lo, hi))
    M_t = rows(cols(M_(t-1))), t = 1..iters;   cols(M) = M / (column sums + eps)
    u  = sum_i Hpre[i] X[i];   y = F(u)
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y

How it is written, and why (PERF.md section 6, PR 38):

- The streams are a TUPLE of ``n`` arrays ``(B, T, C)`` in the compute type,
  never one stacked array: every mix is then elementwise over ``(B, T, C)``
  with per-row scalars, and XLA makes multi-output fusions that read each
  stream once.  A stacked ``(n, B, T, C)`` costs a concatenate or an
  ``einsum`` whose contraction of 4 the TPU compiler transposes for.
- The coefficients are float32.  ``w`` is folded into the projections
  (``r P = rsqrt(mean(X^2) + eps) (X (w P))``), so the streams are read by
  ``n`` thin matmuls and one sum of squares and the normalised ``r`` never
  exists; the three projections are one ``(C, n + n + n^2)`` operand a stream.
- The Sinkhorn iteration is unrolled over a ``(n^2, rows)`` array and its
  sums are products with two constant 0/1 matrices at ``HIGHEST`` precision:
  no ``reduce``, no ``while``, and the TPU compiler makes ONE operation of a
  half step (the sums, their broadcast back, the division): 40 a sublayer.
  Three forms were measured on the chip (PR 38; a 4,096 bucket, a 96-slot
  step, tokens/s over six seeds, set-up warm and cold): over the matrix's
  ``n^2`` ENTRIES as lists, with an ``optimization_barrier`` every two steps
  (unbroken, the compiler did not finish a prefill's chain in 20 minutes):
  111.1 ms, 15.13 ms, 14,595, 77-82 s and 196 s; over ``(n, n, rows)`` with
  sums of slices: 112.6 ms, 15.05 ms, 14,379, 53-56 s and 134 s; this one:
  111.3 ms, 15.05 ms, 14,669, 45-47 s and 84 s.
- Each mix accumulates in float32 and rounds once to the streams' type.

Scopes (under the module's own name, ``block2/hc_attn/...``): ``hc_coeff``
the norm, the projections and the Sinkhorn; ``hc_pre`` the ``n -> 1``
contraction; ``hc_post`` the ``n x n`` mix and the add.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import init as I
from .module import Module, _ctx

__all__ = ["HyperConnection", "open_streams", "close_streams"]


def open_streams(x, streams: int) -> tuple:
    """The embedding as ``streams`` copies (no operation: one array, named
    ``streams`` times)."""
    return (x,) * streams


def close_streams(xs: tuple):
    """The streams' sum, accumulated in float32."""
    with jax.named_scope("close_streams"):
        acc = xs[0].astype(jnp.float32)
        for x in xs[1:]:
            acc = acc + x.astype(jnp.float32)
        return acc.astype(xs[0].dtype)


#: the drawn parameters (``create_params``)
POST_SCALE, POST_BIAS = 3.0, 3.0
RES_SCALE, RES_BIAS_DIAGONAL = 4.0, 3.0


def sinkhorn(m, iters: int, eps: float):
    """``iters`` times columns-then-rows normalisation of the positive
    matrices ``m`` ``(n * n, rows)``, entry ``(i, j)`` in row ``n i + j``;
    unrolled.  The sums are products with constant 0/1 matrices, so that a
    half step (the sums, their broadcast back, the division) is ONE
    operation of the compiled program."""
    n = math.isqrt(m.shape[0])
    same_column = jnp.kron(jnp.ones((n, n)), jnp.eye(n))    # sums over i
    same_row = jnp.kron(jnp.eye(n), jnp.ones((n, n)))       # sums over j
    total = lambda g, m: jnp.dot(g, m, precision=jax.lax.Precision.HIGHEST)
    for _ in range(iters):
        m = m / (total(same_column, m) + eps)
        m = m / (total(same_row, m) + eps)
    return m


class HyperConnection(Module):
    """How ONE sublayer's output joins a residual of ``streams`` streams.

    ``u, coeff = hc(xs)`` gives the sublayer's input and the coefficients
    of the way back, ``xs = hc.post(xs, F(u), coeff)`` the new streams; the
    sublayer runs between the two under its own scope
    (:class:`~tpu_dist.models.TransformerBlock`).  ``res_clamp_*`` bound the
    logits of the stream-to-stream matrix before the exponential."""

    def __init__(self, dim: int, streams: int = 4, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, res_clamp_min: float = -30.0,
                 res_clamp_max: float = 30.0, norm_eps: float = 1e-6):
        super().__init__()
        if streams < 2:
            raise ValueError(f"a hyper-connection mixes at least 2 streams, "
                             f"got {streams}")
        self.dim, self.streams = dim, streams
        self.sinkhorn_iters, self.eps = sinkhorn_iters, eps
        self.res_clamp = (res_clamp_min, res_clamp_max)
        self.norm_eps = norm_eps

    @property
    def numbers_per_row(self) -> int:
        """Numbers of the compute type one row must move through this
        sublayer's mixes: read ``X`` and write ``u``; read ``X`` and ``y``
        and write ``X'``."""
        return (3 * self.streams + 2) * self.dim

    def create_params(self, key):
        """Projections U(+-1/sqrt(n C)) (a normalised row gives each logit a
        deviation of 0.58 a unit of scale); ``pre``: scale 1, bias U(+-0.5);
        ``post``: scale ``POST_SCALE``, bias U(+-``POST_BIAS``), so that a
        sublayer's output enters the streams in unlike shares and they
        diverge; ``res``: scale ``RES_SCALE``, bias U(+-1) with
        ``RES_BIAS_DIAGONAL`` on the diagonal: a token's ``Hres`` keeps most
        of a stream where it is, moves a share that differs token by token,
        and starts far enough from doubly stochastic that the Sinkhorn's
        steps count (chipbench/configs/xing4-29b-a4b-serve.json ``assumed``
        has the counts)."""
        n, width = self.streams, self.streams * self.dim
        ks = jax.random.split(key, 6)
        lin = lambda k, out: I.torch_default_uniform(k, (width, out), width)
        bias = lambda k, shape, b: I.uniform(k, shape, -b, b)
        return {
            "norm_weight": jnp.ones((width,)),
            "pre_weight": lin(ks[0], n), "post_weight": lin(ks[1], n),
            "res_weight": lin(ks[2], n * n),
            "pre_scale": jnp.ones(()),
            "post_scale": jnp.full((), POST_SCALE),
            "res_scale": jnp.full((), RES_SCALE),
            "pre_bias": bias(ks[3], (n,), 0.5),
            "post_bias": bias(ks[4], (n,), POST_BIAS),
            "res_bias": (bias(ks[5], (n, n), 1.0)
                         + RES_BIAS_DIAGONAL * jnp.eye(n))}

    def coefficients(self, xs: tuple) -> tuple:
        """``(Hpre, Hpost, Hres)`` of the rows of ``xs``: lists of ``n``,
        ``n`` and ``n x n`` float32 arrays shaped ``xs[0].shape[:-1] +
        (1,)``, ready to scale a stream."""
        p = _ctx().get_params(self._path)
        n, c = self.streams, self.dim
        f32 = lambda a: a.astype(jnp.float32)
        lead = xs[0].shape[:-1]
        flat = [x.reshape(-1, c) for x in xs]
        # w folded into one (n C, n + n + n^2) operand, read a stream at a
        # time in the streams' own type with float32 accumulation
        proj = (f32(p["norm_weight"])[:, None] * jnp.concatenate(
            [f32(p["pre_weight"]), f32(p["post_weight"]),
             f32(p["res_weight"])], axis=1)).astype(xs[0].dtype)
        z = ss = 0.0
        for i, x in enumerate(flat):
            z = z + jnp.dot(x, proj[i * c:(i + 1) * c],
                            preferred_element_type=jnp.float32)
            ss = ss + jnp.square(f32(x)).sum(-1)
        z = z * jax.lax.rsqrt(ss / (n * c) + self.norm_eps)[:, None]
        col = lambda k: z[:, k]                 # one number a row
        pre = [jax.nn.sigmoid(f32(p["pre_scale"]) * col(i)
                              + f32(p["pre_bias"])[i]) for i in range(n)]
        post = [2.0 * jax.nn.sigmoid(f32(p["post_scale"]) * col(n + i)
                                     + f32(p["post_bias"])[i])
                for i in range(n)]
        logits = (f32(p["res_scale"]) * z[:, 2 * n:].T
                  + f32(p["res_bias"]).reshape(n * n, 1))
        res = sinkhorn(jnp.exp(jnp.clip(logits, *self.res_clamp)),
                       self.sinkhorn_iters, self.eps)
        rows = lambda a: a.reshape(lead + (1,))
        return ([rows(a) for a in pre], [rows(a) for a in post],
                [[rows(res[n * i + j]) for j in range(n)] for i in range(n)])

    def forward(self, xs: tuple):
        with jax.named_scope("hc_coeff"):
            pre, post, res = self.coefficients(xs)
        with jax.named_scope("hc_pre"):
            u = _mix(pre, xs).astype(xs[0].dtype)
        return u, (post, res)

    def post(self, xs: tuple, y, coeff) -> tuple:
        """The streams after the sublayer's output ``y`` joined them."""
        post, res = coeff
        with self.scope(), jax.named_scope("hc_post"):
            y32 = y.astype(jnp.float32)
            return tuple((_mix(res[i], xs) + post[i] * y32).astype(y.dtype)
                         for i in range(self.streams))


def _mix(weights: list, xs: tuple):
    """``sum_i weights[i] * xs[i]`` in float32."""
    acc = weights[0] * xs[0].astype(jnp.float32)
    for w, x in zip(weights[1:], xs[1:]):
        acc = acc + w * x.astype(jnp.float32)
    return acc
