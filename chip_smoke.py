"""chip_smoke.py — does the system still start on the chip?

One process drives the main path once through the entry points a user calls,
at the full width of the repo's GPT-2-small-shaped LM (12 x 768 x 12 heads,
vocab 32768, T = 2048), every phase fatal:

  device    platform must be ``tpu``; versions, compile cache, store backend
  kernels   the Pallas kernels (flash attention, fused CE and the grouped
            matmuls forward and backward, slot-decode attention by head,
            by groups of query heads and over a latent, the recurrent
            state's one-token update and chunked scan, the expert
            combine by its buffer's rows),
            lowered by Mosaic at their full-width users' shapes, against
            plain ``jnp``
  convnet   the source paper's ConvNet through ``init_process_group`` +
            ``DistributedDataParallel.train_step``
  trainer   the LM through the same DDP over ALL local devices, bf16, fused
            CE, flash attention — Mosaic calls checked in the compiled step
  server    the same LM behind SlotEngine + Scheduler + Frontend, queried over
            loopback by ServeClient, against offline ``generate()``
  multichip (>= 4 devices) dp=N loss vs one device, then every
            ``__graft_entry__.dryrun_multichip`` mesh config on the real chips

Seconds are printed per phase as information, under no metric name.  The last
line of stdout is one JSON object; the exit code is 0 only if every phase
passed.  The phases are importable functions taking sizes, so
tests/test_chip_smoke.py runs them tiny on the CPU mesh; ``main()`` has no
CPU branch.

    python chip_smoke.py
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

# bf16 carries 8 mantissa bits (ulp 2^-8 = 3.9e-3).  Each kernel rounds one
# intermediate tile (probabilities / ds / the hidden activation) and its
# output to bf16 while the reference stays in f32, so a correct kernel sits
# within a few ulp of the largest element; 3e-2 (~8 ulp) is also the bound
# tests/test_flash_attention.py holds the bf16 forward to.
BF16_TOL = 3e-2
# float32 sums of 128 products in another order
F32_TOL = 2e-6
# three bfloat16 passes a product (2^-17 of its scale) on both sides, chained
# through the chunks of a prompt
SCAN_TOL = 1e-4

# Serving check (see phase_server): a served greedy token may trail the
# position's max logit in a plain forward by at most this.  The two paths
# differ by f32 reduction order through 12 layers of default-precision TPU
# matmuls; a wrong token on random weights trails by ~2 (logit std ~0.6,
# max of 32768 draws ~4 std up), so 2e-2 separates the two by two orders.
SERVE_LOGIT_TOL = 2e-2

LM = dict(vocab_size=32768, dim=768, depth=12, num_heads=12, max_seq_len=2048)

# the names ops/*.py give their pallas_calls; the compiled trainer step must
# hold a Mosaic custom call for each (neither dense attention, nor plain CE,
# nor the interpreter was taken)
TRAINER_KERNELS = ("flash_fwd", "flash_bwd_dq_dkv",
                   "fused_ce_fwd", "fused_ce_bwd")


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _rel_err(got, want) -> float:
    """max|got - want| over max|want|, both as float32."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def _check_close(name: str, got, want, tol: float) -> None:
    err = _rel_err(got, want)
    if not err <= tol:   # also catches NaN
        raise AssertionError(f"{name}: normalized max error {err:.3e} "
                             f"exceeds {tol:.1e}")
    _say(f"  {name}: normalized max error {err:.2e} (tol {tol:.0e})")


def mosaic_kernels(hlo_text: str, names) -> set:
    """Which of ``names`` appear as Mosaic (``tpu_custom_call``) kernels in
    compiled HLO text."""
    lines = [ln for ln in hlo_text.splitlines() if "tpu_custom_call" in ln]
    return {n for n in names if any(n in ln for ln in lines)}


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_report() -> dict:
    """Print and return what JAX resolved, the versions, where the compile
    cache lives and whether it was warm, and which store server loaded."""
    import jax
    import jaxlib

    from tpu_dist.dist.store import TCPStore
    from tpu_dist.ops._pallas import use_interpret
    from tpu_dist.utils.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    warm = os.path.isdir(cache_dir) and any(os.scandir(cache_dir))
    devs = jax.devices()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    store = TCPStore(is_master=True)   # builds csrc/ with g++ on first use
    store_impl = ("native libtpudist.so" if store.native
                  else "python fallback (no native build)")
    store.close()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pallas_interpret": use_interpret(),
            "cache_dir": cache_dir, "cache_warm_at_start": warm}
    _say(f"platform: {info['platform']}  device_kind: {info['kind']}  "
         f"count: {info['count']}")
    _say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
         f"libtpu {libtpu_version}")
    _say(f"compile cache: {cache_dir} "
         f"({'held entries' if warm else 'empty'} at start)")
    _say(f"pallas interpret mode: {info['pallas_interpret']}")
    _say(f"store server: {store_impl}")
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def check_flash(batch: int, seq: int, heads: int, head_dim: int) -> None:
    """Causal bf16 flash attention, forward and all three grads, at the
    full (batch*heads, seq, head_dim) shape; compared on the last batch
    element against dense f32 attention on the same bf16-rounded inputs."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.nn.attention import scaled_dot_product_attention
    from tpu_dist.ops.flash_attention import flash_attention

    kq, kk, kv, kc = jax.random.split(jax.random.key(1), 4)
    shape = (batch, seq, heads, head_dim)
    q, k, v, cot = (jax.random.normal(key, shape, jnp.float32)
                    .astype(jnp.bfloat16) for key in (kq, kk, kv, kc))

    def run(attend, q, k, v, cot):
        def loss(q, k, v):
            out = attend(q, k, v)
            return (out.astype(jnp.float32)
                    * cot.astype(jnp.float32)).sum(), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1, 2),
                                             has_aux=True)(q, k, v)
        return (out,) + grads

    got = jax.jit(lambda *a: run(
        lambda q, k, v: flash_attention(q, k, v, causal=True), *a))(
            q, k, v, cot)
    last = [a[-1:].astype(jnp.float32) for a in (q, k, v, cot)]
    want = jax.jit(lambda *a: run(
        lambda q, k, v: scaled_dot_product_attention(
            q, k, v, causal=True, impl="dense"), *a))(*last)
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        _check_close(f"flash {name}", g[-1:], w, BF16_TOL)


def check_fused_ce(rows: int, vocab: int) -> None:
    """Fused CE on (rows, vocab) bf16 logits, forward and backward at the
    full shape; compared on the last <= 512 rows against the f32 jnp
    composition.  The kernel upcasts to f32 internally, so the per-row loss
    matches to f32 rounding; dlogits is emitted in bf16 (one rounding)."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.nn import functional as F
    from tpu_dist.ops.cross_entropy import fused_cross_entropy

    kl, ky, kc = jax.random.split(jax.random.key(2), 3)
    logits = (jax.random.normal(kl, (rows, vocab), jnp.float32) * 2.0
              ).astype(jnp.bfloat16)
    labels = jax.random.randint(ky, (rows,), 0, vocab, jnp.int32)
    cot = jax.random.normal(kc, (rows,), jnp.float32)

    def run(ce, logits, labels, cot):
        def loss(lg):
            nll = ce(lg, labels)
            return (nll * cot).sum(), nll
        (_, nll), dlogits = jax.value_and_grad(loss, has_aux=True)(logits)
        return nll, dlogits

    nll, dlogits = jax.jit(lambda *a: run(
        lambda lg, y: fused_cross_entropy(lg, y, reduction="none"), *a))(
            logits, labels, cot)
    n = min(rows, 512)
    nll_ref, dlogits_ref = jax.jit(lambda *a: run(
        lambda lg, y: F.cross_entropy(lg, y, reduction="none"), *a))(
            logits[-n:].astype(jnp.float32), labels[-n:], cot[-n:])
    _check_close("fused CE loss", nll[-n:], nll_ref, 1e-5)
    _check_close("fused CE dlogits", dlogits[-n:], dlogits_ref, BF16_TOL)


def _planted_routing_tokens(router, tokens: int, seed: int = 3):
    """Tokens whose router logits have a planted, well-separated top-2 (an
    uneven expert load on purpose), so the bf16 layer and the f32 reference
    cannot disagree on routing.  Returns float32 (tokens, dim)."""
    import numpy as np

    r = np.asarray(router, np.float32)                    # (dim, experts)
    e = r.shape[1]
    rng = np.random.default_rng(seed)
    load = np.arange(1, e + 1, dtype=np.float64)
    first = rng.choice(e, tokens, p=load / load.sum())
    second = (first + rng.integers(1, e, tokens)) % e
    target = np.zeros((tokens, e), np.float32)
    target[np.arange(tokens), first] = 8.0
    target[np.arange(tokens), second] = 4.0
    x = target @ np.linalg.pinv(r) + 0.05 * rng.standard_normal(
        (tokens, r.shape[0])).astype(np.float32)
    top3 = np.sort(x @ r, axis=-1)[:, -3:]
    margin = float(np.min(np.diff(top3, axis=-1)))
    if margin < 1.0:
        raise AssertionError(f"planted routing margin {margin:.2f} < 1.0")
    return x


def check_dropless_moe(tokens: int, dim: int, experts: int,
                       top_k: int) -> None:
    """``MoELayer(dispatch="dropless")`` — gmm forward, gmm dx and tgmm
    dw/db at both FFN shapes (dim -> 4*dim -> dim) — in bf16, forward and
    grads, against an all-experts f32 jnp reference."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu_dist import nn

    layer = nn.MoELayer(dim, experts, top_k=top_k, dispatch="dropless")
    p = layer.init(jax.random.key(4))[""]
    kb1, kb2, kc = jax.random.split(jax.random.key(5), 3)
    p = dict(p, b1=0.1 * jax.random.normal(kb1, p["b1"].shape),
             b2=0.1 * jax.random.normal(kb2, p["b2"].shape))
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    x16 = jnp.asarray(_planted_routing_tokens(p16["router"], tokens)
                      ).astype(jnp.bfloat16)
    cot = jax.random.normal(kc, (tokens, dim), jnp.float32)

    def reference(p, x):
        hi = lax.Precision.HIGHEST
        probs = jax.nn.softmax(jnp.dot(x, p["router"], precision=hi), -1)
        gate, idx = lax.top_k(probs, top_k)
        gate = gate / gate.sum(-1, keepdims=True)
        hid = jax.nn.gelu(jnp.einsum("nd,edh->enh", x, p["w1"], precision=hi)
                          + p["b1"][:, None, :])
        out = (jnp.einsum("enh,ehd->end", hid, p["w2"], precision=hi)
               + p["b2"][:, None, :])
        picked = jnp.take_along_axis(out, idx.T[:, :, None], axis=0)
        return (picked * gate.T[:, :, None]).sum(0)

    def run(fn, p, x):
        def loss(p, x):
            y = fn(p, x)
            return (y.astype(jnp.float32) * cot).sum(), y
        (_, y), (dp, dx) = jax.value_and_grad(loss, (0, 1),
                                              has_aux=True)(p, x)
        return y, dx, dp

    y, dx, dp = jax.jit(lambda p, x: run(
        lambda p, x: layer.apply({"": p}, x), p, x))(p16, x16)
    to32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    y_ref, dx_ref, dp_ref = jax.jit(lambda p, x: run(reference, p, x))(
        to32(p16), to32(x16))
    _check_close("dropless MoE out", y, y_ref, BF16_TOL)
    _check_close("dropless MoE dx", dx, dx_ref, BF16_TOL)
    for name in ("w1", "b1", "w2", "b2"):
        _check_close(f"dropless MoE d{name}", dp[name], dp_ref[name],
                     BF16_TOL)


def check_decode_attention(slots: int, heads: int, head_dim: int,
                           max_len: int, group: int = 1) -> None:
    """The slot-decode kernel on a bf16 K/V pool ``(slots, heads, head_dim,
    max_len)`` with ragged lengths (free slots, lane and block edges, a
    full row), ``group`` query heads a K/V head (1: the one-row form, more:
    the grouped one), against the float32 jnp composition of the same step:
    the output of every busy slot, and the pools bit for bit (the new
    column in, nothing else touched)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.ops.decode_attention import decode_attention

    keys = jax.random.split(jax.random.key(6), 5)
    q = jax.random.normal(keys[0], (slots, heads * group, head_dim),
                          jnp.bfloat16)
    kn, vn = (jax.random.normal(k, (slots, heads, head_dim), jnp.bfloat16)
              for k in keys[1:3])
    kp, vp = (jax.random.normal(k, (slots, heads, head_dim, max_len),
                                jnp.bfloat16) for k in keys[3:])
    edges = [0, 1, 127, 128, 129, 255, 256, 257, max_len - 1, max_len, 0,
             max_len // 2 + 3]
    lens = jnp.asarray([edges[i % len(edges)] for i in range(slots)],
                       jnp.int32)

    def reference(q, kn, vn, kp, vp, lens):
        hi = jax.lax.Precision.HIGHEST
        at = jnp.arange(max_len) == lens[:, None, None, None]
        kp = jnp.where(at, kn[..., None], kp)
        vp = jnp.where(at, vn[..., None], vp)
        qg = q.astype(jnp.float32).reshape(slots, heads, group, head_dim)
        s = jnp.einsum("bhgd,bhdt->bhgt", qg, kp.astype(jnp.float32),
                       precision=hi) / math.sqrt(head_dim)
        seen = jnp.arange(max_len) <= lens[:, None, None, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgt,bhdt->bhgd", w, vp.astype(jnp.float32),
                         precision=hi)
        return out.reshape(q.shape), kp, vp

    out, k2, v2 = jax.jit(decode_attention)(q, kn, vn, kp, vp, lens)
    want, k_ref, v_ref = jax.jit(reference)(q, kn, vn, kp, vp, lens)
    busy = np.asarray(lens) > 0
    _check_close("decode attention out", np.asarray(out, np.float32)[busy],
                 np.asarray(want)[busy], BF16_TOL)
    for name, got, ref, before in (("K", k2, k_ref, kp), ("V", v2, v_ref, vp)):
        got, ref, before = (np.asarray(a, np.float32)
                            for a in (got, ref, before))
        if not (np.array_equal(got[busy], ref[busy])
                and np.array_equal(got[~busy], before[~busy])):
            raise AssertionError(f"decode attention: the {name} pool is not "
                                 f"the input with the new columns in")
    _say(f"  decode attention pools: new columns in, nothing else touched "
         f"({int(busy.sum())} busy of {slots} slots)")


def check_latent_decode_attention(slots: int, heads: int, latent: int,
                                  values: int, max_len: int) -> None:
    """The latent form of the slot-decode kernel on a bf16 pool ``(slots,
    latent, max_len)`` with the same ragged lengths, ``heads`` query rows a
    slot, the values the first ``values`` rows of each column, against the
    float32 jnp composition of the same step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.ops.decode_attention import latent_decode_attention

    keys = jax.random.split(jax.random.key(8), 3)
    # queries at the size a softmax scale of latent ** -0.5 expects
    q = jax.random.normal(keys[0], (slots, heads, latent), jnp.bfloat16)
    new = jax.random.normal(keys[1], (slots, latent), jnp.bfloat16)
    pool = jax.random.normal(keys[2], (slots, latent, max_len), jnp.bfloat16)
    edges = [0, 1, 127, 128, 129, 255, 256, 257, max_len - 1, max_len, 0,
             max_len // 2 + 3]
    lens = jnp.asarray([edges[i % len(edges)] for i in range(slots)],
                       jnp.int32)
    scale = latent ** -0.5

    def reference(q, new, pool, lens):
        hi = jax.lax.Precision.HIGHEST
        pool = jnp.where(jnp.arange(max_len) == lens[:, None, None],
                         new[..., None], pool)
        wide = pool.astype(jnp.float32)
        s = jnp.einsum("bhc,bct->bht", q.astype(jnp.float32), wide,
                       precision=hi) * scale
        seen = jnp.arange(max_len) <= lens[:, None, None]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bht,bct->bhc", w, wide[:, :values],
                          precision=hi), pool

    out, got = jax.jit(lambda *a: latent_decode_attention(
        *a, value_dim=values, scale=scale))(q, new, pool, lens)
    want, ref = jax.jit(reference)(q, new, pool, lens)
    busy = np.asarray(lens) > 0
    _check_close("latent decode attention out",
                 np.asarray(out, np.float32)[busy], np.asarray(want)[busy],
                 BF16_TOL)
    got, ref, before = (np.asarray(a, np.float32) for a in (got, ref, pool))
    if not (np.array_equal(got[busy], ref[busy])
            and np.array_equal(got[~busy], before[~busy])):
        raise AssertionError("latent decode attention: the pool is not the "
                             "input with the new columns in")
    _say(f"  latent decode attention pool: new columns in, nothing else "
         f"touched ({int(busy.sum())} busy of {slots} slots)")


def check_delta_step(slots: int, heads: int, k_dim: int, v_dim: int) -> None:
    """The recurrent state's one-token update on a float32 state ``(slots,
    heads, k_dim, v_dim)``, a decay a channel and a decay a head, slot 1 a
    no-op row, against ``nn.deltanet.gated_delta_step``, the state donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.nn.deltanet import gated_delta_step
    from tpu_dist.ops.delta_step import delta_step

    keys = jax.random.split(jax.random.key(9), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    state = jax.random.normal(keys[0], (slots, heads, k_dim, v_dim))
    q = unit(jax.random.normal(keys[1], (slots, heads, k_dim))) * k_dim ** -.5
    k = unit(jax.random.normal(keys[2], (slots, heads, k_dim)))
    v = jax.random.normal(keys[3], (slots, heads, v_dim))
    beta = jax.random.uniform(keys[4], (slots, heads)).at[1].set(0.0)
    for rank, shape in (("channel", k.shape), ("head", beta.shape)):
        g = (-2.0 * jax.random.uniform(keys[5], shape)).at[1].set(0.0)
        want_o, want = jax.jit(gated_delta_step)(state, q, k, v, g, beta)
        out, got = jax.jit(delta_step, donate_argnums=0)(
            state + 0.0, q, k, v, g, beta)
        _check_close(f"delta step out, a decay a {rank}", out, want_o, F32_TOL)
        _check_close(f"delta step state, a decay a {rank}", got, want,
                     F32_TOL)
        if not np.array_equal(np.asarray(got)[1], np.asarray(state)[1]):
            raise AssertionError("delta step: a no-op row's state moved")


def check_delta_scan(seq: int, key_heads: int, value_heads: int, k_dim: int,
                     v_dim: int) -> None:
    """The recurrent state's chunked scan (a decay a head) over one prompt
    of ``seq`` positions whose last tenth is padding (``g = 0``, ``beta =
    0``), from a drawn float32 state, against
    ``nn.deltanet.gated_delta_chunked`` on the same operands repeated and
    heads-first, the state donated; and the state after the padded prompt
    against the state after its real positions alone."""
    import jax
    import jax.numpy as jnp

    from tpu_dist.nn.deltanet import gated_delta_chunked
    from tpu_dist.ops.delta_scan import delta_scan

    keys = jax.random.split(jax.random.key(10), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    real = seq - seq // 10
    valid = (jnp.arange(seq) < real)[None, :, None]
    state = jax.random.normal(keys[0], (1, value_heads, k_dim, v_dim))
    q = unit(jax.random.normal(keys[1], (1, seq, key_heads, k_dim))) \
        * k_dim ** -.5
    k = unit(jax.random.normal(keys[2], (1, seq, key_heads, k_dim)))
    v = jax.random.normal(keys[3], (1, seq, value_heads, v_dim))
    beta = jnp.where(valid, jax.random.uniform(
        keys[4], (1, seq, value_heads)), 0.0)
    g = jnp.where(valid, -jnp.exp(jax.random.uniform(
        keys[5], (1, seq, value_heads), minval=-7.0, maxval=1.0)), 0.0)

    def chunked(state, q, k, v, g, beta):
        rep = value_heads // key_heads
        q, k = (jnp.repeat(a, rep, axis=2) for a in (q, k))
        out, state = gated_delta_chunked(state, *(
            jnp.moveaxis(a, 2, 1) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(out, 1, 2), state

    want_o, want = jax.jit(chunked)(state, q, k, v, g, beta)
    scan = jax.jit(delta_scan, donate_argnums=0)
    out, got = scan(state + 0.0, q, k, v, g, beta)
    _check_close("delta scan out", out, want_o, SCAN_TOL)
    _check_close("delta scan state", got, want, SCAN_TOL)
    _, alone = scan(state + 0.0, *(a[:, :real] for a in (q, k, v, g, beta)))
    _check_close("delta scan state, padded against alone", got, alone,
                 F32_TOL)


def check_moe_combine(tokens: int, top_k: int, rows: int, dim: int,
                      share: float) -> None:
    """The combine of a share of the experts by its buffer's rows: ``tokens``
    x ``top_k`` picks of which ``share`` hold a row of a ``(rows, dim)``
    bfloat16 buffer, the last tenth of the tokens alike (bucket padding)
    with every pick held, against the float32 sum of each token's rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist.ops.moe_combine import combine_by_token

    rng = np.random.default_rng(12)
    held = rng.random((top_k, tokens)) < share
    held[:, tokens - tokens // 10:] = True
    picks = np.flatnonzero(held.reshape(-1))
    if len(picks) > rows:
        raise AssertionError(f"{len(picks)} held picks pass {rows} rows")
    slot = np.full(top_k * tokens, rows, np.int32)
    slot[picks] = rng.permutation(rows)[:len(picks)]
    slot = jnp.asarray(slot.reshape(top_k, tokens))
    out = jnp.asarray(rng.standard_normal((rows, dim)), jnp.bfloat16)
    w = jnp.asarray(rng.random((top_k, tokens)), jnp.bfloat16)
    padded = jnp.concatenate([out, jnp.zeros_like(out[:1])])
    want = (padded[slot].astype(jnp.float32)
            * w.astype(jnp.float32)[:, :, None]).sum(0)
    got = jax.jit(combine_by_token)(out, w, slot)
    _check_close("expert combine by the buffer's rows", got, want, BF16_TOL)


def phase_kernels(flash: dict, ce: dict, moe: dict, decode: dict,
                  grouped: dict, latent: dict, state: dict,
                  scan: dict, combine: dict) -> dict:
    t0 = time.perf_counter()
    check_flash(**flash)
    check_fused_ce(**ce)
    check_dropless_moe(**moe)
    check_decode_attention(**decode)
    check_decode_attention(**grouped)
    check_latent_decode_attention(**latent)
    check_delta_step(**state)
    check_delta_scan(**scan)
    check_moe_combine(**combine)
    return {"seconds": time.perf_counter() - t0}


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def _assert_spans_group(pg, x, state) -> None:
    """Every device of the group holds a shard of the batch and, of each
    parameter leaf, a replica or (a leaf the sharded update divides) its
    1/world."""
    import jax

    want = set(pg.devices)
    rows = x.shape[0] // len(want)
    shards = {s.device: s.data.shape for s in x.addressable_shards}
    if set(shards) != want or any(sh[0] != rows for sh in shards.values()):
        raise AssertionError(f"batch shards {shards} do not cover {want} "
                             f"with {rows} rows each")
    for leaf in jax.tree.leaves(state.params):
        if set(leaf.sharding.device_set) != want:
            raise AssertionError(f"params not spread over the group: "
                                 f"{leaf.sharding}")


def _train(ddp, state, batches, put) -> tuple:
    """Run ``train_step`` over ``batches``; returns (state, losses, seconds
    of the first call — trace, compile and step — and mean seconds of the
    others)."""
    losses, secs = [], []
    for x, y in batches:
        t0 = time.perf_counter()
        state, metrics = ddp.train_step(state, put(x), put(y))
        losses.append(float(metrics["loss"]))   # readback = the sync
        secs.append(time.perf_counter() - t0)
    rest = secs[1:] or [float("nan")]
    return state, losses, secs[0], sum(rest) / len(rest)


def phase_convnet(backend: str, per_chip_batch: int, steps: int) -> dict:
    """The reference ConvNet, DDP over every device, on MNIST-shaped noise
    with a planted per-class bright square: loss finite and falling."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist import nn, optim
    from tpu_dist.models import ConvNet
    from tpu_dist.parallel import DistributedDataParallel

    pg = dist.init_process_group(backend=backend)
    try:
        ddp = DistributedDataParallel(
            ConvNet(), optimizer=optim.SGD(lr=0.02, momentum=0.9),
            loss_fn=nn.CrossEntropyLoss(), group=pg)
        state = ddp.init(seed=0)
        rng = np.random.default_rng(0)
        pattern = np.zeros((10, 28, 28, 1), np.float32)
        for k in range(10):
            r, c = divmod(k, 4)
            pattern[k, 2 + 8 * r:10 + 8 * r, 1 + 6 * c:9 + 6 * c] = 2.0
        batch = per_chip_batch * pg.size()

        def batches():
            for _ in range(steps):
                y = rng.integers(0, 10, batch)
                x = pattern[y] + rng.standard_normal(
                    (batch, 28, 28, 1)).astype(np.float32)
                yield x, y

        shard = NamedSharding(pg.mesh, P(pg.axis_name))
        state, losses, setup_s, step_s = _train(
            ddp, state, batches(), lambda a: jax.device_put(a, shard))
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"ConvNet loss not finite: {losses}")
        if not losses[-1] < 0.5 * losses[0]:
            raise AssertionError(f"ConvNet loss did not fall: {losses}")
        _say(f"  ConvNet on {pg.size()} {dist.get_backend()} device(s): "
             f"loss {losses[0]:.3f} -> {losses[-1]:.3f} in {steps} steps; "
             f"set-up {setup_s:.1f} s, then {step_s * 1e3:.1f} ms a step")
        return {"losses": losses}
    finally:
        dist.destroy_process_group()


def _lm_ddp(pg, model_kw: dict, lr: float, accum_steps: int = 1):
    import jax.numpy as jnp

    from tpu_dist import nn, optim
    from tpu_dist.models import TransformerLM
    from tpu_dist.parallel import DistributedDataParallel

    return DistributedDataParallel(
        TransformerLM(**model_kw), optimizer=optim.SGD(lr=lr),
        loss_fn=nn.CrossEntropyLoss(fused=True), group=pg,
        compute_dtype=jnp.bfloat16, accum_steps=accum_steps)


def _lm_batches(vocab: int, batch: int, seq_len: int, steps: int):
    """examples/train_lm.py's permutation task, seeded."""
    import numpy as np

    from examples.train_lm import make_batches

    rng = np.random.default_rng(0)
    perm = rng.permutation(vocab)
    return make_batches(rng, perm, vocab, batch, seq_len, steps)


def phase_trainer(backend: str, model_kw: dict, per_chip_batch: int,
                  seq_len: int, steps: int, lr: float) -> dict:
    """``TransformerLM`` through ``init_process_group`` +
    ``DistributedDataParallel`` (bf16 compute, fused CE) over all local
    devices on the permutation task.  Returns the losses, the Mosaic kernels
    found in the compiled step and each device's peak bytes — ``main()``
    asserts the chip-only facts on them."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import tpu_dist.dist as dist
    from tpu_dist.utils.memory import max_memory_allocated

    pg = dist.init_process_group(backend=backend)
    try:
        ddp = _lm_ddp(pg, model_kw, lr)
        state = ddp.init(seed=0)
        batch = per_chip_batch * pg.size()
        shard = NamedSharding(pg.mesh, P(pg.axis_name))
        put = lambda a: jax.device_put(a, shard)
        batches = list(_lm_batches(model_kw["vocab_size"], batch, seq_len,
                                   steps))
        x0, y0 = put(batches[0][0]), put(batches[0][1])
        _assert_spans_group(pg, x0, state)

        state, losses, setup_s, step_s = _train(ddp, state, batches, put)
        if not np.all(np.isfinite(losses)):
            raise AssertionError(f"LM loss not finite: {losses}")
        if not losses[-1] < losses[0]:
            raise AssertionError(f"LM loss did not fall: {losses}")
        # the step just ran, so this compile is a persistent-cache read
        hlo = ddp._train_step.lower(state, x0, y0).compile().as_text()
        kernels = mosaic_kernels(hlo, TRAINER_KERNELS)
        peaks = [max_memory_allocated(d) for d in pg.devices]
        _say(f"  LM {model_kw['depth']} x {model_kw['dim']} x "
             f"{model_kw['num_heads']} heads, vocab "
             f"{model_kw['vocab_size']}, T={seq_len}, batch "
             f"{per_chip_batch}/device on {pg.size()} "
             f"{dist.get_backend()} device(s): loss {losses[0]:.4f} -> "
             f"{losses[-1]:.4f} in {steps} steps; set-up {setup_s:.1f} s, "
             f"then {step_s * 1e3:.1f} ms a step")
        _say(f"  Mosaic kernels in the compiled step: {sorted(kernels)}")
        _say(f"  peak bytes per device: {peaks}")
        return {"losses": losses, "kernels": kernels, "peak_bytes": peaks}
    finally:
        dist.destroy_process_group()


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def phase_server(model_kw: dict, slots: int, requests) -> dict:
    """The LM behind SlotEngine + Scheduler + Frontend, queried over
    loopback by ServeClient from this process.  ``requests`` is a list of
    ``(prompt_len, max_new_tokens)`` — more of them than ``slots``, so
    later requests are admitted into slots freed mid-run.  Every request
    must complete; an oversized one must fail with the named error.

    Against offline ``generate()``: token identity is the CPU contract
    (tests/test_serve.py) and is counted here, but on the chip it does not
    hold bit-for-bit — the engine prefills a bucket-padded prompt into a
    max_len cache, ``generate()`` the exact prompt into a shorter one, XLA
    tiles the two differently, and among 32768 random-weight logits a
    near-tie flips now and then, after which the continuations part.  So
    the check that must hold is on logits: one plain forward over each
    served sequence, and every served token's logit within
    ``SERVE_LOGIT_TOL`` of that position's maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_dist import serve
    from tpu_dist.models import TransformerLM

    if len(requests) <= slots:
        raise ValueError("need more requests than slots to exercise "
                         "continuous batching")
    model = TransformerLM(**model_kw)
    params = model.init(jax.random.key(0))
    vocab, max_len = model_kw["vocab_size"], model_kw["max_seq_len"]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n, _ in requests]

    t0 = time.perf_counter()
    engine = serve.SlotEngine(model, params, num_slots=slots,
                              max_len=max_len)
    sched = serve.Scheduler(engine)
    frontend = serve.Frontend(sched, port=0)
    client = serve.ServeClient("127.0.0.1", frontend.port, connect_retry=10)
    try:
        handles = [client.submit(p.tolist(), max_new_tokens=n)
                   for p, (_, n) in zip(prompts, requests)]
        served = [h.wait_done(600.0) for h in handles]
        serve_s = time.perf_counter() - t0
        stats = client.stats()

        big = client.submit(list(range(8)), max_new_tokens=max_len)
        try:
            big.wait_done(60.0)
        except serve.RequestFailedError as e:
            if e.error != "ValueError" or "slot capacity" not in e.detail:
                raise
        else:
            raise AssertionError("oversized request was not refused")
    finally:
        client.close()
        frontend.close()
        sched.close()

    for toks, (_, n) in zip(served, requests):
        if len(toks) != n or not all(0 <= t < vocab for t in toks):
            raise AssertionError(f"bad completion: {len(toks)} tokens of "
                                 f"{n}: {toks[:8]}...")
    if stats["completed"] != len(requests):
        raise AssertionError(f"server completed {stats['completed']} of "
                             f"{len(requests)} requests")

    t0 = time.perf_counter()
    generate = jax.jit(model.generate, static_argnums=(2,))
    n_max = max(n for _, n in requests)

    @jax.jit
    def margins(params, toks, pos, targets):
        rows = model.apply(params, toks[None])[0][pos]      # (n_max, vocab)
        return rows.max(-1) - jnp.take_along_axis(
            rows, targets[:, None], axis=1)[:, 0]

    identical, worst = 0, 0.0
    for p, (_, n), toks in zip(prompts, requests, served):
        ref = np.asarray(generate(params, jnp.asarray(p)[None], n))[0]
        identical += ref[len(p):].tolist() == toks
        # causal model: the zero padding past the sequence cannot reach
        # the positions read here
        seq = np.zeros(max_len, np.int32)
        seq[:len(p)] = p
        seq[len(p):len(p) + n] = toks
        pos = np.zeros(n_max, np.int32)
        pos[:n] = len(p) - 1 + np.arange(n)
        tgt = np.zeros(n_max, np.int32)
        tgt[:n] = toks
        worst = max(worst, float(np.max(
            np.asarray(margins(params, seq, pos, tgt))[:n])))
    ref_s = time.perf_counter() - t0
    _say(f"  {len(requests)} requests (prompts "
         f"{min(n for n, _ in requests)}-{max(n for n, _ in requests)} "
         f"tokens) over {slots} slots: all completed, "
         f"{stats['decode_steps']} decode steps at occupancy "
         f"{stats['occupancy']}; oversized request refused by name")
    _say(f"  {identical}/{len(requests)} token-identical to generate(); "
         f"every served token within {worst:.2e} of its position's max "
         f"logit in a plain forward (tol {SERVE_LOGIT_TOL:.0e}); serving "
         f"{serve_s:.1f} s, references {ref_s:.1f} s (both include "
         f"compilation)")
    if not worst <= SERVE_LOGIT_TOL:
        raise AssertionError(f"a served token sits {worst:.3e} below its "
                             f"position's max logit (tol "
                             f"{SERVE_LOGIT_TOL:.0e})")
    return {"identical": identical, "stats": stats}


# ---------------------------------------------------------------------------
# more than one chip
# ---------------------------------------------------------------------------

def phase_multichip(backend: str, model_kw: dict, per_chip_batch: int,
                    seq_len: int, lr: float, dp_first_loss: float) -> dict:
    """The dp=N first-step loss must equal one device's on the same global
    batch (N microbatches of the per-chip size, so the arithmetic per row
    block is the same); then every ``dryrun_multichip`` mesh config runs
    in-process on the real devices."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import __graft_entry__ as graft
    import tpu_dist.dist as dist

    n = len(jax.devices())
    t0 = time.perf_counter()
    dist.init_process_group(backend=backend)
    try:
        one = dist.new_group(ranks=[0])
        ddp = _lm_ddp(one, model_kw, lr, accum_steps=n)
        state = ddp.init(seed=0)
        x, y = next(_lm_batches(model_kw["vocab_size"], per_chip_batch * n,
                                seq_len, 1))
        shard = NamedSharding(one.mesh, P(one.axis_name))
        _, metrics = ddp.train_step(state, jax.device_put(x, shard),
                                    jax.device_put(y, shard))
        one_loss = float(metrics["loss"])
    finally:
        dist.destroy_process_group()
    # the loss is an f32 mean of f32 per-row losses over bf16 logits; one
    # bf16 ulp (2^-8) of slack covers any reduction-order difference
    if not abs(one_loss - dp_first_loss) <= 2 ** -8 * abs(one_loss):
        raise AssertionError(f"dp={n} first-step loss {dp_first_loss!r} != "
                             f"one-device loss {one_loss!r}")
    _say(f"  dp={n} first-step loss {dp_first_loss:.6f} vs one device "
         f"{one_loss:.6f} ({time.perf_counter() - t0:.1f} s)")

    t0 = time.perf_counter()
    graft.dryrun_multichip(n)
    _say(f"  dryrun_multichip({n}) passed on the real devices: dp, dp x sp "
         f"(ring + ring-flash), dp x tp (+ TP decode), dp x pp, dp x ep, "
         f"fsdp, dp x fsdp x tp ({time.perf_counter() - t0:.1f} s)")
    return {"one_device_loss": one_loss}


# ---------------------------------------------------------------------------

def main() -> int:
    t_start = time.perf_counter()
    _say("phase device")
    dev = device_report()
    if dev["platform"] != "tpu":
        _say(f"FAIL: JAX resolved platform {dev['platform']!r} "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
             f"script proves the system on a TPU and nothing else")
        return 1
    if dev["pallas_interpret"]:
        raise AssertionError("Pallas kernels would run interpreted on a TPU")

    _say("phase kernels")
    r = phase_kernels(
        flash=dict(batch=8, seq=2048, heads=12, head_dim=64),    # B*H = 96
        ce=dict(rows=8 * 2048, vocab=32768),
        moe=dict(tokens=8 * 2048, dim=768, experts=8, top_k=2),
        decode=dict(slots=32, heads=25, head_dim=64, max_len=1024),
        grouped=dict(slots=32, heads=4, head_dim=128, max_len=1024, group=5),
        latent=dict(slots=32, heads=64, latent=576, values=512,
                    max_len=1024),
        state=dict(slots=32, heads=32, k_dim=128, v_dim=128),
        scan=dict(seq=4096, key_heads=16, value_heads=32, k_dim=128,
                  v_dim=128),
        combine=dict(tokens=4096, top_k=10, rows=15360, dim=2048,
                     share=1 / 8))
    _say(f"phase kernels passed ({r['seconds']:.1f} s with compilation)")

    _say("phase convnet")
    phase_convnet("tpu", per_chip_batch=256, steps=20)
    _say("phase convnet passed")

    _say("phase trainer")
    lm_run = dict(model_kw=LM, per_chip_batch=8, seq_len=2048, lr=1.0)
    tr = phase_trainer("tpu", steps=6, **lm_run)
    missing = set(TRAINER_KERNELS) - tr["kernels"]
    if missing:
        raise AssertionError(f"compiled LM step lacks Mosaic kernels "
                             f"{sorted(missing)}")
    if not all(b > 0 for b in tr["peak_bytes"]):
        raise AssertionError(f"no allocator statistics on some device: "
                             f"{tr['peak_bytes']}")
    _say("phase trainer passed")

    _say("phase server")
    phase_server(LM, slots=4,
                 requests=[(5, 32), (1000, 48), (100, 64), (520, 32),
                           (5, 32), (1000, 48), (100, 64), (520, 32),
                           (100, 64), (5, 32)])
    _say("phase server passed")

    if dev["count"] >= 4:
        _say("phase multichip")
        phase_multichip("tpu", dp_first_loss=tr["losses"][0], **lm_run)
        _say("phase multichip passed")
    else:
        _say(f"phase multichip skipped: {dev['count']} device(s)")

    _say(f"all phases passed in {time.perf_counter() - t_start:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
