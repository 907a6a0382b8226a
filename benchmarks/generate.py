"""Autoregressive decode throughput — KV-cache generation on the real chip.

The serving-side rung of the LM ladder (training rows live in
transformer_lm.py): GPT-2-small TransformerLM decoding with the KV cache,
whole loop one compiled XLA program (models/transformer.py generate —
prefill advances the cache in a single forward, then lax.scan emits one
token per step).

Decode is HBM-bandwidth-bound, not MXU-bound: each generated token reads
every parameter once (plus the growing KV cache), so the ceiling is
~bandwidth / bytes-per-token.  The row therefore reports both tokens/sec
and the implied parameter-read bandwidth — the bf16 cache halves cache
traffic and is the default here.

Timing: the generate() program is dispatched once per measurement (the
scan runs on device), so host dispatch amortizes over max_new_tokens; a
long-minus-short difference cancels prefill + dispatch + readback.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _build_lm(max_seq_len: int, int8_weights: bool, dim: int = 768,
              depth: int = 12, heads: int = 12, vocab: int = 32768):
    """GPT-2-small-shaped TransformerLM with bf16 params; with
    ``int8_weights``, weight-only int8 (nn/quant.py) on Linears
    (INCLUDING the LM head — a plain nn.Linear, int8 since the r4
    recordings) and attention qkv/out.  The embedding table stays bf16
    ON PURPOSE: decode gathers one ~1.5 KB row per token (see
    _per_token_read_bytes), and an interleaved A/B measured
    ``embedding=True`` 1.38x SLOWER at batch-1 (0.328 vs 0.238 ms/token
    — int8 table gathers lower poorly on v5e), so QuantEmbedding is a
    model-size option, not a decode one."""
    import jax
    import jax.numpy as jnp

    from tpu_dist import nn
    from tpu_dist.models import TransformerLM

    model = TransformerLM(vocab_size=vocab, dim=dim, depth=depth,
                          num_heads=heads, max_seq_len=max_seq_len)
    params = model.init(jax.random.key(0))
    if int8_weights:
        model, params = nn.quantize_linear_weights(model, params,
                                                   attention=True)
    params = jax.tree.map(
        lambda a: a if a.dtype == jnp.int8 else a.astype(jnp.bfloat16),
        params)
    return model, params


def _per_token_read_bytes(model, params):
    """Bytes of parameters actually READ per decoded token: every leaf
    except embedding tables (a decode step gathers one ~d-sized row from
    each, not the (V, d) table — counting the table overstated the r4
    "implied bandwidth" figures by the table's share of bytes).
    Returns (read_bytes, total_bytes)."""
    import jax

    from tpu_dist.nn.layers import Embedding
    from tpu_dist.nn.quant import QuantEmbedding

    embed_paths = {path for path, mod in model.named_modules()
                   if isinstance(mod, (Embedding, QuantEmbedding))}
    read = total = 0
    for path, leaves in params.items():
        for arr in jax.tree.leaves(leaves):
            b = arr.size * arr.dtype.itemsize
            total += b
            if path not in embed_paths:
                read += b
    return read, total


def run(batch: int = 8, prompt_len: int = 128, gen_long: int = 256,
        gen_short: int = 32, dim: int = 768, depth: int = 12,
        heads: int = 12, vocab: int = 32768, reps: int = 5,
        int8_weights: bool = False, cache_dtype=None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if cache_dtype is None:
        cache_dtype = jnp.bfloat16

    model, params = _build_lm(prompt_len + gen_long, int8_weights,
                              dim=dim, depth=depth, heads=heads, vocab=vocab)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, prompt_len)))

    gen = jax.jit(
        lambda p, t, n: model.generate(p, t, n, cache_dtype=cache_dtype),
        static_argnums=2)

    def t_once(n):
        out = gen(params, prompt, n)
        np.asarray(out[0, -1])  # host readback = the sync
        return out

    for n in (gen_long, gen_short):
        t_once(n)  # compile + warm

    def best(n):
        b = 1e9
        for _ in range(reps):
            t0 = time.perf_counter()
            t_once(n)
            b = min(b, time.perf_counter() - t0)
        return b

    n_bytes, n_bytes_total = _per_token_read_bytes(model, params)
    d_long, d_short = best(gen_long), best(gen_short)
    diff = d_long - d_short
    sec_per_tok = diff / (gen_long - gen_short)
    # two invalidity checks on the differenced estimate: (a) the window
    # drowned in dispatch noise, (b) it implies reading the weights
    # faster than HBM (~819 GB/s on v5e) — min-over-reps under shifting
    # contention can understate the difference.  Either way the gross
    # long-run rate is a safe UNDER-estimate (still pays prefill +
    # dispatch) — report that rather than an impossible number.
    implied_bw = n_bytes / 1e9 / max(sec_per_tok, 1e-12)
    if diff < 0.1 * d_long or implied_bw > 819.0:
        sec_per_tok = d_long / gen_long
        gross = True
    else:
        gross = False
    tok_s = batch / sec_per_tok

    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    # weights-READ accounting (r5 fix): bytes a decode step actually
    # fetches — embedding tables excluded (one gathered row per token,
    # ~KB); the r4 rows divided TOTAL param bytes by the step time, which
    # overstated "implied bandwidth" by the tables' share (~24% bf16 /
    # ~31% int8 of total).  KV-cache traffic is still NOT included, so
    # the implied bandwidth stays a lower bound on total HBM traffic.
    gb_per_tok = n_bytes / 1e9
    return {
        "metric": ("transformer_lm_decode_int8_tokens_per_sec"
                   if int8_weights else
                   "transformer_lm_decode_tokens_per_sec"),
        "value": round(tok_s, 1),
        "unit": "tokens/sec (batch total, KV-cache decode)",
        "ms_per_token": round(sec_per_tok * 1e3, 3),
        "model": {"params_M": round(n_params / 1e6, 1), "depth": depth,
                  "dim": dim, "heads": heads, "vocab": vocab,
                  "cache_dtype": str(jnp.dtype(cache_dtype)),
                  "weights": "int8(linear+head+attn)+bf16embed"
                             if int8_weights else "bfloat16"},
        "batch": batch,
        "prompt_len": prompt_len,
        "implied_weight_read_gb_per_sec": round(gb_per_tok / sec_per_tok, 1),
        "weight_read_mb_per_token": round(n_bytes / 1e6, 1),
        "weight_total_mb": round(n_bytes_total / 1e6, 1),
        "gross_timing_fallback": gross,
        "n_chips": 1,
    }


def run_int8() -> dict:
    """Weight-only int8 decode (nn/quant.py) at the default batch 8 —
    there decode is no longer purely weight-bound, so int8 buys only a
    few percent; the regime where bytes convert to speed is batch-1
    latency, measured by :func:`run_latency_int8`."""
    return run(int8_weights=True)


def _latency(int8_weights: bool) -> dict:
    """Batch-1 latency configuration: long windows (512/64 tokens) keep
    the differenced estimate out of the dispatch-noise floor."""
    r = run(batch=1, gen_long=512, gen_short=64, reps=6,
            int8_weights=int8_weights)
    r["metric"] = ("transformer_lm_decode_batch1_int8_tokens_per_sec"
                   if int8_weights else
                   "transformer_lm_decode_batch1_tokens_per_sec")
    return r


def run_latency() -> dict:
    """Batch-1 bf16 decode latency: recorded 0.353 ms/token = 624.7 GB/s
    of actual weight reads (220.5 MB/token, embedding tables excluded —
    see _per_token_read_bytes; KV-cache traffic extra); see
    run_latency_int8."""
    return _latency(False)


def run_long_context_int8_cache(prompt_len: int = 7680, gen_long: int = 384,
                                gen_short: int = 48, reps: int = 6) -> dict:
    """Long-context batch-1 decode where the KV cache, not the weights,
    dominates HBM traffic (at prompt ~8k, GPT-2-small reads ~290 MB of
    bf16 cache per token vs ~136 MB of int8 weights).  The int8 cache
    (per-token-per-head scales hoisted into the score/PV matmuls,
    nn/attention.py _decode) halves the cache bytes — recorded 2.596x
    tokens/sec at prompt 7680 (a pre-PR-1 lead).  NOTE the crossover: at
    short context (<~4k) the quantize + custom-attention overhead exceeds
    the byte saving and bf16 cache is faster (measured 0.94x at 3k, 0.72x
    at 0.6k) — int8 cache is a long-context tool, which is why
    ``generate`` defaults to bf16.

    Methodology: both cache dtypes are timed INTERLEAVED in one process
    (rep of A, rep of B, ...), so minute-scale chip-sharing drift hits
    both equally — sequential whole-runs per config measured a spurious
    1.27x here before interleaving."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params = _build_lm(prompt_len + gen_long, int8_weights=True)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 32768, (1, prompt_len)))

    gens = {}
    for name, dt in (("bf16_cache", jnp.bfloat16), ("int8_cache", jnp.int8)):
        gens[name] = jax.jit(
            lambda p, t, n, dt=dt: model.generate(p, t, n, cache_dtype=dt),
            static_argnums=2)
        for n in (gen_long, gen_short):
            np.asarray(gens[name](params, prompt, n)[0, -1])  # compile+warm

    best = {name: [1e9, 1e9] for name in gens}
    for _ in range(reps):
        for name, gen in gens.items():
            for i, n in enumerate((gen_long, gen_short)):
                t0 = time.perf_counter()
                np.asarray(gen(params, prompt, n)[0, -1])
                best[name][i] = min(best[name][i],
                                    time.perf_counter() - t0)
    rows = {}
    for name, (d_long, d_short) in best.items():
        diff = d_long - d_short
        sec = diff / (gen_long - gen_short)
        # same invalidity checks as run(): a drowned or crossed difference
        # falls back to the gross long-run rate — which here ALSO pays the
        # multi-second long-prompt prefill, so flag it loudly
        gross = diff < 0.1 * d_long
        if gross:
            sec = d_long / gen_long
        rows[name] = {"ms_per_token": round(sec * 1e3, 3),
                      "tokens_per_sec": round(1.0 / sec, 1),
                      "gross_timing_fallback_incl_prefill": gross}
    flags = {name: r["gross_timing_fallback_incl_prefill"]
             for name, r in rows.items()}
    if any(flags.values()):
        # a gross-fallback rate includes the multi-second 7680-token
        # prefill (where the int8 cache buys nothing): if the flags
        # disagree the ratio compares incomparable quantities, and if
        # BOTH fell back it is prefill-dominated (~1.0x regardless of the
        # real decode speedup) — either way publish null, not a wrong
        # number
        speed = None
        note = ("speedup invalid: gross_timing_fallback rates include "
                f"prefill ({flags}); rerun under less contention")
    else:
        speed = round(rows["int8_cache"]["tokens_per_sec"]
                      / max(rows["bf16_cache"]["tokens_per_sec"], 1e-9), 3)
        note = None
    out = {
        "metric": "transformer_lm_decode_long_context_int8_cache",
        "value": rows["int8_cache"]["tokens_per_sec"],
        "unit": f"tokens/sec (batch 1, prompt {prompt_len}, int8 "
                "weights+cache)",
        "int8_cache_speedup_vs_bf16_cache": speed,
        "prompt_len": prompt_len,
        **rows,
        "n_chips": 1,
    }
    if note is not None:
        out["speedup_note"] = note
    return out


def run_prefill(batch: int = 8, prompt_len: int = 2048, reps: int = 6,
                long_k: int = 12, short_k: int = 3) -> dict:
    """Prefill throughput — the other half of serving (the decode rows
    deliberately difference prefill away; r4 verdict: no prefill number
    existed).  Times ``generate(prompt, 1)``, which is PURE prefill: one
    causal forward populates the KV cache and the single new token is
    sampled from the prefill logits themselves — the decode scan runs
    zero steps at max_new_tokens=1.

    Methodology: ``lax.scan`` of whole generate(n=1) calls with the
    prompt perturbed by the carry (XLA cannot elide re-prefills),
    long-minus-short chunks cancel dispatch+readback, min-over-reps —
    the same timing as every other row (benchmarks/timing.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    model, params = _build_lm(prompt_len + 8, int8_weights=False)
    rng = np.random.default_rng(0)
    vocab = model.vocab_size
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, prompt_len)))

    def chunk(n):
        @jax.jit
        def run_(params, prompt):
            def body(c, _):
                p = (prompt + c.astype(jnp.int32)) % vocab
                out = model.generate(params, p, 1)
                # FLOAT carry, not int: int32 `x * 0` constant-folds to 0
                # (exact), making `out` dead and letting XLA DCE the whole
                # generate out of the loop (measured: 12-chunk == 3-chunk
                # wall time); f32 `x * 0` is not foldable (NaN semantics)
                return out[0, -1].astype(jnp.float32) * 0, ()
            c, _ = lax.scan(body, jnp.float32(0), None, length=n)
            return c
        return run_

    run_long, run_short = chunk(long_k), chunk(short_k)

    def t(f):
        t0 = time.perf_counter()
        float(f(params, prompt))  # host readback = the only true sync
        return time.perf_counter() - t0

    for f in (run_long, run_short):
        t(f)
    bl = min(t(run_long) for _ in range(reps))
    bs = min(t(run_short) for _ in range(reps))
    sec = (bl - bs) / (long_k - short_k)
    gross = False
    if sec <= 0:
        sec, gross = bl / long_k, True

    # model-FLOPs accounting for one prefill forward: 2 * matmul-param
    # count * tokens (embedding gathers excluded) + causal attention
    # 4 * B * T^2 * dim per layer, halved for the causal skip NOT being
    # credited (standard flash accounting charges full T^2 — stay
    # consistent with the attention rows)
    n_matmul = sum(int(np.prod(p.shape))
                   for path, leaves in params.items()
                   if path not in ("tok", "pos")
                   for p in jax.tree.leaves(leaves)
                   if p.ndim >= 2)
    depth, dim = model.depth, model.tok.embedding_dim
    flops = (2 * n_matmul * batch * prompt_len
             + depth * 4 * batch * prompt_len * prompt_len * dim)
    return {
        "metric": "transformer_lm_prefill_tokens_per_sec",
        "value": round(batch * prompt_len / sec, 1),
        "unit": f"tokens/sec (batch {batch}, {prompt_len}-token prompt "
                "prefill through generate())",
        "prefill_ms": round(sec * 1e3, 2),
        "achieved_model_tflops": round(flops / sec / 1e12, 2),
        "batch": batch,
        "prompt_len": prompt_len,
        "gross_timing_fallback": gross,
        "n_chips": 1,
    }


def run_latency_int8() -> dict:
    """Batch-1 int8 decode latency (all matmul weights int8, LM head
    included): recorded 0.239 vs 0.353 ms/token (1.48x) after hoisting
    the per-channel scale past the matmul (nn/quant.py; the
    pre-multiplied form measured only 1.29x because XLA materialized the
    dequantized bf16 weight).  Actual weight reads 110.6 MB/token =
    462.9 GB/s — sub-ceiling, so the residual time is not weight
    bytes (KV cache + per-layer latency); see _per_token_read_bytes."""
    return _latency(True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    print(json.dumps(run()))
