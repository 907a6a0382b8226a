"""Serving through ``SlotEngine`` + ``Scheduler`` + ``Frontend`` on one chip,
queried over loopback by chipbench.loadgen in a process of its own.

Set-up builds the server, warms the decode step and the prefill buckets this
mix's prompt lengths fall into (and no others), and starts the generator.
The window is the generator's; this process sleeps through it, profiles a
slice of it in a traced run, and reads the engine's counters at its end.
Latencies are the client's, timed from the instant a request was due; the
engine's own histograms feed per-layer metrics only.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from .. import spec, traffic
from ..clock import now
from ..spans import traced_slice


def build(ctx) -> dict:
    """Model, parameters from the seed, engine, scheduler, frontend."""
    import jax
    import jax.numpy as jnp
    from tpu_dist import serve

    cfg, mix, sv = ctx.config, ctx.mix, ctx.config["serve"]
    if mix["kind"] != "requests":
        raise SystemExit(f"serve cannot run a {mix['kind']!r} mix")
    kw = spec.model_kwargs(cfg)
    model = spec.resolve(cfg["model"]["factory"])(**kw)
    dtype = jnp.dtype(sv["param_dtype"])
    # one jitted call from the seed, in the type the weights are served in
    params = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(dtype), model.init(key)))(jax.random.key(ctx.seed))
    jax.block_until_ready(params)
    ctx.mark("parameters from the seed")
    engine = serve.SlotEngine(model, params, num_slots=sv["slots"],
                              max_len=sv["max_len"],
                              cache_dtype=jnp.dtype(sv["cache_dtype"]),
                              min_bucket=sv["min_bucket"])
    if ctx.trace:
        # spans from the benchmark's side only: set on this instance
        engine.step = ctx.spans.wrap("engine.step", engine.step)
        engine._admit = ctx.spans.wrap("engine.admit", engine._admit)
    sched = serve.Scheduler(engine)
    frontend = serve.Frontend(sched, port=0)
    return {"model": model, "params": params, "engine": engine,
            "sched": sched, "frontend": frontend, "vocab": kw["vocab_size"]}


def warm_up(ctx, server: dict) -> None:
    """The decode step and the prefill buckets this mix's prompts fall into,
    and no others: one short request per bucket, all at once."""
    engine, sched = server["engine"], server["sched"]
    lo, hi = traffic.prompt_range(ctx.mix)
    buckets = [b for b in engine.buckets
               if engine.bucket_for(lo) <= b <= engine.bucket_for(hi)]
    below = lambda b: max([0] + [x for x in engine.buckets if x < b])
    warm = [sched.submit(list(range(1, below(b) + 2)), max_new_tokens=3)
            for b in buckets]
    for h in warm:
        h.wait_done(1200.0)
        ctx.mark("a warm-up request done")
    print(f"[chipbench] warmed decode and prefill buckets {buckets} of "
          f"{engine.buckets}", flush=True)


def start_generator(ctx, server: dict, mix_path: str):
    """chipbench.loadgen in a process of its own, kept off the chip."""
    return subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen",
         "--port", str(server["frontend"].port), "--mix", mix_path,
         "--seed", str(ctx.seed), "--seconds", str(ctx.seconds),
         "--slots", str(server["engine"].num_slots),
         "--vocab", str(server["vocab"])],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)


def measure(ctx, server: dict, gen) -> dict:
    """One window against a built server; returns the client's log, the
    engine's counters at the window's end and the window itself."""
    engine, mix = server["engine"], ctx.mix
    # no wait on the generator is unbounded: it is killed past this limit
    limit = 120.0 + ctx.seconds + float(mix.get("drain_s", 0)) + 60.0
    watchdog = threading.Timer(limit, gen.kill)
    watchdog.start()
    try:
        if gen.stdout.readline().strip() != "ready":
            raise RuntimeError("the load generator did not come up")
        engine.reset_stats()
        t0 = now() + 0.2
        gen.stdin.write(f"go {t0!r}\n")
        gen.stdin.flush()
        if ctx.trace:
            time.sleep(max(0.0, t0 + mix["trace_from_s"] - now()))
            with traced_slice(ctx.trace_dir):
                time.sleep(mix["trace_seconds"])
        time.sleep(max(0.0, t0 + ctx.seconds - now()))
        stats = dict(engine.stats(), scheduler=server["sched"].snapshot())
        out = gen.stdout.read()
        rc = gen.wait(timeout=30.0)
    finally:
        watchdog.cancel()
    if rc != 0 or not out.strip():
        raise RuntimeError(f"the load generator exited with code {rc}")
    log = json.loads(out.strip().splitlines()[-1])
    return {"log": log, "stats": stats, "window": (t0, t0 + ctx.seconds)}


def summarise(ctx, m: dict, vocab: int) -> dict:
    """The client's log -> counts, latencies and rates (client's clock)."""
    (t0, t1), reqs = m["window"], m["log"]["requests"]
    closed = ctx.mix["loop"] == "closed"
    for r in reqs:
        valid = all(0 <= t < vocab for t in r["tokens"])
        r["good"] = (r["status"] == "ok" and valid
                     and len(r["tokens"]) == r["n_out"])
        # cancelled by the generator when it stopped listening: a closed
        # loop's requests at the window's end (queued ones included); an
        # open loop's that still streamed at the drain limit.  Neither
        # failed; an open-loop request with no token by then did.
        r["cut"] = (r["status"] == "cancelled" and valid
                    and (closed or len(r["tokens"]) > 0))
    counted = [r for r in reqs if not (closed and r["cut"])]
    good = [r for r in counted if r["good"]]
    failed = [r for r in counted if not (r["good"] or r["cut"])]
    in_window = [r for r in good if r["end"] <= t1]
    ttft = np.array([r["recv"][0] - r["due"] for r in reqs if r["recv"]])
    itl = np.concatenate([np.diff(r["recv"]) for r in reqs] or [[]])
    late = np.array([r["sent"] - r["due"] for r in reqs])
    got = sum(sum(1 for t in r["recv"] if t <= t1) for r in reqs)
    mid = (t0 + t1) / 2
    got_late = sum(sum(1 for t in r["recv"] if mid < t <= t1) for r in reqs)
    backlog = lambda t: (sum(r["due"] <= t for r in reqs)
                         - sum(r["end"] is not None and r["end"] <= t
                               for r in reqs))
    pct = lambda a, q: float(np.percentile(a, q)) if len(a) else float("nan")
    s = {"attempted": len(counted), "failed": len(failed),
         "ttft_p50_ms": 1e3 * pct(ttft, 50), "ttft_p95_ms": 1e3 * pct(ttft, 95),
         "itl_p50_ms": 1e3 * pct(itl, 50), "itl_p95_ms": 1e3 * pct(itl, 95),
         "late_ms_p95": 1e3 * pct(late, 95),
         "serve_tokens_per_s": sum(r["n_prompt"] + r["n_out"]
                                   for r in in_window) / (t1 - t0),
         "generated_tokens_per_s": got / (t1 - t0),
         "generated_tokens_per_s_2nd_half": got_late / (t1 - mid),
         "backlog_mid": backlog(mid), "backlog_end": backlog(t1),
         "n_ttft": len(ttft), "n_itl": len(itl),
         "completed_in_window": len(in_window)}
    print(f"[chipbench] {ctx.mix['loop']} loop: {s['attempted']} requests "
          f"counted, {s['failed']} failed, {s['completed_in_window']} "
          f"completed in the window; ttft p50/p95 {s['ttft_p50_ms']:.2f}/"
          f"{s['ttft_p95_ms']:.2f} ms (n={s['n_ttft']}), gap p50/p95 "
          f"{s['itl_p50_ms']:.2f}/{s['itl_p95_ms']:.2f} ms (n={s['n_itl']}); "
          f"{s['serve_tokens_per_s']:.1f} prompt+generated tokens/s "
          f"completed, {s['generated_tokens_per_s']:.1f} generated tokens/s "
          f"received ({s['generated_tokens_per_s_2nd_half']:.1f} in the "
          f"window's second half); backlog mid/end {s['backlog_mid']}/{s['backlog_end']}; "
          f"generator late p95 {s['late_ms_p95']:.3f} ms", flush=True)
    st = m["stats"]
    ms = lambda h: f"{1e3 * h['mean']:.3f} ms x {h['count']}"
    print(f"[chipbench] engine: {st['decode_steps']} decode steps at "
          f"occupancy {st['occupancy']}; mean decode step "
          f"{ms(st['decode_step'])}, prefill {ms(st['prefill'])}, queue "
          f"{ms(st['queue'])}", flush=True)
    return s


def close(server: dict) -> None:
    server["frontend"].close()
    server["sched"].close()


def verifier(ctx, server: dict, m: dict):
    """Outside the window: a seeded sample of completed requests against the
    plain reference's full forward.  Logits, not token identity: with random
    weights the largest logit changes on rounding (PERF.md, PR 21)."""
    cfg, sv = ctx.config, ctx.config["serve"]

    def verify():
        import jax
        import jax.numpy as jnp

        # completed requests, and the tokens of those cut off while streaming
        good = [r for r in m["log"]["requests"]
                if (r["good"] or r["cut"]) and r["tokens"]]
        if not good:
            print("[chipbench] INCORRECT: no request to check", flush=True)
            return False
        server["engine"].cache = None       # the slot pool: make room
        ref = spec.load_module(spec.find(ctx.bench, "reference",
                                         cfg["reference"]))
        stacked = ref.stack_params(cfg, server["params"])

        @jax.jit
        def margins(stacked, seq, pos, served):
            rows = ref.forward(cfg, stacked, seq[None])[0][pos]
            return rows.max(-1) - jnp.take_along_axis(
                rows, served[:, None], axis=1)[:, 0]

        rng = np.random.default_rng([ctx.seed, 0xC0DE])
        picks = rng.choice(len(good), min(sv["reference_requests"],
                                          len(good)), replace=False)
        max_len, worst = sv["max_len"], 0.0
        for r in (good[i] for i in picks):
            prompt, _ = traffic.request(ctx.mix, ctx.seed, r["i"],
                                        server["vocab"])
            n = len(r["tokens"])
            # causal: the zero padding cannot reach the positions read
            seq = np.zeros(max_len, np.int32)
            seq[:len(prompt)] = prompt
            seq[len(prompt):len(prompt) + n] = r["tokens"]
            pos = np.zeros(max_len, np.int32)
            pos[:n] = len(prompt) - 1 + np.arange(n)
            served = np.zeros(max_len, np.int32)
            served[:n] = r["tokens"]
            worst = max(worst, float(np.max(np.asarray(
                margins(stacked, seq, pos, served))[:n])))
        print(f"[chipbench] {len(picks)} sampled requests: every served "
              f"token within {worst:.3e} of its position's largest logit in "
              f"the reference (tolerance {sv['logit_tol']:.1e}: "
              f"{sv['logit_tol_reason']})", flush=True)
        return worst <= sv["logit_tol"]

    return verify


def run(ctx) -> dict:
    server = build(ctx)
    gen = start_generator(ctx, server, spec.find(
        ctx.bench, "traffic", ctx.cell["traffic"] + ".json"))
    try:
        warm_up(ctx, server)
        m = measure(ctx, server, gen)
    finally:
        if gen.poll() is None:
            gen.kill()
        gen.wait()
        close(server)
    s = summarise(ctx, m, server["vocab"])
    return {"window": m["window"], "attempted": s["attempted"],
            "failed": s["failed"], "end_to_end": s,
            "counters": {"engine": m["stats"]}, "client": s,
            "verify": verifier(ctx, server, m), "close": lambda: None}
