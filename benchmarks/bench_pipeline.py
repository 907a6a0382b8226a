"""Benchmark: host-path pipeline parallelism — bubble fraction + rate.

Runs the :mod:`tpu_dist.pipeline` stage runtime in an in-process rig
(one thread per stage, real store-backed channels, dp=False pinning the
store path) and measures, per (schedule, microbatch count) cell:

- **tokens/s** over the steady-state steps (step 0 compiles and is
  excluded);
- **bubble fraction**, both the schedule's closed form
  ``(S-1)/(M+S-1)`` and the *measured* idle share
  ``1 - busy/(S * wall)`` where ``busy`` sums the stages' actual
  fwd/bwd compute time — channel claims, waits and Python overhead all
  land in the measured bubble, which is the honest number;
- **stash watermarks** per stage: GPipe stashes all M microbatch
  inputs on every stage, 1F1B caps stage *i* at ``min(S-i, M)`` — the
  memory claim the stage runtime asserts.

Output: one BENCH JSON row per cell to stdout + ``BENCH_PIPELINE.json``::

    {"metric": "pipeline_host_tokens_per_sec", "schedule": "1f1b",
     "stages": 2, "microbatches": 8, "value": 1234.5, "unit": "tokens/s",
     "bubble_theoretical": 0.111, "bubble_measured": 0.31, ...}

``--smoke`` is the tier-1 parity gate (tests/test_pipeline_host.py): one
tiny cell per schedule plus the serial oracle, asserting GPipe == 1F1B
== serial loss-bitwise AND 1F1B's stage-0 stash peak strictly below
GPipe's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

VOCAB, DIM, DEPTH, HEADS = 31, 16, 4, 2
SEQ = 12


def _batch(step: int, batch: int):
    import numpy as np
    rng = np.random.default_rng(1_000_003 * step + 1)
    x = rng.integers(0, VOCAB, size=(batch, SEQ), dtype=np.int32)
    y = rng.integers(0, VOCAB, size=(batch, SEQ), dtype=np.int32)
    return x, y


def _timed(fn, busy, stage):
    """Wrap a stage fn to accumulate its blocked compute time — the
    numerator of the measured busy fraction."""
    if fn is None:
        return None
    import jax

    def f(*a):
        t0 = time.perf_counter()
        r = jax.block_until_ready(fn(*a))
        busy[stage] += time.perf_counter() - t0
        return r
    return f


def run_cell(schedule: str, num_stages: int, num_microbatches: int,
             steps: int, batch: int, compress=None):
    """One threaded pipeline run; returns (losses, rate/bubble row)."""
    import jax

    from tpu_dist import nn, optim
    from tpu_dist.dist.store import TCPStore
    from tpu_dist.models import TransformerLM
    from tpu_dist.pipeline import (PipelineStage, act_channel,
                                   build_pipeline_graph, build_stage_fns,
                                   grad_channel, partition_model,
                                   split_microbatches, stage_role)
    from tpu_dist.roles.channel import Channel

    S, M = num_stages, num_microbatches
    graph = build_pipeline_graph(S, num_microbatches=M, schedule=schedule)
    specs = {c.name: c for c in graph.channels}
    store = TCPStore(is_master=True)
    busy = [0.0] * S
    stash_bytes = [0] * S
    stash_count = [0] * S
    losses: list = []
    errs: list = []
    state = {"round": 0, "t0": time.perf_counter()}

    def _round():
        # runs while every party is still parked in wait(): the busy
        # reset and the clock start cannot race the next step's compute
        state["round"] += 1
        if state["round"] == 1:  # step 0 was the compile step
            for j in range(S):
                busy[j] = 0.0
            state["t0"] = time.perf_counter()

    barrier = threading.Barrier(S + 1, action=_round)

    def stage_main(i: int):
        try:
            # per-thread model instance: nn.Module apply contexts are
            # thread-local, but path assignment is per-object
            model = TransformerLM(vocab_size=VOCAB, dim=DIM, depth=DEPTH,
                                  num_heads=HEADS, max_seq_len=SEQ)
            part = partition_model(model, S)
            fns = build_stage_fns(part, i, nn.CrossEntropyLoss())
            fns.fwd = _timed(fns.fwd, busy, i)
            fns.fwd_loss = _timed(fns.fwd_loss, busy, i)
            fns.bwd = _timed(fns.bwd, busy, i)
            fns.bwd_loss = _timed(fns.bwd_loss, busy, i)
            params = part.stage_params(model.init(jax.random.key(0)), i)
            opt = optim.SGD(lr=1e-2)
            opt_state = opt.init(params)

            def chan(name):
                spec = specs[name]
                s = int(spec.src[len("stage"):])
                d = int(spec.dst[len("stage"):])
                return Channel(spec, store, rank=i, role=stage_role(i),
                               src_span=[s], dst_span=[d], generation=0,
                               graph_world=S, dp=False)

            stage = PipelineStage(
                fns, i, S, M, schedule=schedule,
                in_act=chan(act_channel(i - 1)) if i > 0 else None,
                out_act=chan(act_channel(i)) if i < S - 1 else None,
                in_grad=chan(grad_channel(i)) if i < S - 1 else None,
                out_grad=chan(grad_channel(i - 1)) if i > 0 else None,
                compress=compress)
            for step in range(steps):
                x, y = _batch(step, batch)
                res = stage.run_step(
                    params,
                    x_mb=split_microbatches(x, M) if i == 0 else None,
                    y_mb=split_microbatches(y, M) if i == S - 1 else None)
                params, opt_state = opt.update(res.grads, opt_state,
                                               params)
                stash_bytes[i] = max(stash_bytes[i], res.stash_peak_bytes)
                stash_count[i] = max(stash_count[i], res.stash_peak_count)
                if i == S - 1:
                    losses.append(float(jax.numpy.mean(jax.numpy.stack(
                        [res.losses[k] for k in sorted(res.losses)]))))
                # barrier per step: step 0 is the compile step, the timed
                # window starts at the first post-compile barrier
                barrier.wait()
            stage.close()
        except Exception as e:
            errs.append(e)
            try:
                barrier.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=stage_main, args=(i,),
                                name=f"bench-stage{i}")
               for i in range(S)]
    for t in threads:
        t.start()
    for step in range(steps):
        barrier.wait()
    wall = time.perf_counter() - state["t0"]
    for t in threads:
        t.join(timeout=60)
    store.close()
    if errs:
        raise errs[0]
    timed_steps = steps - 1
    tokens = batch * SEQ * timed_steps
    from tpu_dist.pipeline import bubble_fraction
    row = {"metric": "pipeline_host_tokens_per_sec",
           "schedule": schedule, "stages": S, "microbatches": M,
           "value": round(tokens / wall, 1), "unit": "tokens/s",
           "bubble_theoretical": round(bubble_fraction(S, M), 4),
           "bubble_measured": round(1.0 - sum(busy) / (S * wall), 4),
           "stash_peak_bytes": stash_bytes,
           "stash_peak_count": stash_count}
    if compress:
        row["compress"] = compress
    return losses, row


def _serial_losses(num_stages, num_microbatches, steps, batch):
    from tpu_dist import nn, optim
    from tpu_dist.models import TransformerLM
    from tpu_dist.pipeline import SerialPipelineRunner

    model = TransformerLM(vocab_size=VOCAB, dim=DIM, depth=DEPTH,
                          num_heads=HEADS, max_seq_len=SEQ)
    runner = SerialPipelineRunner(model, optim.SGD(lr=1e-2),
                                  nn.CrossEntropyLoss(), num_stages,
                                  num_microbatches)
    out = []
    for step in range(steps):
        x, y = _batch(step, batch)
        out.append(runner.step(x, y))
    return out


def smoke() -> int:
    """The tier-1 gate: GPipe == 1F1B == serial oracle bitwise, and the
    1F1B stash watermark strictly below GPipe's on stage 0."""
    S, M, steps, batch = 2, 4, 3, 8
    serial = _serial_losses(S, M, steps, batch)
    gp_losses, gp = run_cell("gpipe", S, M, steps, batch)
    f1_losses, f1 = run_cell("1f1b", S, M, steps, batch)
    print(json.dumps(gp), flush=True)
    print(json.dumps(f1), flush=True)
    assert gp_losses == serial, (gp_losses, serial)
    assert f1_losses == serial, (f1_losses, serial)
    assert f1["stash_peak_bytes"][0] < gp["stash_peak_bytes"][0], (
        f"1F1B stage-0 stash {f1['stash_peak_bytes'][0]} not below "
        f"GPipe's {gp['stash_peak_bytes'][0]}")
    assert gp["stash_peak_count"][0] == M
    assert f1["stash_peak_count"][0] == min(S, M)
    print(json.dumps({"metric": "pipeline_smoke", "parity": "bitwise",
                      "losses": serial}), flush=True)
    return 0


def _full_rows(steps: int, batch: int):
    rows = []
    for schedule in ("gpipe", "1f1b"):
        for m in (2, 4, 8, 16):
            _, row = run_cell(schedule, 2, m, steps, batch)
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny parity + stash-bound gate (the tier-1 "
                         "entry); no JSON file written")
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.smoke:
        return smoke()
    rows = _full_rows(args.steps, args.batch)
    with open(os.path.join(_REPO, "BENCH_PIPELINE.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
