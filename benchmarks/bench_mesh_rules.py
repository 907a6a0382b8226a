"""Benchmark: the unified sharding-rule plane — dp×tp vs pure-dp, world 4.

Two TPTrainer worlds over REAL socket data planes (4 endpoints on
threads), identical model/optimizer/batches, differing ONLY in the tp
factor the rule table is bound with:

- ``dp4``    — pure data parallelism (tp=1): every rank holds full
  params and all-reduces the full gradient tree per step;
- ``dp2tp2`` — the rule-table dp×tp split: each tp gang shards heads/
  mlp/vocab over ``model``, so per-rank gradient trees (and the dp ring
  that sums them) HALVE, at the cost of small per-layer activation
  all-reduces inside the tp gang.

Per cell: steady-state **steps/s** (step 0 compiles and is excluded) and
**wire bytes/step/rank** — measured tp combiner traffic
(``PlaneCombiner.bytes_sent``) plus the dp ring's analytic
``2*G*(dp-1)/dp`` (the bucketer's ring reduce-scatter + all-gather over
``G`` gradient bytes).  The headline is the wire reduction — the model is
sized so pure-dp is wire-bound (gradient bytes ≫ activation bytes) and
the dp×tp cell must cut wire ≥1.3× AND not lose steps/s; both land in
``BENCH_MESH.json``.

``--smoke`` is the tier-1 gate (tests/test_mesh_rules_bench.py):
1. rule-vs-legacy cross-check — the generated pjit specs reproduce the
   hand-written TRANSFORMER_TP_RULES literals of the pre-rule-table tree;
2. host-vs-pjit parity — the eager tp=2 engine's logits are BITWISE
   equal to the compiled mesh program under the SAME rule table.

``run()`` is the full recording: both cells and the ratio, into
``BENCH_MESH.json``.
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

# sized so pure-dp is wire-bound: G ~= 14.5 MB of f32 gradients per rank
# vs ~32 KB tp activation all-reduces per layer
VOCAB, DIM, DEPTH, HEADS, SEQ, BATCH = 4096, 256, 2, 8, 16, 4
WORLD = 4
TARGET = 1.3


def _model():
    from tpu_dist.models import TransformerLM
    return TransformerLM(vocab_size=VOCAB, dim=DIM, depth=DEPTH,
                         num_heads=HEADS, max_seq_len=SEQ)


def _loss_fn():
    from tpu_dist import nn

    def loss_fn(logits, y):
        return nn.CrossEntropyLoss()(logits.reshape(-1, VOCAB),
                                     y.reshape(-1))
    return loss_fn


def _batch(step: int):
    import numpy as np
    rng = np.random.default_rng(1_000_003 * step + 7)
    x = rng.integers(0, VOCAB, size=(BATCH, SEQ), dtype=np.int32)
    y = rng.integers(0, VOCAB, size=(BATCH, SEQ), dtype=np.int32)
    return x, y


def _grad_nbytes(params) -> int:
    import numpy as np
    return int(sum(a.nbytes for d in params.values()
                   for a in d.values() if isinstance(a, np.ndarray)))


def run_cell(tp: int, steps: int = 5):
    """One threaded world-4 TPTrainer run; returns the BENCH row."""
    import numpy as np

    from tpu_dist import optim
    from tpu_dist.collectives.topology import SubGroup
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.dist.store import TCPStore
    from tpu_dist.parallel.tensor import TPTrainer

    dp_n = WORLD // tp
    loss_fn = _loss_fn()
    store = TCPStore(is_master=True)
    planes = [DataPlane(store, r, WORLD) for r in range(WORLD)]
    trainers = [None] * WORLD
    errs: list = []
    try:
        def build(r):
            d, t = divmod(r, tp)
            try:
                # in-process threads share new_group's process-global
                # creation counters — pin the gang ids by hand
                trainers[r] = TPTrainer(
                    _model(), optim.SGD(lr=0.1), loss_fn,
                    dp=planes[r], tp=tp,
                    tp_group=SubGroup(
                        tuple(d * tp + i for i in range(tp)),
                        r, WORLD, instance=0),
                    dp_group=SubGroup(
                        tuple(i * tp + t for i in range(dp_n)),
                        r, WORLD, instance=0))
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        ths = [threading.Thread(target=build, args=(r,), daemon=True)
               for r in range(WORLD)]
        for th in ths:
            th.start()
        for th in ths:
            th.join(300)
        if errs:
            raise errs[0]

        g_bytes = _grad_nbytes(trainers[0].params)
        dp_wire = 2 * g_bytes * (dp_n - 1) // dp_n  # ring rs+ag per rank
        t_steady = None
        tp_wire0 = 0
        for step in range(steps):
            x, y = _batch(step)
            xs = np.split(x, dp_n)
            ys = np.split(y, dp_n)

            def run(r):
                d = r // tp
                try:
                    trainers[r].step(xs[d], ys[d])
                except BaseException as e:  # noqa: BLE001
                    errs.append(e)

            ths = [threading.Thread(target=run, args=(r,), daemon=True)
                   for r in range(WORLD)]
            for th in ths:
                th.start()
            for th in ths:
                th.join(300)
            if errs:
                raise errs[0]
            if step == 0:  # compile step: start the clock after it
                t_steady = time.perf_counter()
                tp_wire0 = trainers[0].tp_bytes_sent
        wall = time.perf_counter() - t_steady
        tp_wire = (trainers[0].tp_bytes_sent - tp_wire0) // (steps - 1)
        return {
            "cell": f"dp{dp_n}tp{tp}" if tp > 1 else f"dp{dp_n}",
            "world": WORLD, "dp": dp_n, "tp": tp,
            "steps_per_sec": round((steps - 1) / wall, 3),
            "grad_bytes_per_rank": g_bytes,
            "dp_ring_bytes_per_step": dp_wire,
            "tp_bytes_per_step": int(tp_wire),
            "wire_bytes_per_step": int(dp_wire + tp_wire),
        }
    finally:
        for p in planes:
            if p is not None:
                p.close()
        store.close()


def run():
    """Both cells + the headline ratio, written to BENCH_MESH.json."""
    pure = run_cell(tp=1)
    mesh = run_cell(tp=2)
    wire_ratio = pure["wire_bytes_per_step"] / \
        max(1, mesh["wire_bytes_per_step"])
    row = {
        "metric": "mesh_rules_dp_tp_wire_reduction_world4",
        "value": round(wire_ratio, 3),
        "unit": "x (pure-dp wire bytes / dp2tp2 wire bytes, per step)",
        "target": TARGET,
        "steps_per_sec_ratio": round(mesh["steps_per_sec"] /
                                     pure["steps_per_sec"], 3),
        "cells": [pure, mesh],
        "note": "one rule table drives both cells; the tp factor is the "
                "only knob turned",
    }
    out = os.path.join(_REPO, "BENCH_MESH.json")
    with open(out, "w") as f:
        json.dump(row, f, indent=1)
    return row


# ---------------------------------------------------------------------------
# --smoke: tier-1 parity gate
# ---------------------------------------------------------------------------

_SMOKE_DIMS = dict(vocab_size=64, dim=32, depth=2, num_heads=4,
                   max_seq_len=8)


def _legacy_literal_rules():
    """TRANSFORMER_TP_RULES exactly as hand-written before the rule
    table existed (gspmd.py at the PR-17 seed)."""
    from jax.sharding import PartitionSpec as P

    from tpu_dist.parallel.gspmd import PartitionRules
    return PartitionRules([
        (r"qkv_weight", P(None, "model")),
        (r"qkv_bias", P("model")),
        (r"out_weight", P("model", None)),
        (r"mlp\.0'\]\['weight", P(None, "model")),
        (r"mlp\.0'\]\['bias", P("model")),
        (r"mlp\.2'\]\['weight", P("model", None)),
        (r"\['head'\].*weight", P(None, "model")),
        (r"\['head'\].*bias", P("model")),
        (r"\['tok'\].*weight", P("model", None)),
    ])


def _smoke_layout_cross_check():
    import jax

    from tpu_dist.models import TransformerLM
    from tpu_dist.parallel.gspmd import TRANSFORMER_TP_RULES

    model = TransformerLM(**_SMOKE_DIMS)
    params = model.init(jax.random.PRNGKey(0))
    got = TRANSFORMER_TP_RULES.tree_specs(params)
    want = _legacy_literal_rules().tree_specs(params)

    def norm(spec):
        t = tuple(spec)
        while t and t[-1] is None:
            t = t[:-1]
        return t

    flat_g = jax.tree_util.tree_leaves_with_path(got)
    flat_w = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat_g) == len(flat_w)
    for (pg, sg), (pw, sw) in zip(flat_g, flat_w):
        assert pg == pw
        assert norm(sg) == norm(sw), (jax.tree_util.keystr(pg), sg, sw)
    return len(flat_g)


def _smoke_host_vs_pjit():
    """Eager tp=2 logits == compiled dp1×mp2 mesh logits, BITWISE, from
    the same rule table."""
    import jax
    import numpy as np

    from tpu_dist.models import TransformerLM
    from tpu_dist.nn.attention import attention_impl
    from tpu_dist.parallel.gspmd import TRANSFORMER_TP_RULES, shard_pytree
    from tpu_dist.parallel.mesh import get_mesh
    from tpu_dist.parallel.tensor import LocalCombiner, _TPEngine, \
        tp_shard_params

    model = TransformerLM(**_SMOKE_DIMS)
    full = model.init(jax.random.PRNGKey(0))
    full_np = {p: {n: np.asarray(a) for n, a in d.items()}
               for p, d in full.items()}
    rng = np.random.default_rng(3)
    x = rng.integers(0, _SMOKE_DIMS["vocab_size"], (2, 8), dtype=np.int32)

    # compiled mesh program under the generated rule specs
    mesh = get_mesh(dp=1, mp=2)
    sharded = shard_pytree(full, mesh, TRANSFORMER_TP_RULES)
    from jax.sharding import NamedSharding, PartitionSpec as P
    xd = jax.device_put(jax.numpy.asarray(x), NamedSharding(mesh, P()))
    with attention_impl("dense"):
        y_pjit = np.asarray(jax.jit(model.apply)(sharded, xd))

    # eager host twin over a 2-rank LocalCombiner gang
    comb = LocalCombiner(2)
    engines = [_TPEngine(model, None, comb.bound(t)) for t in range(2)]
    shards = [tp_shard_params(model, full_np, t, 2) for t in range(2)]
    outs = [None, None]
    errs: list = []

    def run(t):
        try:
            outs[t] = engines[t].forward(shards[t], x)
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ths = [threading.Thread(target=run, args=(t,), daemon=True)
           for t in range(2)]
    for th in ths:
        th.start()
    for th in ths:
        th.join(120)
    if errs:
        raise errs[0]
    assert np.array_equal(outs[0], outs[1]), "tp ranks disagree"
    assert np.array_equal(outs[0], y_pjit), \
        f"host-vs-pjit drift: max abs {np.abs(outs[0] - y_pjit).max()}"
    return y_pjit.shape


def smoke() -> None:
    leaves = _smoke_layout_cross_check()
    print(f"smoke: rule table reproduces legacy pjit specs "
          f"({leaves} leaves)  OK")
    shape = _smoke_host_vs_pjit()
    print(f"smoke: host tp=2 logits {shape} bitwise == pjit  OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 gate: layout cross-check + host-vs-pjit "
                         "bitwise parity (no timing)")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    row = run()
    for cell in row["cells"]:
        print(json.dumps(cell))
    print(json.dumps({k: v for k, v in row.items() if k != "cells"}))


if __name__ == "__main__":
    # the pjit half of --smoke needs virtual devices; set BEFORE jax loads
    if "--smoke" in sys.argv and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    main()
