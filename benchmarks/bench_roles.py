"""Benchmark: role-graph channel throughput + actor→learner step rate.

Two quantities for the ``tpu_dist.roles`` subsystem (docs/roles.md):

- **Channel throughput** (MB/s × payload size × depth × path): a
  single-producer/single-consumer queue channel moving float32 payloads
  through an in-process rig — the ``store`` path (sealed pickled
  payloads through the control-plane server) and the ``dataplane`` path
  (raw CRC'd frames over rank↔rank sockets, envelope on the store).
  Depth shows the backpressure cost: depth 1 serializes producer and
  consumer, depth 8 pipelines them.
- **Actor→learner end-to-end step rate**: the spawned
  ``examples/actor_learner.py`` graph (1 learner + N actors over the
  role launcher), reporting the learner's steady-state steps/s — the
  whole-subsystem number: channel claims, dp frames, bucketed grad
  application, parameter republication.

Output: one BENCH JSON row per cell to stdout + ``BENCH_ROLES.json``::

    {"metric": "roles_channel_mb_s", "path": "dataplane",
     "payload_bytes": 8388608, "depth": 8, "value": 312.4, "unit": "MB/s"}

``--smoke`` runs two small cells per path with a payload-equality
cross-check and no spawned graph — wired as a tier-1 gate
(tests/test_roles.py).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

_SMOKE_SIZES = (64 * 1024, 1 << 20)
_FULL_SIZES = (64 * 1024, 1 << 20, 8 << 20)
_DEPTHS = (1, 8)


def _channel_pair(store, name, depth, dps=None):
    from tpu_dist.roles import Channel, ChannelSpec
    spec = ChannelSpec(name, src="prod", dst="cons", depth=depth)
    # dp=False pins the store path: an in-process rig's lazy singleton
    # belongs to one rank only, and the store cells must measure the
    # store, not whatever the data plane happens to route
    prod = Channel(spec, store, rank=0, role="prod", src_span=[0],
                   dst_span=[1], generation=0, graph_world=2,
                   dp=dps[0] if dps else False)
    cons = Channel(spec, store, rank=1, role="cons", src_span=[0],
                   dst_span=[1], generation=0, graph_world=2,
                   dp=dps[1] if dps else False)
    return prod, cons


def _throughput_cell(store, path, size, depth, n_msgs, check, dps):
    import numpy as np
    name = f"bench-{path}-{size}-{depth}"
    prod, cons = _channel_pair(store, name, depth,
                               dps if path == "dataplane" else None)
    payload = np.random.default_rng(7).standard_normal(
        max(1, size // 4)).astype(np.float32)
    errs = []

    def producer():
        try:
            for _ in range(n_msgs):
                prod.put(payload, timeout=120)
        except Exception as e:  # surfaced below: a hang here is the bug
            errs.append(e)

    t = threading.Thread(target=producer)
    t0 = time.perf_counter()
    t.start()
    got = []
    for _ in range(n_msgs):
        got.append(cons.get(timeout=120))
    dt = time.perf_counter() - t0
    t.join(timeout=30)
    if errs:
        raise errs[0]
    if check:
        assert all(np.array_equal(g, payload) for g in got), \
            f"payload corrupted on the {path} path"
        if path == "dataplane" and size >= 64 * 1024:
            assert cons.stats["dp_msgs"] == n_msgs, cons.stats
    return {"metric": "roles_channel_mb_s", "path": path,
            "payload_bytes": size, "depth": depth, "msgs": n_msgs,
            "value": round(payload.nbytes * n_msgs / dt / 1e6, 2),
            "unit": "MB/s"}


def _bench_channels(smoke: bool):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # restored on exit: run_all executes every bench in ONE process, and
    # leaking a 16 KiB threshold would silently reroute later benches'
    # eager collectives over the data plane
    prev_thr = os.environ.get("TPU_DIST_DP_THRESHOLD")
    os.environ["TPU_DIST_DP_THRESHOLD"] = str(16 * 1024)
    from tpu_dist.collectives.transport import DataPlane
    from tpu_dist.dist.store import TCPStore

    sizes = _SMOKE_SIZES if smoke else _FULL_SIZES
    n_msgs = 8 if smoke else 24
    rows = []
    store = TCPStore(is_master=True)
    dps = [DataPlane(store, 0, 2), DataPlane(store, 1, 2)]
    try:
        for path in ("store", "dataplane"):
            for size in sizes:
                for depth in _DEPTHS:
                    if smoke and depth != _DEPTHS[-1]:
                        continue  # smoke: one depth per (path, size)
                    rows.append(_throughput_cell(store, path, size, depth,
                                                 n_msgs, smoke, dps))
                    print(json.dumps(rows[-1]), flush=True)
    finally:
        for dp in dps:
            dp.close()
        store.close()
        if prev_thr is None:
            os.environ.pop("TPU_DIST_DP_THRESHOLD", None)
        else:
            os.environ["TPU_DIST_DP_THRESHOLD"] = prev_thr
    best = max((r["value"] for r in rows
                if r["path"] == "dataplane"
                and r["payload_bytes"] == sizes[-1]), default=0.0)
    rows.append({"metric": "roles_channel_dp_best_mb_s",
                 "payload_bytes": sizes[-1], "value": best,
                 "unit": "MB/s"})
    print(json.dumps(rows[-1]), flush=True)
    return rows


def _bench_e2e(actors: int, steps: int):
    """Spawn the actor/learner example through the role launcher and read
    the learner's steady-state step rate."""
    import tempfile
    out = tempfile.mkdtemp(prefix="bench_roles_")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "tpu_dist.launch",
         "--roles", f"learner:1,actor:{actors}:solo",
         os.path.join(_REPO, "examples", "actor_learner.py"),
         "--actors", str(actors), "--max-steps", str(steps),
         "--out", out],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        return {"metric": "roles_actor_learner_steps_per_sec",
                "error": (r.stderr or r.stdout)[-500:]}
    with open(os.path.join(out, "learner.json")) as f:
        learner = json.load(f)
    return {"metric": "roles_actor_learner_steps_per_sec",
            "actors": actors, "steps": learner["steps"],
            "value": round(learner["steps_per_sec"], 2),
            "unit": "steps/s",
            "dp_msgs": learner["traj_stats"]["dp_msgs"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="small cells + correctness cross-check, no "
                         "spawned graph (the tier-1 gate)")
    ap.add_argument("--actors", type=int, default=4)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--no-e2e", action="store_true")
    args = ap.parse_args(argv)

    rows = _bench_channels(args.smoke)
    if not args.smoke and not args.no_e2e:
        rows.append(_bench_e2e(args.actors, args.steps))
        print(json.dumps(rows[-1]), flush=True)
    if not args.smoke:
        with open(os.path.join(_REPO, "BENCH_ROLES.json"), "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
